"""jit'd wrappers over the Pallas kernels with backend dispatch, plus the
per-layer seed-derivation scheme that lets the model forward and the
server-side seed-replay agree on one noise stream.

Three ZO-matmul backends share bit-identical noise (the global-coordinate
hash stream of :mod:`repro.kernels.zo_matmul`):

* ``"pallas"``    — compiled TPU kernel (production hot path);
* ``"interpret"`` — the same kernel body interpreted on CPU (validation);
* ``"xla"``       — a pure-jnp emulation ``x @ (W + mu*U)`` with U from
  :func:`uniform_noise`.  Numerically it is the oracle the kernels are
  tested against; on CPU it is also *fast*, so it is the default
  client-forward backend off-TPU (interpret mode walks the grid in
  Python and is test-speed only).

Seed scheme (DESIGN.md §3): every parameter leaf gets
``seed_leaf = base_seed + fnv1a(pytree_path)`` (int32, wrapping), and its
noise is defined on the canonical 2-D view (prod(shape[:-1]), shape[-1])
of the leaf.  A leaf stacked along a leading scan axis (reps, K, N) is
one canonical (reps*K, N) field; rep r addresses rows [r*K, (r+1)*K) via
``row_offset`` — so per-rep kernel calls inside a ``lax.scan`` and
whole-leaf server-side replay regenerate the same direction.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import flash_attention as FA
from repro.kernels import grouped_matmul as GM
from repro.kernels import ref as REF
from repro.kernels import rg_lru as RG
from repro.kernels import zo_matmul as ZM

uniform_noise = ZM.uniform_noise
uniform_noise_at = ZM.uniform_noise_at


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def default_forward_impl() -> str:
    """Preferred client-forward backend: compiled kernel on TPU, the
    bit-equivalent jnp emulation elsewhere."""
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def _resolve(impl):
    if impl is None:
        return "pallas" if jax.default_backend() == "tpu" else "interpret"
    assert impl in ("pallas", "interpret", "xla"), impl
    return impl


def zo_matmul(x, w, seed, mu, *, row_offset=0, impl=None, **kw):
    """Fused perturbed matmul y = x @ (W + mu*U(seed)).

    ``impl=None`` keeps the kernel path (compiled on TPU, interpreted on
    CPU); ``impl="xla"`` runs the bit-equivalent jnp emulation."""
    impl = _resolve(impl)
    if impl == "xla":
        u = uniform_noise(seed, w.shape, row_offset=row_offset)
        wf = w.astype(jnp.float32) + jnp.asarray(mu, jnp.float32) * u
        return (x.astype(jnp.float32) @ wf).astype(x.dtype)
    kw.setdefault("interpret", impl == "interpret" or _interpret())
    return ZM.zo_matmul(x, w, seed, mu, row_offset=row_offset, **kw)


def zo_dual_matmul(xa, xb, w, seed, mu_a, mu_b, *, row_offset=0, impl=None,
                   perturb_a: bool = False, perturb_b: bool = True, **kw):
    """Fused dual probe (ya, yb) — both estimator evals for one read of W.
    Clean+perturbed by default; pass ``perturb_a=True, mu_b=-mu_a`` for
    the antithetic pair."""
    impl = _resolve(impl)
    if impl == "xla":
        u = uniform_noise(seed, w.shape, row_offset=row_offset)
        wf = w.astype(jnp.float32)
        wa = wf + jnp.asarray(mu_a, jnp.float32) * u if perturb_a else wf
        wb = wf + jnp.asarray(mu_b, jnp.float32) * u if perturb_b else wf
        ya = (xa.astype(jnp.float32) @ wa).astype(xa.dtype)
        yb = (xb.astype(jnp.float32) @ wb).astype(xb.dtype)
        return ya, yb
    kw.setdefault("interpret", impl == "interpret" or _interpret())
    return ZM.zo_dual_matmul(xa, xb, w, seed, mu_a, mu_b,
                             row_offset=row_offset, perturb_a=perturb_a,
                             perturb_b=perturb_b, **kw)


def zo_dual_grouped_matmul(xa, xb, w, meta, seed, mu_a, mu_b, *, bm: int,
                           row_offset=0, expert_offset=0, impl=None,
                           perturb_a: bool = False, perturb_b: bool = True):
    """Both streams' rows through their held experts ``w`` (E, K, N), in
    the tile layout ``meta`` of :func:`repro.kernels.grouped_matmul.
    group_layout`: one read of each W tile and one noise tile for both
    (see that module).  ``impl="xla"`` runs the jnp emulation, which
    leaves the rows of tiles no pair tile computes at zero."""
    impl = _resolve(impl)
    if impl == "xla":
        E, K, N = w.shape
        u = uniform_noise(seed, (E * K, N),
                          row_offset=row_offset + expert_offset * K)
        wf = w.astype(jnp.float32)
        u = u.reshape(wf.shape)

        def one(x, tiles, valid, mu, pert):
            n_tiles = x.shape[0] // bm
            tile_e = jnp.full((n_tiles,), E, jnp.int32).at[
                jnp.where(valid != 0, tiles, n_tiles)].set(meta[0],
                                                           mode="drop")
            row_e = jnp.repeat(tile_e, bm)[:, None]
            ww = wf + jnp.asarray(mu, jnp.float32) * u if pert else wf
            y = jnp.zeros((x.shape[0], N), jnp.float32)
            for e in range(E):
                y = jnp.where(row_e == e, x.astype(jnp.float32) @ ww[e], y)
            return y.astype(x.dtype)

        return (one(xa, meta[1], meta[3], mu_a, perturb_a),
                one(xb, meta[2], meta[4], mu_b, perturb_b))
    return GM.zo_dual_grouped_matmul(
        xa, xb, w, meta, seed, mu_a, mu_b, row_offset=row_offset,
        expert_offset=expert_offset, bm=bm,
        interpret=impl == "interpret" or _interpret(),
        perturb_a=perturb_a, perturb_b=perturb_b)


def zo_dual_forward(x, w, seed, mu, *, impl=None, **kw):
    """(clean, perturbed) pair for the two-point estimator from a single
    fused pass (one HBM read of W serves both)."""
    return zo_dual_matmul(x, x, w, seed, 0.0, mu, impl=impl,
                          perturb_a=False, perturb_b=True, **kw)


def zo_dual_forward_split(x, w, seed, mu, **kw):
    """The unfused baseline: two independent passes over W (clean +
    perturbed).  Kept for the before/after benchmark delta."""
    kw.setdefault("interpret", _interpret())
    clean = ZM.zo_matmul(x, w, seed, 0.0, perturb=False, **kw)
    pert = ZM.zo_matmul(x, w, seed, mu, perturb=True, **kw)
    return clean, pert


def zo_noise(w, seed, **kw):
    kw.setdefault("interpret", _interpret())
    return ZM.zo_noise(w, seed, **kw)


def flash_attention(q, k, v, **kw):
    kw.setdefault("interpret", _interpret())
    return FA.flash_attention(q, k, v, **kw)


def attn_score_field(seed, n_heads, seq_q, seq_kv, row_offset=0):
    """Materialized (H, Sq, Skv) score-noise field — the replay /
    emulation oracle of the in-kernel per-tile windows of
    :func:`repro.kernels.flash_attention.zo_dual_flash_attention`: head
    h, query row i, kv column j reads ``U[row_offset + h*Sq + i, j]`` of
    the canonical 2-D hash stream (batch-independent; stacked scan
    layers pass ``row_offset = rep * n_heads * seq_q``)."""
    u = uniform_noise(seed, (n_heads * seq_q, seq_kv),
                      row_offset=row_offset)
    return u.reshape(n_heads, seq_q, seq_kv)


def zo_dual_flash_attention(qa, qb, k, v, *, kb=None, vb=None, seed=0,
                            mu_a=0.0, mu_b=0.0, row_offset=0,
                            perturb_a=False, perturb_b=True, impl=None,
                            **kw):
    """Fused dual-probe flash attention — both estimator streams of the
    two-point ZO probe in ONE pass over the K/V blocks.

    ``kb is None`` selects the shared-KV score-probe mode (perturbation
    ``mu * U(seed)`` on the pre-softmax scores); ``kb``/``vb`` given is
    the weight-probe mode (per-stream K/V, no score noise by default).
    ``impl="xla"`` runs the pure-jnp oracle with the score field
    materialized by :func:`attn_score_field` — bit-identical noise, the
    same stream the compiled/interpret kernel generates tile-by-tile.
    """
    impl = _resolve(impl)
    if impl == "xla":
        u = None
        if perturb_a or perturb_b:
            u = attn_score_field(seed, qa.shape[2], qa.shape[1],
                                 k.shape[1], row_offset)
        return REF.zo_dual_flash_attention_ref(
            qa, qb, k, v, kb=kb, vb=vb, u=u, mu_a=mu_a, mu_b=mu_b,
            perturb_a=perturb_a, perturb_b=perturb_b,
            causal=kw.get("causal", True), window=kw.get("window", 0),
            cap=kw.get("cap", 0.0), scale=kw.get("scale"))
    kw.setdefault("interpret", impl == "interpret" or _interpret())
    return FA.zo_dual_flash_attention(
        qa, qb, k, v, kb=kb, vb=vb, seed=seed, mu_a=mu_a, mu_b=mu_b,
        row_offset=row_offset, perturb_a=perturb_a, perturb_b=perturb_b,
        **kw)


def rg_lru_scan(a, b, **kw):
    kw.setdefault("interpret", _interpret())
    return RG.rg_lru_scan(a, b, **kw)


# ===========================================================================
# per-layer seed derivation + tree-level noise utilities
# ===========================================================================

def path_hash(path: str) -> int:
    """Stable 31-bit FNV-1a hash of a '/'-joined pytree path."""
    h = 2166136261
    for ch in path.encode():
        h = ((h ^ ch) * 16777619) & 0xFFFFFFFF
    return h & 0x7FFFFFFF


def fold_seed(seed, i):
    """Derive a child int32 seed: elementwise over arrays, so one call
    folds a whole (N,) client-seed vector by a step index (the kernel
    analogue of ``jax.random.fold_in``)."""
    s = jnp.asarray(seed, jnp.int32).astype(jnp.uint32)
    x = (s ^ (jnp.asarray(i, jnp.int32).astype(jnp.uint32)
              * jnp.uint32(0x9E3779B9))) + jnp.uint32(0x7F4A7C15)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(0x2C1B3C6D)
    x = x ^ (x >> 12)
    return x.astype(jnp.int32)


def leaf_seed_tree(tree, base_seed, pred=None):
    """Per-leaf seeds ``base_seed + path_hash(path)`` mirroring ``tree``.

    ``None`` leaves of ``tree`` (frozen placeholders from
    ``core.split.partition``) and leaves rejected by ``pred(path)`` map
    to ``None`` — layers skip perturbation for them.  Paths use the same
    '/'-joined format as :func:`repro.core.split.partition`, so the same
    predicates (e.g. ``lora_pred``) apply."""
    base = jnp.asarray(base_seed, jnp.int32)

    def walk(node, path):
        if isinstance(node, dict):
            return {k: walk(v, f"{path}/{k}" if path else str(k))
                    for k, v in node.items()}
        if isinstance(node, (list, tuple)):
            return type(node)(walk(v, f"{path}/{i}" if path else str(i))
                              for i, v in enumerate(node))
        if node is None:
            return None
        if pred is not None and not pred(path):
            return None
        return base + jnp.int32(path_hash(path))

    return walk(tree, "")


# score-probe seed scheme: the per-layer score field's seed is derived
# from the layer's wq leaf seed by folding a fixed salt, so it rides the
# exact (base_seed, pair, path) stream weight leaves use without needing
# its own entry in the seeds tree.
ATTN_SCORE_SALT = path_hash("attn/scores")


def attn_score_seed(seeds):
    """Per-layer score-field seed for the shared-KV score probe:
    ``fold_seed(seed(wq/w), ATTN_SCORE_SALT)``; None when wq is not
    ZO-seeded (frozen / LoRA-only layers skip the score probe)."""
    if not isinstance(seeds, dict):
        return None
    sw = seeds.get("wq")
    sw = sw.get("w") if isinstance(sw, dict) else None
    if sw is None:
        return None
    return fold_seed(sw, ATTN_SCORE_SALT)


def attn_kv_seed_pred(path: str) -> bool:
    """Seed predicate for ``attn_probe="scores"``: attention k/v
    projections are NOT weight-perturbed (both streams attend k/v from
    the clean half; the probe moves to the score field instead), so
    their leaves must be excluded from BOTH the client's forward seeds
    and the server's replay — same predicate on both sides keeps the
    lean uplink exact.  Module-level so it hashes stably across the jit
    caches keyed on it."""
    return "attn/wk/" not in path and "attn/wv/" not in path


def any_seed(seeds) -> bool:
    if seeds is None:
        return False
    if isinstance(seeds, dict):
        return any(any_seed(v) for v in seeds.values())
    if isinstance(seeds, (list, tuple)):
        return any(any_seed(v) for v in seeds)
    return True


def leaf_noise(seed, shape, rep=0):
    """U(seed) for one (possibly rep-sliced) leaf on its canonical 2-D
    view (prod(shape[:-1]), shape[-1]); ``rep`` offsets the rows for a
    leaf sliced out of a stacked (reps, ...) scan parameter."""
    shape = tuple(int(s) for s in shape) or (1,)
    cols = shape[-1]
    rows = int(np.prod(shape[:-1])) if len(shape) > 1 else 1
    off = jnp.asarray(rep, jnp.int32) * rows
    return uniform_noise(seed, (rows, cols), row_offset=off).reshape(shape)


def kernel_direction_tree(params, seeds):
    """Materialized f32 direction U for a whole tree: the replay-side
    oracle of the in-kernel stream (None seed -> zeros)."""
    def walk(p, s):
        if isinstance(p, dict):
            return {k: walk(v, None if s is None else s[k])
                    for k, v in p.items()}
        if isinstance(p, (list, tuple)):
            return type(p)(walk(v, None if s is None else s[i])
                           for i, v in enumerate(p))
        if p is None:
            return None
        if s is None:
            return jnp.zeros(p.shape, jnp.float32)
        return leaf_noise(s, p.shape)

    return walk(params, seeds)


def perturb_tree(params, seeds, mu, rep=0):
    """theta + mu*U(seeds) with U materialized per leaf — the generic
    XLA fallback for layers without a fused kernel lowering (and the
    whole-tree single-probe reference)."""
    def walk(p, s):
        if s is None:
            return p
        if isinstance(p, dict):
            return {k: walk(v, s[k]) for k, v in p.items()}
        if isinstance(p, (list, tuple)):
            return type(p)(walk(v, s[i]) for i, v in enumerate(p))
        if p is None:
            return None
        u = leaf_noise(s, p.shape, rep)
        return (p.astype(jnp.float32)
                + jnp.asarray(mu, jnp.float32) * u).astype(p.dtype)

    return walk(params, seeds)


@dataclasses.dataclass(frozen=True)
class Perturb:
    """Perturbation context threaded through the client forward.

    ``seeds`` mirrors the layer's param subtree (int32 scalars / None);
    ``dual=True`` means activations carry [clean; perturbed] halves
    stacked along the leading batch axis — parametric call sites split
    the halves, everything else runs unchanged on the doubled batch.
    ``rep`` is the scan-segment repeat index (row offset into stacked
    leaves).  ``impl`` picks the matmul backend (see module docstring).
    """
    seeds: Any
    mu: Any
    rep: Any = 0
    dual: bool = False
    impl: str = "xla"


def psub(perturb: Perturb | None, key):
    """Narrow a Perturb to a child subtree; None when nothing under
    ``key`` is seeded (callers then take the plain path)."""
    if perturb is None or perturb.seeds is None:
        return None
    s = perturb.seeds
    sub = s.get(key) if isinstance(s, dict) else s[key]
    if not any_seed(sub):
        return None
    return dataclasses.replace(perturb, seeds=sub)
