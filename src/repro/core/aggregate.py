"""Fed-Server aggregation: FedAvg, partial participation, straggler
mitigation, and ZO seed-replay aggregation (gradient compression).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import zo as Z
from repro.kernels import ops as O


def fedavg(stacked_params, weights=None):
    """stacked_params: pytree with leading client axis N -> mean tree."""
    if weights is None:
        return jax.tree.map(lambda p: jnp.mean(p.astype(jnp.float32),
                                               axis=0).astype(p.dtype),
                            stacked_params)
    w = weights / jnp.maximum(jnp.sum(weights), 1e-9)

    def avg(p):
        wf = w.reshape((-1,) + (1,) * (p.ndim - 1)).astype(jnp.float32)
        return jnp.sum(p.astype(jnp.float32) * wf, axis=0).astype(p.dtype)

    return jax.tree.map(avg, stacked_params)


def participation_mask(key, n_clients: int, fraction: float):
    """Sample ceil(fraction*N) participants uniformly (paper Fig. 3c)."""
    k = max(1, int(round(fraction * n_clients)))
    perm = jax.random.permutation(key, n_clients)
    mask = jnp.zeros((n_clients,), jnp.float32).at[perm[:k]].set(1.0)
    return mask


def straggler_mask(key, n_clients: int, fraction: float,
                   straggler_prob: float = 0.0):
    """Deadline-based straggler mitigation: over-sample participants and
    drop simulated stragglers; aggregation weights renormalize over the
    survivors (elastic: the round proceeds with whoever reported)."""
    base = participation_mask(key, n_clients, fraction)
    if straggler_prob <= 0:
        return base
    drop = jax.random.bernoulli(jax.random.fold_in(key, 1),
                                straggler_prob, (n_clients,))
    survived = base * (1.0 - drop.astype(jnp.float32))
    # never let every participant drop: fall back to the base mask
    return jnp.where(jnp.sum(survived) > 0, survived, base)


def fedavg_masked(stacked_params, mask, prev_global):
    """FedAvg over the masked participants; non-participants contribute
    the previous global params (equivalent to weighting survivors)."""
    def avg(p, g):
        m = mask.reshape((-1,) + (1,) * (p.ndim - 1)).astype(jnp.float32)
        tot = jnp.maximum(jnp.sum(mask), 1.0)
        return (jnp.sum(p.astype(jnp.float32) * m, axis=0) / tot).astype(
            p.dtype)

    return jax.tree.map(avg, stacked_params,
                        jax.tree.map(lambda g: g[None], prev_global))


# ---------------------------------------------------------------------------
# seed-replay aggregation — the ZO gradient-compression uplink
# ---------------------------------------------------------------------------

def _resolve_replay_mesh(shard: str, mesh):
    """The mesh the client axis is partitioned over.  Default: all local
    devices on a 1-D mesh whose sole axis is ``shard``."""
    if mesh is not None:
        if shard not in mesh.shape:
            raise ValueError(
                f"replay shard axis {shard!r} not in mesh axes "
                f"{tuple(mesh.shape)}")
        return mesh
    return Mesh(np.asarray(jax.devices()), (shard,))


def _pad_leading(x, m_pad: int):
    m = x.shape[0]
    if m_pad == m:
        return x
    return jnp.pad(x, [(0, m_pad - m)] + [(0, 0)] * (x.ndim - 1))


def _apply_acc(global_params, acc):
    return jax.tree.map(
        lambda p, a: (p.astype(jnp.float32) + a).astype(p.dtype),
        global_params, acc)


def _replay_engine(global_params, tokens, scales, make_direction,
                   shard: str = "none", mesh=None, chunk=None):
    """Reconstruction engine shared by :func:`seed_replay_aggregate` and
    the async engine.

    ``tokens`` is the flattened (client, step, pair) stream of (M,)
    int32 replay seeds and ``scales`` the matching (M,) fp32
    coefficients (lr, participation mask and 1/|S| already folded
    in, so padded entries are exact no-ops at scale 0).
    ``make_direction(token, shapes)`` regenerates one direction tree; it
    receives a static ShapeDtypeStruct tree, never parameter values, so
    the same closure is legal inside ``shard_map``.

    Execution modes (composable):

    * ``shard="none"`` (default): one flat ``lax.scan`` — bit-identical
      to the historical single-device behavior.
    * ``shard=<axis>``: the token stream is padded to a device multiple
      and partitioned over mesh axis ``<axis>`` with ``shard_map``; each
      device scans only its own clients' sub-stream into a local fp32
      accumulator and the partials meet in one ``psum`` tree.  Every
      device derives directions from the same sharding-invariant token
      stream, so the result matches the flat scan up to fp32 summation
      order.
    * ``chunk=<c>``: the stream is processed ``c`` entries per device at
      a time through a donated-accumulator jitted step, so server memory
      stays O(d) + O(c) however large the cohort is.  Unsharded chunking
      continues the same scan carry and is bit-exact vs one-shot;
      sharded chunking reduces per chunk (allclose, not bitwise).
    """
    with jax.named_scope("heron_replay"):
        shapes = jax.tree.map(
            lambda p: jax.ShapeDtypeStruct(p.shape, p.dtype), global_params)

        def scan_into(acc, toks, scs):
            def step(a, ts):
                t, s = ts
                u = make_direction(t, shapes)
                return jax.tree.map(lambda ai, ul: ai + s * ul, a, u), None
            acc, _ = jax.lax.scan(step, acc, (toks, scs))
            return acc

        def zeros_acc():
            return jax.tree.map(lambda s: jnp.zeros(s.shape, jnp.float32),
                                shapes)

        m = scales.shape[0]
        if shard == "none":
            if chunk is None:
                return _apply_acc(global_params,
                                  scan_into(zeros_acc(), tokens, scales))
            n_chunks = -(-m // chunk)
            tokens = _pad_leading(tokens, n_chunks * chunk)
            scales = _pad_leading(scales, n_chunks * chunk)
            step_fn = jax.jit(scan_into, donate_argnums=0)
            acc = zeros_acc()
            for c in range(n_chunks):
                sl = slice(c * chunk, (c + 1) * chunk)
                acc = step_fn(acc, tokens[sl], scales[sl])
            return _apply_acc(global_params, acc)

        mesh = _resolve_replay_mesh(shard, mesh)
        n_sh = mesh.shape[shard]
        tok_spec = P(shard, *([None] * (tokens.ndim - 1)))

        def shard_delta(toks, scs):
            def body(tl, sl):
                acc = scan_into(zeros_acc(), tl, sl)
                return jax.tree.map(lambda a: jax.lax.psum(a, shard), acc)
            return jax.shard_map(body, mesh=mesh,
                                 in_specs=(tok_spec, P(shard)),
                                 out_specs=P(), check_vma=False)(toks, scs)

        if chunk is None:
            m_pad = -(-m // n_sh) * n_sh
            return _apply_acc(global_params,
                              shard_delta(_pad_leading(tokens, m_pad),
                                          _pad_leading(scales, m_pad)))

        per_dev = -(-m // (n_sh * chunk)) * chunk
        n_chunks = per_dev // chunk
        toks = _pad_leading(tokens, per_dev * n_sh)
        scs = _pad_leading(scales, per_dev * n_sh)
        # device-major -> chunk-major, so each chunk is one contiguous slab
        # holding `chunk` consecutive entries of every device's sub-stream
        toks = jnp.moveaxis(
            toks.reshape((n_sh, n_chunks, chunk) + toks.shape[1:]), 1, 0)
        scs = jnp.moveaxis(scs.reshape(n_sh, n_chunks, chunk), 1, 0)

        def chunk_step(acc, tc, sc):
            d = shard_delta(tc.reshape((n_sh * chunk,) + tc.shape[2:]),
                            sc.reshape(-1))
            return jax.tree.map(jnp.add, acc, d)

        step_fn = jax.jit(chunk_step, donate_argnums=0)
        acc = zeros_acc()
        for c in range(n_chunks):
            acc = step_fn(acc, toks[c], scs[c])
        return _apply_acc(global_params, acc)


def replay_token_stream(client_seeds, client_coeffs, lr: float, weights,
                        tot):
    """Flatten a cohort's lean uplinks into the (tokens, scales) stream
    :func:`_replay_engine` consumes.

    ``client_seeds``: (N,) int32 client seeds; ``client_coeffs``:
    (N, h, n_pairs);  ``weights``: (N,) fp32 per-client multipliers — the
    participation mask with any staleness weight already folded in (a
    weight of exactly 1.0 or 0.0 is a bit-exact no-op on the scales);
    ``tot``: the normalizer (participant count for FedAvg semantics).
    The pair seed is ``fold_seed(fold_seed(client_seeds[i], m), p)`` —
    ``fold_seed`` is elementwise, so all N·h·n_pairs seeds derive in two
    vectorized mixes.

    This is THE canonical flattening: the synchronous aggregator and the
    async engine (:mod:`repro.fed.async_engine`) both call it, so a
    buffered flush over the same cohort in client order produces
    bit-identical tokens and scales to the one-shot synchronous path.
    """
    n, h, n_pairs = client_coeffs.shape
    flat = jnp.arange(n * h * n_pairs)
    i_idx = flat // (h * n_pairs)
    m_idx = (flat // n_pairs) % h
    p_idx = flat % n_pairs
    tokens = O.fold_seed(O.fold_seed(
        jnp.asarray(client_seeds, jnp.int32)[i_idx], m_idx), p_idx)
    scales = (-lr * client_coeffs.reshape(-1)
              * weights[i_idx] / tot).astype(jnp.float32)
    return tokens, scales


def kernel_direction_builder(seed_pred=None):
    """``make_direction`` closure for the int32 hash-seed stream."""
    def make_direction(sp, shapes):
        return O.kernel_direction_tree(
            shapes, O.leaf_seed_tree(shapes, sp, seed_pred))

    return make_direction


def seed_replay_aggregate(global_params, client_seeds, client_coeffs,
                          lr: float, mask=None, seed_pred=None,
                          shard: str = "none", mesh=None, chunk=None):
    """Reconstruct the FedAvg'd client update from (seed, coeff) uplinks.

    client_seeds: (N,) int32 (one per client round); client_coeffs:
    (N, h, n_pairs) projected-gradient scalars for h local steps.  The
    aggregated update equals FedAvg of the clients' local ZO trajectories
    to first order in lr (exact when h==1), at an uplink cost of
    O(h·n_pairs) floats per client instead of O(d).

    The reconstruction is ONE jitted `lax.scan` over the flattened
    (client, step, pair) axis (:func:`replay_token_stream`): each
    iteration regenerates one direction from the per-layer hash stream
    the client's fused dual-probe forward generated in-kernel, adds it
    into a single fp32 accumulator tree, and the accumulator is applied
    to ``global_params`` once at the end.  Because the hash noise is
    backend- and sharding-invariant, the server regenerates
    bit-identical directions to what the clients' kernels applied.
    ``seed_pred`` selects the seeded leaves, as on the client.

    ``shard``/``mesh``/``chunk`` select the mesh-sharded and/or chunked
    execution modes of :func:`_replay_engine` — the default
    ``shard="none"``, ``chunk=None`` is the flat scan.
    """
    n = client_coeffs.shape[0]
    if mask is None:
        mask = jnp.ones((n,), jnp.float32)
    tot = jnp.maximum(jnp.sum(mask), 1.0)
    seeds, scales = replay_token_stream(client_seeds, client_coeffs, lr,
                                        mask, tot)
    make_direction = kernel_direction_builder(seed_pred)
    return _replay_engine(global_params, seeds, scales, make_direction,
                          shard=shard, mesh=mesh, chunk=chunk)


def seed_replay_aggregate_reference(global_params, client_seeds,
                                    client_coeffs, lr: float, mask=None,
                                    seed_pred=None):
    """Unvectorized (client, step, pair) loop reference for
    :func:`seed_replay_aggregate` (N·h·n_pairs full-tree Python
    dispatches — kept only as the oracle for tests)."""
    n = client_coeffs.shape[0]
    if mask is None:
        mask = jnp.ones((n,), jnp.float32)
    tot = jnp.maximum(jnp.sum(mask), 1.0)
    out = global_params
    for i in range(n):
        for m in range(client_coeffs.shape[1]):
            seed_im = O.fold_seed(client_seeds[i], jnp.int32(m))
            for p in range(client_coeffs.shape[2]):
                seed = O.fold_seed(seed_im, jnp.int32(p))
                u = O.kernel_direction_tree(
                    global_params,
                    O.leaf_seed_tree(global_params, seed, seed_pred))
                scale = -lr * client_coeffs[i, m, p] * mask[i] / tot
                out = Z.add_scaled(out, u, scale)
    return out
