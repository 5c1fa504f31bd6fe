"""Mixture-of-Experts FFN.

Paths sharing one routing function:

* ``moe_reference`` — computes *all* experts for all tokens and combines
  with the top-k gates.  Exact (no token dropping); the tests' oracle.
* ``moe_xla``       — sort-based capacity dispatch on the global view
  (no shard_map).  Used for decode (tiny token counts) and single-device.
* ``moe_ep``        — production path: shard_map over the mesh, tokens
  sharded (batch over data axes, sequence over the model axis), experts
  sharded over the model axis (EP), expert weights FSDP-gathered
  just-in-time, dispatch/return via ``lax.all_to_all``.
* ``moe_held``      — dropless, over the experts this chip holds
  (``MoECfg.n_held`` from ``expert_offset``; ``capacity_factor`` None):
  the router scores every expert, the layer computes its held experts'
  part for every (token, held expert) pair and adds the shared experts.
  Its grouped products are ``lax.ragged_dot`` (which has a gradient);
  under a dual ZO probe, ``kernels/grouped_matmul`` for both streams.

Capacity semantics match GShard/Switch: per-expert capacity
``C = ceil(T·k·cf / E)``; overflow tokens are dropped (their residual
stream passes through unchanged — plus the shared-expert branch if any).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.distributed.sharding import AxisRules, constrain
from repro.kernels import grouped_matmul as GM
from repro.kernels import ops as O
from repro.models import layers as L
from repro.models.config import ModelConfig


def init_moe(pb: L.ParamBuilder, path: str, cfg: ModelConfig):
    m = cfg.moe
    d, e = cfg.d_model, m.held
    p = {
        "router": pb.param(f"{path}.router", (d, m.n_experts),
                           ("d_model", "experts"), "normal", 0.02),
        "up": pb.param(f"{path}.up", (e, d, m.d_ff_expert),
                       ("experts", "d_model", "expert_ff"), "normal"),
        "gate": pb.param(f"{path}.gate", (e, d, m.d_ff_expert),
                         ("experts", "d_model", "expert_ff"), "normal"),
        "down": pb.param(f"{path}.down", (e, m.d_ff_expert, d),
                         ("experts", "expert_ff", "d_model"), "normal"),
    }
    if m.n_shared_experts:
        p["shared"] = L.init_mlp(pb, f"{path}.shared", d,
                                 m.n_shared_experts * m.d_ff_expert,
                                 gated=True)
    return p


def route(router_w, x_flat, cfg: ModelConfig, bias=None):
    """x_flat: (T, d) -> gates (T, k) f32, idx (T, k) i32 over all experts.

    ``scoring="softmax"``: the top-k of the softmax, renormalized.
    ``"sigmoid"`` (DeepSeek-V3's noaux_tc with one group): sigmoid scores,
    the top-k chosen on ``scores + bias`` (the selection bias, zero when
    None), the gates the chosen experts' unbiased scores, renormalized.
    Either way the gates are scaled by ``routed_scale``.  The logits are
    an f32 product at full precision."""
    m = cfg.moe
    logits = jnp.dot(x_flat.astype(jnp.float32),
                     router_w.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)  # (T, E)
    if m.scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        pick = scores if bias is None else scores + bias
        _, idx = jax.lax.top_k(pick, m.top_k)
        gates = jnp.take_along_axis(scores, idx, axis=-1)
    else:
        gates, idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), m.top_k)
    gates = gates / jnp.maximum(jnp.sum(gates, -1, keepdims=True), 1e-9)
    return gates * m.routed_scale, idx


def _capacity(n_tokens: int, cfg: ModelConfig) -> int:
    m = cfg.moe
    c = int(np.ceil(n_tokens * m.top_k * m.capacity_factor / m.n_experts))
    return max(4, -(-c // 4) * 4)


def _expert_ffn(buf, up, gate, down, cdt, activation="silu"):
    """buf: (E, C, d); expert weights (E, d, f)/(E, f, d)."""
    h_up = jnp.einsum("ecd,edf->ecf", buf.astype(cdt), up.astype(cdt))
    h_g = jnp.einsum("ecd,edf->ecf", buf.astype(cdt), gate.astype(cdt))
    act = jax.nn.silu(h_g) if activation == "silu" else jax.nn.gelu(h_g)
    return jnp.einsum("ecf,efd->ecd", act * h_up, down.astype(cdt))


# ---------------------------------------------------------------------------
def moe_reference(params, x, cfg: ModelConfig):
    """All-experts dense combine; the exact no-drop oracle."""
    B, S, d = x.shape
    cdt = cfg.jnp_compute_dtype()
    xf = x.reshape(-1, d)
    gates, idx = route(params["router"], xf, cfg)
    m = cfg.moe
    # (T, E) combine weights, of the held experts
    comb = jnp.zeros((xf.shape[0], m.n_experts), jnp.float32)
    comb = jax.vmap(lambda c, i, g: c.at[i].add(g))(comb, idx, gates)
    comb = comb[:, m.expert_offset:m.expert_offset + m.held]
    up = jnp.einsum("td,edf->tef", xf.astype(cdt), params["up"].astype(cdt))
    gt = jnp.einsum("td,edf->tef", xf.astype(cdt), params["gate"].astype(cdt))
    h = jax.nn.silu(gt) * up
    y = jnp.einsum("tef,efd->ted", h, params["down"].astype(cdt))
    out = jnp.einsum("te,ted->td", comb.astype(cdt), y)
    out = out.reshape(B, S, d)
    if "shared" in params:
        out = out + L.mlp(params["shared"], x, cfg.activation, cdt)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
def _dispatch_compute_combine(xf, gates, idx, up, gate, down, cfg,
                              a2a_axis=None):
    """Sort-based capacity dispatch on a flat token buffer.

    xf: (T, d).  If ``a2a_axis`` is set (inside shard_map), experts are
    exchanged over that mesh axis with all_to_all (EP).
    """
    T, d = xf.shape
    m = cfg.moe
    cdt = cfg.jnp_compute_dtype()
    k = m.top_k
    E = m.n_experts
    C = _capacity(T, cfg)
    e_flat = idx.reshape(-1)                               # (T*k,)
    g_flat = gates.reshape(-1)
    order = jnp.argsort(e_flat)                            # stable
    e_sorted = e_flat[order]
    tok_sorted = order // k
    g_sorted = g_flat[order]
    counts = jnp.bincount(e_flat, length=E)
    starts = jnp.concatenate([jnp.zeros(1, counts.dtype),
                              jnp.cumsum(counts)[:-1]])
    pos = jnp.arange(T * k) - starts[e_sorted]
    keep = pos < C
    slot = jnp.where(keep, e_sorted * C + pos, E * C)      # OOB => dropped
    buf = jnp.zeros((E * C, d), cdt)
    buf = buf.at[slot].add(xf[tok_sorted].astype(cdt), mode="drop")
    buf = buf.reshape(E, C, d)
    if a2a_axis is not None:
        buf = jax.lax.all_to_all(buf, a2a_axis, split_axis=0, concat_axis=1,
                                 tiled=True)               # (E/n, n*C, d)
    y = _expert_ffn(buf, up, gate, down, cdt, cfg.activation)
    if a2a_axis is not None:
        y = jax.lax.all_to_all(y, a2a_axis, split_axis=1, concat_axis=0,
                               tiled=True)                 # (E, C, d)
    yf = y.reshape(E * C, d)
    contrib = yf[jnp.minimum(slot, E * C - 1)] * (
        g_sorted * keep).astype(cdt)[:, None]
    out = jnp.zeros((T, d), cdt).at[tok_sorted].add(contrib)
    return out


def moe_xla(params, x, cfg: ModelConfig, rules: AxisRules):
    """Global-view capacity MoE (decode / single device / tests)."""
    B, S, d = x.shape
    xf = x.reshape(-1, d)
    gates, idx = route(params["router"], xf, cfg)
    out = _dispatch_compute_combine(xf, gates, idx, params["up"],
                                    params["gate"], params["down"], cfg)
    out = out.reshape(B, S, d).astype(x.dtype)
    if "shared" in params:
        out = out + L.mlp(params["shared"], x, cfg.activation,
                          cfg.jnp_compute_dtype()).astype(x.dtype)
    return out


# ---------------------------------------------------------------------------
def moe_ep(params, x, cfg: ModelConfig, rules: AxisRules):
    """Expert-parallel shard_map path (production).

    Token layout inside shard_map: batch sharded over data axes, sequence
    sharded over the model axis (so every device owns a distinct token
    slab); experts sharded over the model axis; expert weights stored
    FSDP-sharded on d_model and all-gathered just-in-time.
    """
    mesh = rules.mesh
    assert mesh is not None
    B, S, d = x.shape
    data_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    n_model = mesh.shape.get("model", 1)
    n_data = int(np.prod([mesh.shape[a] for a in data_axes])) if data_axes else 1
    if S % max(n_model, 1) != 0 or B % max(n_data, 1) != 0 or n_model == 1:
        return moe_xla(params, x, cfg, rules)
    fsdp_ok = (cfg.d_model % n_data == 0) and rules.enable_fsdp

    xspec = P(data_axes if data_axes else None, "model", None)
    wspec = (P("model", data_axes, None) if fsdp_ok
             else P("model", None, None))
    dspec = (P("model", None, data_axes) if fsdp_ok
             else P("model", None, None))

    def local_fn(router_w, up, gate, down, x_loc):
        Bl, Sl, _ = x_loc.shape
        if fsdp_ok and data_axes:
            up = jax.lax.all_gather(up, data_axes, axis=1, tiled=True)
            gate = jax.lax.all_gather(gate, data_axes, axis=1, tiled=True)
            down = jax.lax.all_gather(down, data_axes, axis=2, tiled=True)
            if cfg.remat_policy == "save_gathers":
                from jax.ad_checkpoint import checkpoint_name
                up = checkpoint_name(up, "moe_wgather")
                gate = checkpoint_name(gate, "moe_wgather")
                down = checkpoint_name(down, "moe_wgather")
        xf = x_loc.reshape(-1, d)
        gates, idx = route(router_w, xf, cfg)
        out = _dispatch_compute_combine(xf, gates, idx, up, gate, down,
                                        cfg, a2a_axis="model")
        return out.reshape(Bl, Sl, d)

    out = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(None, None), wspec, wspec, dspec, xspec),
        out_specs=xspec, check_vma=False,
    )(params["router"], params["up"], params["gate"], params["down"], x)
    out = out.astype(x.dtype)
    if "shared" in params:
        out = out + L.mlp(params["shared"], x, cfg.activation,
                          cfg.jnp_compute_dtype()).astype(x.dtype)
    return out


# ---------------------------------------------------------------------------
def _dispatch(local, held: int, k: int, starts, n_rows: int):
    """Where each (token, slot) pair's row goes in a buffer of ``n_rows``
    rows whose held expert e's group begins at ``starts[e]``, in token
    order within a group.  ``local``: (T, k) expert index relative to the
    first held one.  Returns (row_tok (n_rows,) the token of each row,
    row_live (n_rows,) whether a pair fills it, dest (T, k) each pair's
    row, n_rows for the pairs of experts not held here)."""
    e = local.reshape(-1)
    e = jnp.where((e >= 0) & (e < held), e, held)
    order = jnp.argsort(e, stable=True)
    e_sorted = e[order]
    counts = jnp.bincount(e, length=held + 1)
    first = jnp.cumsum(counts) - counts
    rank = jnp.arange(e.shape[0]) - first[e_sorted]
    starts = jnp.concatenate([starts, jnp.asarray([n_rows], starts.dtype)])
    dest_sorted = jnp.where(e_sorted < held, starts[e_sorted] + rank, n_rows)
    row_tok = jnp.zeros((n_rows,), jnp.int32).at[dest_sorted].set(
        (order // k).astype(jnp.int32), mode="drop")
    row_live = jnp.zeros((n_rows,), bool).at[dest_sorted].set(True,
                                                              mode="drop")
    dest = jnp.zeros_like(dest_sorted).at[order].set(dest_sorted)
    return row_tok, row_live, dest.reshape(local.shape)


def _gather_rows(xf, row_tok, row_live):
    return jnp.where(row_live[:, None], xf[row_tok], 0).astype(xf.dtype)


def _combine(y, dest, gates):
    """sum over a token's slots of gate * its row's output (0 for a slot
    whose expert is not held: ``dest`` past the buffer)."""
    n_rows = y.shape[0]
    rows = y[jnp.minimum(dest, n_rows - 1)].astype(jnp.float32)
    return jnp.sum(jnp.where((dest < n_rows)[..., None],
                             rows * gates[..., None], 0.0), axis=1)


def _sizes(local, held: int):
    e = local.reshape(-1)
    return jnp.bincount(jnp.where((e >= 0) & (e < held), e, held),
                        length=held + 1)[:held].astype(jnp.int32)


def _held_ffn(params, xf, gates, local, cfg: ModelConfig):
    """The held experts' part for one stream, dropless: rows grouped by
    expert, three ragged products (differentiable)."""
    m = cfg.moe
    T, k, held = xf.shape[0], m.top_k, m.held
    cdt = cfg.jnp_compute_dtype()
    n_rows = T * min(k, held)
    sizes = _sizes(local, held)
    row_tok, row_live, dest = _dispatch(local, held, k,
                                        jnp.cumsum(sizes) - sizes, n_rows)
    xs = _gather_rows(xf.astype(cdt), row_tok, row_live)

    def rd(a, w):
        return jax.lax.ragged_dot(a, w.astype(cdt), sizes,
                                  preferred_element_type=jnp.float32
                                  ).astype(cdt)

    act = jax.nn.silu if cfg.activation == "silu" else jax.nn.gelu
    h = act(rd(xs, params["gate"])) * rd(xs, params["up"])
    return _combine(rd(h, params["down"]), dest, gates)


def _held_ffn_dual(params, xa, xb, ga, la, gb, lb, cfg: ModelConfig,
                   perturb):
    """Both streams' held-expert parts through the grouped dual-probe
    kernel: one layout (``group_layout``) for the three projections.
    Returns (out_a, out_b, rows computed)."""
    m = cfg.moe
    T, k, held = xa.shape[0], m.top_k, m.held
    cdt = cfg.jnp_compute_dtype()
    d, f = params["up"].shape[1:]
    bm = GM.row_block(T, k, m.n_experts, ((d, f), (f, d)))
    n_tiles = GM.capacity_tiles(T, k, held, bm)
    n_rows = (n_tiles + 1) * bm
    sa, sb = _sizes(la, held), _sizes(lb, held)
    starts_a, starts_b, meta = GM.group_layout(
        sa, sb, bm, n_tiles, GM.pair_tiles(T, k, held, bm))
    tok_a, live_a, dest_a = _dispatch(la, held, k, starts_a, n_rows)
    tok_b, live_b, dest_b = _dispatch(lb, held, k, starts_b, n_rows)
    ya = _gather_rows(xa.astype(cdt), tok_a, live_a)
    yb = _gather_rows(xb.astype(cdt), tok_b, live_b)
    seeds = perturb.seeds

    def proj(name, a, b):
        w = params[name].astype(cdt)
        s = seeds.get(name)
        return O.zo_dual_grouped_matmul(
            a, b, w, meta, 0 if s is None else s, 0.0, perturb.mu, bm=bm,
            row_offset=jnp.asarray(perturb.rep, jnp.int32) * (
                held * w.shape[1]),
            expert_offset=m.expert_offset, impl=perturb.impl,
            perturb_b=s is not None)

    act = jax.nn.silu if cfg.activation == "silu" else jax.nn.gelu
    ua, ub = proj("up", ya, yb)
    gta, gtb = proj("gate", ya, yb)
    ya, yb = proj("down", act(gta) * ua, act(gtb) * ub)
    rows = jnp.sum(sa) + jnp.sum(sb)
    return _combine(ya, dest_a, ga), _combine(yb, dest_b, gb), rows


def moe_held(params, x, cfg: ModelConfig, perturb=None):
    """Dropless MoE over the held experts (see the module docstring):
    ``sum_{selected e held here} gate_e FFN_e(x)`` plus the shared
    experts, counted once.  Under a dual probe ``x`` stacks [clean;
    perturbed] halves; each half routes with its own router weights.
    Returns (out, rows): rows is the (token, held expert) pairs the
    grouped kernel computed, both streams, None off the dual probe."""
    m = cfg.moe
    B, S, d = x.shape
    cdt = cfg.jnp_compute_dtype()
    if perturb is not None and not perturb.dual:
        params = O.perturb_tree(params, perturb.seeds, perturb.mu,
                                perturb.rep)
        perturb = None
    rows = None
    if perturb is None:
        xf = x.reshape(-1, d)
        with jax.named_scope("heron_moe_route"):
            gates, idx = route(params["router"], xf, cfg)
        with jax.named_scope("heron_moe_experts"):
            out = _held_ffn(params, xf, gates, idx - m.expert_offset, cfg)
    else:
        if m.expert_offset:
            raise NotImplementedError(
                "the seed replay regenerates a held expert leaf from its "
                "first row; a nonzero expert_offset needs it shifted too")
        half = B // 2
        xa, xb = x[:half].reshape(-1, d), x[half:].reshape(-1, d)
        sr = perturb.seeds.get("router")
        with jax.named_scope("heron_moe_route"):
            ga, ia = route(params["router"], xa, cfg)
            wr = params["router"]
            if sr is not None:
                wr = wr.astype(jnp.float32) + jnp.asarray(
                    perturb.mu, jnp.float32) * O.leaf_noise(sr, wr.shape,
                                                            perturb.rep)
            gb, ib = route(wr, xb, cfg)
        with jax.named_scope("heron_moe_experts"):
            oa, ob, rows = _held_ffn_dual(
                params, xa, xb, ga, ia - m.expert_offset, gb,
                ib - m.expert_offset, cfg, perturb)
        out = jnp.concatenate([oa, ob], axis=0)
    out = out.reshape(B, S, d).astype(x.dtype)
    if "shared" in params:
        out = out + L.mlp(params["shared"], x, cfg.activation, cdt,
                          O.psub(perturb, "shared")).astype(x.dtype)
    return out, rows


def moe_ffn(params, x, cfg: ModelConfig, rules: AxisRules):
    if cfg.moe.capacity_factor is None:
        return moe_held(params, x, cfg)[0]
    if rules.mesh is not None and x.shape[1] > 1:
        return moe_ep(params, x, cfg, rules)
    return moe_xla(params, x, cfg, rules)
