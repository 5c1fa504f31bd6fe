"""Mixture-of-Experts FFN.

Three execution paths sharing one routing function:

* ``moe_reference`` — computes *all* experts for all tokens and combines
  with the top-k gates.  Exact (no token dropping); the tests' oracle.
* ``moe_xla``       — sort-based capacity dispatch on the global view
  (no shard_map).  Used for decode (tiny token counts) and single-device.
* ``moe_ep``        — production path: shard_map over the mesh, tokens
  sharded (batch over data axes, sequence over the model axis), experts
  sharded over the model axis (EP), expert weights FSDP-gathered
  just-in-time, dispatch/return via ``lax.all_to_all``.

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
from repro.models import layers as L
from repro.models.config import ModelConfig


def init_moe(pb: L.ParamBuilder, path: str, cfg: ModelConfig):
    m = cfg.moe
    d = cfg.d_model
    p = {
        "router": pb.param(f"{path}.router", (d, m.n_experts),
                           ("d_model", "experts"), "normal", 0.02),
        "up": pb.param(f"{path}.up", (m.n_experts, d, m.d_ff_expert),
                       ("experts", "d_model", "expert_ff"), "normal"),
        "gate": pb.param(f"{path}.gate", (m.n_experts, d, m.d_ff_expert),
                         ("experts", "d_model", "expert_ff"), "normal"),
        "down": pb.param(f"{path}.down", (m.n_experts, m.d_ff_expert, d),
                         ("experts", "expert_ff", "d_model"), "normal"),
    }
    if m.n_shared_experts:
        p["shared"] = L.init_mlp(pb, f"{path}.shared", d,
                                 m.n_shared_experts * m.d_ff_expert,
                                 gated=True)
    return p


def route(router_w, x_flat, cfg: ModelConfig):
    """x_flat: (T, d) -> gates (T, k) f32, idx (T, k) i32."""
    m = cfg.moe
    logits = (x_flat.astype(jnp.float32)
              @ router_w.astype(jnp.float32))              # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, m.top_k)
    gates = gates / jnp.maximum(jnp.sum(gates, -1, keepdims=True), 1e-9)
    return gates, idx


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
    # (T, E) combine weights
    comb = jnp.zeros((xf.shape[0], m.n_experts), jnp.float32)
    comb = jax.vmap(lambda c, i, g: c.at[i].add(g))(comb, idx, gates)
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


def moe_ffn(params, x, cfg: ModelConfig, rules: AxisRules):
    if rules.mesh is not None and x.shape[1] > 1:
        return moe_ep(params, x, cfg, rules)
    return moe_xla(params, x, cfg, rules)
