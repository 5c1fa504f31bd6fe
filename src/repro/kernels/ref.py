"""Pure-jnp oracles for every Pallas kernel (the allclose references)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def zo_matmul_ref(x, w, u, mu):
    """y = x @ (W + mu*U) with U materialized explicitly."""
    wf = w.astype(jnp.float32) + jnp.float32(mu) * u.astype(jnp.float32)
    return (x.astype(jnp.float32) @ wf).astype(x.dtype)


def matmul_ref(x, w):
    return (x.astype(jnp.float32) @ w.astype(jnp.float32)).astype(x.dtype)


def zo_dual_matmul_ref(xa, xb, w, u, mu_a, mu_b, *, perturb_a=False,
                       perturb_b=True):
    """Dual probe with U materialized: one branch per (x, mu) pair."""
    ya = zo_matmul_ref(xa, w, u, mu_a) if perturb_a else matmul_ref(xa, w)
    yb = zo_matmul_ref(xb, w, u, mu_b) if perturb_b else matmul_ref(xb, w)
    return ya, yb


def flash_attention_ref(q, k, v, *, causal=True, window=0, cap=0.0,
                        scale=None):
    """Naive full-score attention with GQA/local/softcap semantics."""
    B, Sq, H, D = q.shape
    Kv = k.shape[2]
    G = H // Kv
    scale = scale if scale is not None else D ** -0.5
    qr = q.reshape(B, Sq, Kv, G, D).astype(jnp.float32)
    s = jnp.einsum("bqkgd,bskd->bkgqs", qr, k.astype(jnp.float32)) * scale
    if cap and cap > 0:
        s = cap * jnp.tanh(s / cap)
    q_pos = jnp.arange(Sq)[:, None]
    kv_pos = jnp.arange(k.shape[1])[None, :]
    mask = jnp.ones((Sq, k.shape[1]), bool)
    if causal:
        mask &= q_pos >= kv_pos
    if window and window > 0:
        mask &= (q_pos - kv_pos) < window
    s = jnp.where(mask[None, None, None], s, -2.0e38)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqs,bskd->bqkgd", p, v.astype(jnp.float32))
    return o.reshape(B, Sq, H, v.shape[-1]).astype(q.dtype)


def zo_dual_flash_attention_ref(qa, qb, k, v, *, kb=None, vb=None, u=None,
                                mu_a=0.0, mu_b=0.0, perturb_a=False,
                                perturb_b=True, causal=True, window=0,
                                cap=0.0, scale=None):
    """Dual-probe attention oracle: clean + perturbed outputs from ONE
    stream definition (the same ``one()`` closure evaluates both, so the
    oracle cannot drift between streams).

    ``u`` is the materialized (H, Sq, Skv) score-noise field (see
    ``repro.kernels.ops.attn_score_field``), added to the perturbed
    stream's scores post-softcap / pre-mask; ``kb``/``vb`` give the
    b-stream its own K/V (weight-probe mode — no score noise there
    unless requested).
    """
    def one(q, kk, vv, pert, mu):
        B, Sq, H, D = q.shape
        Skv, Kv = kk.shape[1], kk.shape[2]
        G = H // Kv
        sc = scale if scale is not None else D ** -0.5
        qr = q.reshape(B, Sq, Kv, G, D).astype(jnp.float32)
        s = jnp.einsum("bqkgd,bskd->bkgqs", qr,
                       kk.astype(jnp.float32)) * sc
        if cap and cap > 0:
            s = cap * jnp.tanh(s / cap)
        if pert and u is not None:
            un = u.reshape(Kv, G, Sq, Skv)      # (H,Sq,Skv) head-major
            s = s + jnp.float32(mu) * un[None]
        q_pos = jnp.arange(Sq)[:, None]
        kv_pos = jnp.arange(Skv)[None, :]
        mask = jnp.ones((Sq, Skv), bool)
        if causal:
            mask &= q_pos >= kv_pos
        if window and window > 0:
            mask &= (q_pos - kv_pos) < window
        s = jnp.where(mask[None, None, None], s, -2.0e38)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bkgqs,bskd->bqkgd", p, vv.astype(jnp.float32))
        return o.reshape(B, Sq, H, vv.shape[-1]).astype(q.dtype)

    oa = one(qa, k, v, perturb_a, mu_a)
    ob = one(qb, kb if kb is not None else k,
             vb if vb is not None else v, perturb_b, mu_b)
    return oa, ob


def rg_lru_scan_ref(a, b):
    """Sequential reference for h_t = a_t h_{t-1} + b_t."""
    def step(h, ab):
        a_t, b_t = ab
        h = a_t * h + b_t
        return h, h

    a_t = jnp.moveaxis(a, 1, 0)
    b_t = jnp.moveaxis(b, 1, 0)
    h0 = jnp.zeros_like(a[:, 0])
    _, hs = jax.lax.scan(step, h0, (a_t, b_t))
    return jnp.moveaxis(hs, 0, 1).astype(a.dtype)
