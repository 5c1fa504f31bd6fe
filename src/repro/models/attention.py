"""GQA attention: naive, blocked (flash-style, online softmax in XLA),
and decode-with-cache paths.  Supports local windows, logit soft-capping,
RoPE / M-RoPE, causal static block skipping (perf opt).
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.distributed.sharding import AxisRules, constrain
from repro.kernels import ops as O
from repro.kernels.flash_attention import FLASH_BLOCK
from repro.kernels.ops import psub
from repro.models import layers as L
from repro.models.config import ModelConfig

NEG_INF = -2.0e38
# f32 scores of a whole attention past which blocked_attention recomputes
# each q block for the gradient (8192 tokens x 16 heads take 4 GiB)
REMAT_SCORES_BYTES = 2 ** 30


def _fa_impl(cfg) -> str | None:
    """Resolve the config's forward_impl to a flash-ATTENTION kernel
    backend; None keeps the pure-XLA :func:`blocked_attention` path
    (which IS the online-softmax emulation of the kernel — the
    off-TPU "kernel" resolution for the clean stream)."""
    fi = cfg.forward_impl
    if fi == "kernel_interpret":
        return "interpret"
    if fi == "kernel" and jax.default_backend() == "tpu":
        return "pallas"
    return None


def init_attention(pb: L.ParamBuilder, path: str, cfg: ModelConfig):
    d, hd = cfg.d_model, cfg.resolved_head_dim
    return {
        "wq": L.init_dense(pb, f"{path}.wq", d, cfg.n_heads * hd,
                           "d_model", "heads", cfg.qkv_bias),
        "wk": L.init_dense(pb, f"{path}.wk", d, cfg.n_kv_heads * hd,
                           "d_model", "kv_heads", cfg.qkv_bias),
        "wv": L.init_dense(pb, f"{path}.wv", d, cfg.n_kv_heads * hd,
                           "d_model", "kv_heads", cfg.qkv_bias),
        "wo": L.init_dense(pb, f"{path}.wo", cfg.n_heads * hd, d,
                           "heads", "d_model", False),
    }


def _split_heads(x, n, hd):
    return x.reshape(x.shape[:-1] + (n, hd))


def _mask(q_pos, kv_pos, causal: bool, window: int):
    # q_pos: (..., Sq), kv_pos: (..., Skv) -> bool (..., Sq, Skv)
    m = jnp.ones(q_pos.shape + kv_pos.shape[-1:], bool)
    d = q_pos[..., :, None] - kv_pos[..., None, :]
    if causal:
        m = m & (d >= 0)
    if window > 0:
        m = m & (d < window)
    return m


# ---------------------------------------------------------------------------
# naive reference path
# ---------------------------------------------------------------------------

def naive_attention(q, k, v, *, causal=True, window=0, cap=None, scale=None,
                    q_offset=0):
    """q: (B,Sq,H,D)  k,v: (B,Skv,K,D).  Reference; materializes scores."""
    B, Sq, H, D = q.shape
    K = k.shape[2]
    G = H // K
    scale = scale if scale is not None else D ** -0.5
    qr = q.reshape(B, Sq, K, G, D)
    s = jnp.einsum("bqkgd,bskd->bkgqs", qr.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    s = L.softcap(s, cap)
    q_pos = jnp.arange(Sq) + q_offset
    kv_pos = jnp.arange(k.shape[1])
    m = _mask(q_pos, kv_pos, causal, window)
    s = jnp.where(m[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqs,bskd->bqkgd", p, v.astype(jnp.float32))
    return o.reshape(B, Sq, H, v.shape[-1]).astype(q.dtype)


# ---------------------------------------------------------------------------
# blocked flash-style path (pure XLA online softmax)
# ---------------------------------------------------------------------------

def _attend_block(q_blk, k_blk, v_blk, q_pos, kv_pos, carry, *,
                  causal, window, cap, scale, p_dtype=jnp.float32):
    """One (q_chunk x kv_chunk) tile of online-softmax attention.

    q_blk: (B,cq,K,G,D); k_blk/v_blk: (B,ck,K,D); carry=(m,l,acc) with
    m,l: (B,K,G,cq), acc: (B,cq,K,G,D).
    """
    m_prev, l_prev, acc = carry
    s = jnp.einsum("bqkgd,bskd->bkgqs", q_blk.astype(jnp.float32),
                   k_blk.astype(jnp.float32)) * scale
    s = L.softcap(s, cap)
    msk = _mask(q_pos, kv_pos, causal, window)          # (cq, ck)
    s = jnp.where(msk[None, None, None], s, NEG_INF)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))    # (B,K,G,cq)
    # guard: fully-masked rows keep m at NEG_INF -> exp underflows to 0
    p = jnp.exp(s - m_new[..., None])
    alpha = jnp.exp(m_prev - m_new)
    l_new = l_prev * alpha + jnp.sum(p, axis=-1)
    # the p matrix is the single biggest HBM tensor in the XLA attention
    # path; feeding p@v in bf16 halves its traffic (softmax state m/l
    # stays f32; the accumulator stays f32)
    pv = jnp.einsum("bkgqs,bskd->bqkgd", p.astype(p_dtype),
                    v_blk.astype(p_dtype)).astype(jnp.float32)
    acc = acc * jnp.moveaxis(alpha, 3, 1)[..., None] + pv
    return m_new, l_new, acc


def blocked_attention(q, k, v, *, causal=True, window=0, cap=None,
                      scale=None, q_chunk=1024, kv_chunk=1024,
                      causal_skip=False, q_offset=0, p_dtype=jnp.float32):
    """Flash-attention-style blocked attention in pure XLA.

    q, k: (B, S, H|K, D); v: (B, Skv, K, Dv), Dv may differ from D (MLA).
    Never materializes the (Sq, Skv) score matrix.  With
    ``causal_skip=True`` the q-block loop is unrolled in Python and each
    q block only scans the kv blocks that are not fully masked (static
    bounds) — halves FLOPs for causal, and makes local attention O(S·W).
    Where the whole score matrix would pass ``REMAT_SCORES_BYTES`` in f32,
    each q block is recomputed for the gradient, so that a backward pass
    keeps one block's scores and not all of them.
    """
    B, Sq, H, D = q.shape
    Skv, K = k.shape[1], k.shape[2]
    Dv = v.shape[-1]
    remat = B * H * Sq * Skv * 4 > REMAT_SCORES_BYTES
    G = H // K
    scale = scale if scale is not None else D ** -0.5
    cq = min(q_chunk, Sq)
    ck = min(kv_chunk, Skv)
    nq = -(-Sq // cq)
    nk = -(-Skv // ck)
    # pad to full tiles
    Sq_p, Skv_p = nq * cq, nk * ck
    qp = jnp.pad(q, ((0, 0), (0, Sq_p - Sq), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, Skv_p - Skv), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, Skv_p - Skv), (0, 0), (0, 0)))
    qp = qp.reshape(B, nq, cq, K, G, D)
    kp = kp.reshape(B, nk, ck, K, D)
    vp = vp.reshape(B, nk, ck, K, Dv)
    kv_pos_all = jnp.arange(Skv_p).reshape(nk, ck)
    # padded kv positions must never be attended: mark them far-future
    kv_valid = kv_pos_all < Skv

    def run_q_block(qi: int, kv_lo: int, kv_hi: int):
        q_blk = qp[:, qi]
        q_pos = jnp.arange(cq) + qi * cq + q_offset

        def step(carry, idx):
            k_blk = jnp.take(kp, idx, axis=1)
            v_blk = jnp.take(vp, idx, axis=1)
            kv_pos = jnp.where(kv_valid[idx], kv_pos_all[idx],
                               jnp.iinfo(jnp.int32).max // 2)
            carry = _attend_block(q_blk, k_blk, v_blk, q_pos, kv_pos, carry,
                                  causal=causal, window=window, cap=cap,
                                  scale=scale, p_dtype=p_dtype)
            return carry, None

        m0 = jnp.full((B, K, G, cq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, K, G, cq), jnp.float32)
        a0 = jnp.zeros((B, cq, K, G, Dv), jnp.float32)
        idxs = jnp.arange(kv_lo, kv_hi)
        (m, l, acc), _ = jax.lax.scan(step, (m0, l0, a0), idxs)
        l = jnp.moveaxis(l, 3, 1)[..., None]            # (B,cq,K,G,1)
        return acc / jnp.maximum(l, 1e-30)

    if causal_skip:
        outs = []
        for qi in range(nq):
            q_hi_pos = (qi + 1) * cq + q_offset          # exclusive
            q_lo_pos = qi * cq + q_offset
            hi = min(nk, -(-q_hi_pos // ck)) if causal else nk
            lo = 0
            if window > 0:
                lo = max(0, (q_lo_pos - window + 1) // ck)
            blk = functools.partial(run_q_block, qi, lo, max(hi, lo + 1))
            outs.append(jax.checkpoint(blk)() if remat else blk())
        out = jnp.stack(outs, axis=1)                    # (B,nq,cq,K,G,D)
    else:
        # scan over q blocks with full kv range
        def q_step(_, qi):
            q_blk = jnp.take(qp, qi, axis=1)
            q_pos = jnp.arange(cq) + qi * cq + q_offset

            def step(carry, idx):
                k_blk = jnp.take(kp, idx, axis=1)
                v_blk = jnp.take(vp, idx, axis=1)
                kv_pos = jnp.where(kv_valid[idx], kv_pos_all[idx],
                                   jnp.iinfo(jnp.int32).max // 2)
                return _attend_block(q_blk, k_blk, v_blk, q_pos, kv_pos,
                                     carry, causal=causal, window=window,
                                     cap=cap, scale=scale,
                                     p_dtype=p_dtype), None

            m0 = jnp.full((B, K, G, cq), NEG_INF, jnp.float32)
            l0 = jnp.zeros((B, K, G, cq), jnp.float32)
            a0 = jnp.zeros((B, cq, K, G, Dv), jnp.float32)
            (m, l, acc), _ = jax.lax.scan(step, (m0, l0, a0),
                                          jnp.arange(nk))
            l = jnp.moveaxis(l, 3, 1)[..., None]
            return None, acc / jnp.maximum(l, 1e-30)

        _, out = jax.lax.scan(jax.checkpoint(q_step) if remat else q_step,
                              None, jnp.arange(nq))
        out = jnp.moveaxis(out, 0, 1)                    # (B,nq,cq,K,G,D)

    out = out.reshape(B, Sq_p, H, Dv)[:, :Sq]
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# decode path (single new token against a cache)
# ---------------------------------------------------------------------------

def decode_attention(q, k_cache, v_cache, valid_len, *, window=0, cap=None,
                     scale=None):
    """q: (B,1,H,D); caches: (B,S,K,D) and (B,S,K,Dv); valid_len: scalar
    or (B,) ints."""
    B, _, H, D = q.shape
    S, K = k_cache.shape[1], k_cache.shape[2]
    G = H // K
    scale = scale if scale is not None else D ** -0.5
    qr = q.reshape(B, K, G, D)
    s = jnp.einsum("bkgd,bskd->bkgs", qr.astype(jnp.float32),
                   k_cache.astype(jnp.float32)) * scale
    s = L.softcap(s, cap)
    pos = jnp.arange(S)
    vl = jnp.asarray(valid_len)
    vl = vl if vl.ndim else vl[None]
    m = pos[None] < vl[:, None]                          # (B,S)
    if window > 0:
        m = m & (pos[None] >= (vl[:, None] - window))
    s = jnp.where(m[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgs,bskd->bkgd", p, v_cache.astype(jnp.float32))
    return o.reshape(B, 1, H, v_cache.shape[-1]).astype(q.dtype)


# ---------------------------------------------------------------------------
# fused ZO dual-probe dispatch
# ---------------------------------------------------------------------------

def _dual_probe_attention(q, k, v, cfg: ModelConfig, *, window: int,
                          perturb, score_probe: bool):
    """Both estimator streams through ONE fused flash pass.

    ``q`` stacks [clean; perturbed] on the leading batch axis.  In
    weight-probe mode k/v are stacked the same way and each stream
    attends its own K/V (bit-identical per stream to two separate flash
    calls, half the grid steps).  In score-probe mode k/v carry ONLY the
    clean half — both streams share every K/V load — and the perturbed
    stream adds ``mu * U(seed)`` to its pre-softmax scores, seeded per
    layer/pair by :func:`repro.kernels.ops.attn_score_seed` with the
    scan repeat index row-offsetting the canonical (reps*H*Sq, Skv)
    field.
    """
    B2 = q.shape[0] // 2
    S = q.shape[1]
    common = dict(causal=True, window=window,
                  cap=cfg.attn_softcap or 0.0, scale=cfg.attn_scale,
                  impl=perturb.impl)
    if score_probe:
        sseed = O.attn_score_seed(perturb.seeds)
        off = jnp.asarray(perturb.rep, jnp.int32) * (cfg.n_heads * S)
        oa, ob = O.zo_dual_flash_attention(
            q[:B2], q[B2:], k, v, seed=0 if sseed is None else sseed,
            mu_a=0.0, mu_b=perturb.mu, row_offset=off, perturb_a=False,
            perturb_b=sseed is not None, **common)
    else:
        oa, ob = O.zo_dual_flash_attention(
            q[:B2], q[B2:], k[:B2], v[:B2], kb=k[B2:], vb=v[B2:],
            perturb_a=False, perturb_b=False, **common)
    return jnp.concatenate([oa, ob], axis=0)


# ---------------------------------------------------------------------------
# full attention layer (proj + rope + impl dispatch + out proj)
# ---------------------------------------------------------------------------

def attention_layer(params, x, cfg: ModelConfig, rules: AxisRules, *,
                    positions=None, local: bool = False, cache=None,
                    cross_kv=None, decode: bool = False, perturb=None):
    """Returns (out, new_cache).  ``cache`` (decode mode) is a dict
    {k, v, pos}; cross_kv provides precomputed (k, v) for cross-attention.
    ``perturb`` (training-time ZO context) fuses weight noise into the
    q/k/v/o projections; unsupported combined with decode/cache/cross.
    """
    if perturb is not None:
        assert cache is None and cross_kv is None and not decode, \
            "ZO perturbed forward is a training-time path"
    B, S, _ = x.shape
    hd = cfg.resolved_head_dim
    cdt = cfg.jnp_compute_dtype()
    window = cfg.window if local else 0
    # score-probe mode: the dual probe moves from the k/v projections to
    # the pre-softmax scores — k/v come from the CLEAN half only (one
    # projection serves both streams, every K/V load shared in-kernel)
    # and wk/wv are never weight-perturbed (ops.attn_kv_seed_pred keeps
    # the estimator/replay seed streams consistent with this).
    score_probe = (perturb is not None and perturb.dual
                   and cross_kv is None and not cfg.seq_sharding
                   and getattr(cfg, "attn_probe", "weights") == "scores")
    q = _split_heads(L.dense(params["wq"], x, cdt, psub(perturb, "wq")),
                     cfg.n_heads, hd)
    if cross_kv is None:
        xkv = x[: x.shape[0] // 2] if score_probe else x
        pkv = None if score_probe else perturb
        k = _split_heads(L.dense(params["wk"], xkv, cdt, psub(pkv, "wk")),
                         cfg.n_kv_heads, hd)
        v = _split_heads(L.dense(params["wv"], xkv, cdt, psub(pkv, "wv")),
                         cfg.n_kv_heads, hd)
    else:
        k, v = cross_kv
    positions = _positions(positions, cache, decode, B, S)
    kv_positions = positions
    if score_probe and positions.ndim == 2 and positions.shape[0] == B:
        kv_positions = positions[: B // 2]      # k/v carry the clean half
    if cfg.rope_kind == "rope" and cross_kv is None:
        q = L.apply_rope(q, positions, cfg.rope_theta)
        k = L.apply_rope(k, kv_positions, cfg.rope_theta)
    elif cfg.rope_kind == "mrope" and cross_kv is None:
        pos3 = positions if positions.ndim == 3 else jnp.broadcast_to(
            positions, (3,) + positions.shape)
        kpos3 = kv_positions if kv_positions.ndim == 3 else \
            jnp.broadcast_to(kv_positions, (3,) + kv_positions.shape)
        q = L.apply_mrope(q, pos3, cfg.mrope_sections, cfg.rope_theta)
        k = L.apply_mrope(k, kpos3, cfg.mrope_sections, cfg.rope_theta)
    if cfg.seq_sharding and not decode:
        # sequence-parallel attention: q (and the online-softmax state)
        # sharded on seq over the model axis; k/v replicated (small under
        # GQA).  Removes head-replication waste and the involuntary
        # score resharding GSPMD otherwise inserts (EXPERIMENTS.md §Perf).
        q = constrain(q, rules, ("batch", "seq_model", None, None))
        k = constrain(k, rules, ("batch", None, None, None))
        v = constrain(v, rules, ("batch", None, None, None))
    else:
        q = constrain(q, rules, ("batch", None, "heads", None))
    o, new_cache = _attend(q, k, v, cfg, rules, window=window, cache=cache,
                           decode=decode, perturb=perturb,
                           cross_kv=cross_kv, score_probe=score_probe)
    o = o.reshape(B, S, cfg.n_heads * hd)
    out = L.dense(params["wo"], o, cdt, psub(perturb, "wo"))
    return constrain(out, rules, ("batch", None, None)), new_cache


def _attend(q, k, v, cfg: ModelConfig, rules: AxisRules, *, window: int,
            cache, decode: bool, perturb, cross_kv=None,
            score_probe: bool = False):
    """Attention of q (B, S, H, D) over k (.., D) and v (.., Dv) after
    the projections and positions: the decode step against the cache, the
    fused dual probe, the single-stream kernel or the blocked XLA path.
    Returns (o (B, S, H, Dv), new_cache)."""
    B, S = q.shape[:2]
    new_cache = None
    if decode:
        assert cache is not None and S == 1
        pos = cache["pos"]
        size = cache["k"].shape[1]
        if jnp.ndim(pos) == 1:
            # slot-paged cache: every request decodes at its own position.
            # Scatter the new k/v row per slot (mode="drop" silences
            # requests that ran past capacity — the engine retires them).
            slot = pos % size if window > 0 else pos
            b_ix = jnp.arange(B)
            kc = cache["k"].at[b_ix, slot].set(
                k[:, 0].astype(cache["k"].dtype), mode="drop")
            vc = cache["v"].at[b_ix, slot].set(
                v[:, 0].astype(cache["v"].dtype), mode="drop")
        else:
            slot = pos % size if window > 0 else pos
            kc = jax.lax.dynamic_update_slice_in_dim(cache["k"], k.astype(
                cache["k"].dtype), slot, axis=1)
            vc = jax.lax.dynamic_update_slice_in_dim(cache["v"], v.astype(
                cache["v"].dtype), slot, axis=1)
        new_cache = {"k": kc, "v": vc, "pos": pos + 1}
        if window > 0:
            o = decode_attention(q, kc, vc, jnp.minimum(pos + 1, size),
                                 window=0, cap=cfg.attn_softcap,
                                 scale=cfg.attn_scale)
        else:
            o = decode_attention(q, kc, vc, pos + 1, window=0,
                                 cap=cfg.attn_softcap, scale=cfg.attn_scale)
    elif cross_kv is not None:
        o = naive_attention(q, k, v, causal=False, window=0,
                            cap=cfg.attn_softcap, scale=cfg.attn_scale) \
            if cfg.attn_impl == "naive" else \
            blocked_attention(q, k, v, causal=False, window=0,
                              cap=cfg.attn_softcap, scale=cfg.attn_scale,
                              q_chunk=cfg.q_chunk, kv_chunk=cfg.kv_chunk,
                              causal_skip=False)
    else:
        causal = True
        dual = perturb is not None and perturb.dual
        fused_dual = dual and (
            score_probe or (perturb.impl != "xla"
                            and cfg.attn_impl != "naive"
                            and not cfg.seq_sharding))
        fa = None if rules.partitioned else _fa_impl(cfg)
        if fused_dual:
            # ONE fused kernel pass carries both estimator streams —
            # the dual probe no longer rides a doubled attention batch
            o = _dual_probe_attention(q, k, v, cfg, window=window,
                                      perturb=perturb,
                                      score_probe=score_probe)
        elif cfg.attn_impl == "naive":
            o = naive_attention(q, k, v, causal=causal, window=window,
                                cap=cfg.attn_softcap, scale=cfg.attn_scale)
        elif fa is not None and cache is None and not cfg.seq_sharding \
                and ((perturb is not None and not dual)
                     or (fa == "pallas"
                         and max(S, k.shape[1]) > FLASH_BLOCK)):
            # the single-stream flash kernel, differentiable: under a
            # single ZO probe, and on the chip every self-attention
            # without a cache (the server's FO update, the FO baselines)
            # longer than one tile.  Interpret mode keeps the rest on
            # XLA, whose backward it would otherwise interpret in every
            # CPU test that trains a server; so does a sequence of one
            # tile, whose f32 scores are small (GPT-2 Small's server at
            # 128 tokens ran slower on the kernel on one TPU v5e).
            o = O.flash_attention(q, k, v, causal=causal, window=window,
                                  cap=cfg.attn_softcap or 0.0,
                                  scale=cfg.attn_scale,
                                  interpret=(fa != "pallas"))
        else:
            # seq-sharded: one q block (the whole sharded seq), kv scan
            qc = q.shape[1] if cfg.seq_sharding else cfg.q_chunk
            o = blocked_attention(q, k, v, causal=causal, window=window,
                                  cap=cfg.attn_softcap, scale=cfg.attn_scale,
                                  q_chunk=qc, kv_chunk=cfg.kv_chunk,
                                  causal_skip=(cfg.causal_skip
                                               and not cfg.seq_sharding),
                                  p_dtype=jnp.dtype(cfg.attn_p_dtype))
        if cache is not None:
            # block prefill: write the prompt's k/v so decode continues
            # at pos = S (fresh caches only — assumes cache["pos"] == 0)
            new_cache = _prefill_cache(cache, k, v)
    return o, new_cache


def _positions(positions, cache, decode: bool, B: int, S: int):
    """(B, S) absolute positions: given, or counted on from the cache."""
    if positions is not None:
        return positions
    base = cache["pos"] if (cache is not None and decode) else 0
    base = jnp.asarray(base)
    if base.ndim == 1:        # slot-paged cache: per-request positions
        base = base[:, None]
    return base + jnp.arange(S)[None, :] * jnp.ones((B, 1), jnp.int32)


# ---------------------------------------------------------------------------
# multi-head latent attention (DeepSeek-V2/V3, mixer "mla")
# ---------------------------------------------------------------------------

def init_mla(pb: L.ParamBuilder, path: str, cfg: ModelConfig):
    d, H = cfg.d_model, cfg.n_heads
    dqk = cfg.qk_nope_dim + cfg.qk_rope_dim
    p = {}
    if cfg.q_lora_rank:
        p["wq_a"] = L.init_dense(pb, f"{path}.wq_a", d, cfg.q_lora_rank,
                                 "d_model", None)
        p["norm_q"] = L.init_rmsnorm(pb, f"{path}.norm_q", cfg.q_lora_rank)
        p["wq_b"] = L.init_dense(pb, f"{path}.wq_b", cfg.q_lora_rank,
                                 H * dqk, None, "heads")
    else:
        p["wq"] = L.init_dense(pb, f"{path}.wq", d, H * dqk, "d_model",
                               "heads")
    p["wkv_a"] = L.init_dense(pb, f"{path}.wkv_a", d,
                              cfg.kv_lora_rank + cfg.qk_rope_dim, "d_model",
                              None)
    p["norm_kv"] = L.init_rmsnorm(pb, f"{path}.norm_kv", cfg.kv_lora_rank)
    p["wkv_b"] = L.init_dense(pb, f"{path}.wkv_b", cfg.kv_lora_rank,
                              H * (cfg.qk_nope_dim + cfg.v_head_dim), None,
                              "heads")
    p["wo"] = L.init_dense(pb, f"{path}.wo", H * cfg.v_head_dim, d, "heads",
                           "d_model")
    return p


def mla_layer(params, x, cfg: ModelConfig, rules: AxisRules, *,
              positions=None, cache=None, decode: bool = False,
              perturb=None):
    """Multi-head latent attention; returns (out, new_cache).

    Per token: q = x Wq (or the low-rank x Wq_a -> RMSNorm -> Wq_b), a
    latent c = RMSNorm(x Wkv_a[:, :r]) of rank r and one rope key k_r =
    x Wkv_a[:, r:] shared by every head; c Wkv_b gives each head's k_nope
    and v.  Keys are [k_nope, RoPE(k_r)], queries [q_nope, RoPE(q_rope)]:
    query/key dim nope + rope, value dim v_head_dim, scale 1/sqrt(nope +
    rope).  Under a dual probe every projection is a ``zo_dual_matmul``,
    the norm scales are probed like the others, and both streams' q, k,
    v go through one ``zo_dual_flash_attention`` (weight probe).  The
    cache holds the decompressed k and v per head."""
    B, S, _ = x.shape
    H, dn, dv = cfg.n_heads, cfg.qk_nope_dim, cfg.v_head_dim
    r = cfg.kv_lora_rank
    cdt = cfg.jnp_compute_dtype()
    rms = functools.partial(L.rmsnorm, eps=cfg.norm_eps or 1e-6)
    with jax.named_scope("heron_mla"):
        if "wq" in params:
            q = L.dense(params["wq"], x, cdt, psub(perturb, "wq"))
        else:
            qa = L.dense(params["wq_a"], x, cdt, psub(perturb, "wq_a"))
            qa = L.norm_apply(rms, params["norm_q"], qa,
                              psub(perturb, "norm_q"))
            q = L.dense(params["wq_b"], qa, cdt, psub(perturb, "wq_b"))
        q = _split_heads(q, H, dn + cfg.qk_rope_dim)
        kv_a = L.dense(params["wkv_a"], x, cdt, psub(perturb, "wkv_a"))
        c = L.norm_apply(rms, params["norm_kv"], kv_a[..., :r],
                         psub(perturb, "norm_kv"))
        kv = _split_heads(L.dense(params["wkv_b"], c, cdt,
                                  psub(perturb, "wkv_b")), H, dn + dv)
        positions = _positions(positions, cache, decode, B, S)
        q_rope = L.apply_rope(q[..., dn:], positions, cfg.rope_theta)
        k_rope = L.apply_rope(kv_a[..., None, r:], positions, cfg.rope_theta)
        q = jnp.concatenate([q[..., :dn], q_rope], axis=-1)
        k_rope = jnp.broadcast_to(k_rope, (B, S, H, k_rope.shape[-1]))
        k = jnp.concatenate([kv[..., :dn], k_rope], axis=-1)
        v = kv[..., dn:]
        q = constrain(q, rules, ("batch", None, "heads", None))
        o, new_cache = _attend(q, k, v, cfg, rules, window=0, cache=cache,
                               decode=decode, perturb=perturb)
        o = o.reshape(B, S, H * dv)
        out = L.dense(params["wo"], o, cdt, psub(perturb, "wo"))
    return constrain(out, rules, ("batch", None, None)), new_cache


def _prefill_cache(cache, k, v):
    """Write a whole prompt's k/v into a (possibly ring) KV cache.

    Entry at absolute position ``p`` lands at slot ``p % size`` — the
    invariant the decode path's ring addressing (``slot = pos % size``)
    continues from.  For ``S >= size`` (local-window ring shorter than
    the prompt) only the last ``size`` entries are kept, rolled by
    ``S % size`` so slot ``(S - size + i) % size`` holds tail entry
    ``i``; for ``S < size`` it is a plain prefix write.
    """
    size = cache["k"].shape[1]
    S = k.shape[1]
    kd = k.astype(cache["k"].dtype)
    vd = v.astype(cache["v"].dtype)
    if S >= size:
        kc = jnp.roll(kd[:, -size:], S % size, axis=1)
        vc = jnp.roll(vd[:, -size:], S % size, axis=1)
    else:
        kc = jax.lax.dynamic_update_slice_in_dim(cache["k"], kd, 0, axis=1)
        vc = jax.lax.dynamic_update_slice_in_dim(cache["v"], vd, 0, axis=1)
    return {"k": kc, "v": vc, "pos": cache["pos"] + S}


def init_kv_cache(cfg: ModelConfig, batch: int, seq: int, *, local: bool,
                  per_slot: bool = False):
    """``per_slot=True`` makes ``pos`` a (batch,) vector — the slot-paged
    layout the fused decode engine uses so requests of different lengths
    coexist in one batch (see :mod:`repro.core.decode`)."""
    size = min(seq, cfg.window) if local and cfg.window > 0 else seq
    heads, dk = cfg.n_kv_heads, cfg.resolved_head_dim
    dv = dk
    if cfg.kv_lora_rank:          # MLA: decompressed k and v per head
        heads, dk = cfg.n_heads, cfg.qk_nope_dim + cfg.qk_rope_dim
        dv = cfg.v_head_dim
    dt = cfg.jnp_compute_dtype()
    return {
        "k": jnp.zeros((batch, size, heads, dk), dt),
        "v": jnp.zeros((batch, size, heads, dv), dt),
        "pos": jnp.zeros((batch,) if per_slot else (), jnp.int32),
    }
