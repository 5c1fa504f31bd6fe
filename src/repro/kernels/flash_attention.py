"""Pallas TPU kernels: blocked flash attention (online softmax) with GQA,
causal masking, local windows, and gemma2-style logit soft-capping —
single-stream, plus the fused ZO dual-probe variant
:func:`zo_dual_flash_attention` that carries the clean and ±mu-perturbed
streams of the two-point estimator through ONE sequential pass over the
K/V blocks.

Grid: (B * H, nq, nk) — the kv loop innermost; m/l/acc live in VMEM
scratch and persist across kv steps (sequential TPU grid).  The kv-head
BlockSpec index map folds the GQA group: q head h reads kv head
h // (H // Kv).

The dual kernel keeps TWO (m, l, acc) scratch sets and shares, per grid
step, the K/V VMEM loads, the position iotas, and the mask between both
streams; in score-probe mode (``kb is None``) the perturbed stream
additionally reads the SAME K/V blocks as the clean one and instead adds
``mu * U(seed)`` to its pre-softmax scores, with U drawn from the exact
global-coordinate hash stream of :mod:`repro.kernels.zo_matmul`
(block-size invariant, bit-identical compiled / interpret / pure-jnp) on
the canonical 2-D field (n_heads * Sq, Skv): head h, query row i, kv
column j reads ``U[row_offset + h*Sq + i, j]`` — so the server can
regenerate the field from ``(seed, shape)`` alone (see
``repro.kernels.ops.attn_score_field``).

The pure-XLA equivalent used by the model stack is
``repro.models.attention.blocked_attention``; these kernels are the TPU
hot-path with explicit VMEM tiling.  Validated in interpret mode against
``ref.flash_attention_ref`` / ``ref.zo_dual_flash_attention_ref``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.zo_matmul import pad_to, tile, uniform_noise

NEG_INF = -2.0e38


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
               nk: int, bq: int, bk: int, causal: bool, window: int,
               cap: float, scale: float, seq_kv: int):
    ki = pl.program_id(2)
    qi = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)                   # (bq, D)
    k = k_ref[0].astype(jnp.float32)                   # (bk, D)
    v = v_ref[0].astype(jnp.float32)
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
    if cap > 0:
        s = cap * jnp.tanh(s / cap)
    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kv_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = kv_pos < seq_kv                             # padding
    if causal:
        mask &= q_pos >= kv_pos
    if window > 0:
        mask &= (q_pos - kv_pos) < window
    s = jnp.where(mask, s, NEG_INF)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    p = jnp.exp(s - m_new[:, None])
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _done():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "cap", "scale", "bq", "bk", "interpret"))
def flash_attention(q, k, v, *, causal=True, window=0, cap=0.0,
                    scale=None, bq=512, bk=512, interpret=True):
    """q, k: (B, Sq|Skv, H|Kv, D); v: (B, Skv, Kv, Dv) -> (B, Sq, H, Dv).
    The value head dim may differ from the query/key one (MLA)."""
    B, Sq, H, D = q.shape
    Skv, Kv, Dv = k.shape[1], k.shape[2], v.shape[-1]
    G = H // Kv
    scale = float(scale) if scale is not None else D ** -0.5
    (bq, Sq_p), (bk, Skv_p) = _seq_tiles(Sq, Skv, bq, bk, interpret)
    nq, nk = Sq_p // bq, Skv_p // bk
    # (BH, S, D) layouts; padded kv columns are masked, padded q rows
    # are sliced off
    qf = _flat(q, Sq_p)
    kf, vf = _flat(k, Skv_p), _flat(v, Skv_p)

    def kv_index(bh, qi, ki):
        b = bh // H
        h = bh % H
        return b * Kv + h // G, ki, 0

    kernel = functools.partial(
        _fa_kernel, nk=nk, bq=bq, bk=bk, causal=causal, window=window,
        cap=float(cap), scale=scale, seq_kv=Skv)
    out = pl.pallas_call(
        kernel,
        grid=(B * H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, bk, D), kv_index),
            pl.BlockSpec((1, bk, Dv), kv_index),
        ],
        out_specs=pl.BlockSpec((1, bq, Dv), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Sq_p, Dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, Dv), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf)
    return _unflat(out, B, Sq)


def _seq_tiles(Sq, Skv, bq, bk, interpret):
    """(block, padded extent) for the q and kv sequence axes: rows align
    to 8 on the compiled path (head_dim is always a whole block)."""
    align = 1 if interpret else 8
    return tile(Sq, bq, align), tile(Skv, bk, align)


def _flat(t, s_pad):
    """(B, S, H, D) -> (B*H, s_pad, D), zero-padded along the sequence."""
    B, S, H, D = t.shape
    t = pad_to(t, (B, s_pad, H, D))
    return t.transpose(0, 2, 1, 3).reshape(B * H, s_pad, D)


def _unflat(o, B, S):
    """(B*H, S_pad, D) -> (B, S, H, D), dropping padded rows."""
    BH, s_pad, D = o.shape
    return o.reshape(B, BH // B, s_pad, D)[:, :, :S].transpose(0, 2, 1, 3)


# ---------------------------------------------------------------------------
# fused ZO dual-probe flash attention: both estimator streams in ONE pass
# ---------------------------------------------------------------------------

def _zo_dual_fa_kernel(seed_ref, mu_ref, off_ref, qa_ref, qb_ref, k_ref,
                       v_ref, *refs, nk: int, bq: int, bk: int,
                       causal: bool, window: int, cap: float, scale: float,
                       seq_kv: int, n_heads: int, seq_q: int,
                       shared_kv: bool, perturb_a: bool, perturb_b: bool):
    """Two online-softmax streams per grid step.

    Scratch layout is two full (m, l, acc) sets — the clean stream's set
    updates with the exact op sequence of :func:`_fa_kernel`, so with
    ``perturb_a=False`` its output bit-matches a separate
    ``flash_attention`` call.  The position iotas and the mask are
    computed once and shared; in ``shared_kv`` mode the K/V block loads
    are shared too (the score-probe mode), otherwise the b-stream gets
    its own K/V blocks (the weight-probe mode, where k/v diverged
    upstream) and the fusion still halves the grid-step count.
    """
    if shared_kv:
        oa_ref, ob_ref, ma_ref, la_ref, acca_ref, mb_ref, lb_ref, \
            accb_ref = refs
        kb_ref, vb_ref = k_ref, v_ref
    else:
        kb_ref, vb_ref, oa_ref, ob_ref, ma_ref, la_ref, acca_ref, \
            mb_ref, lb_ref, accb_ref = refs
    bh = pl.program_id(0)
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        ma_ref[...] = jnp.full_like(ma_ref, NEG_INF)
        la_ref[...] = jnp.zeros_like(la_ref)
        acca_ref[...] = jnp.zeros_like(acca_ref)
        mb_ref[...] = jnp.full_like(mb_ref, NEG_INF)
        lb_ref[...] = jnp.zeros_like(lb_ref)
        accb_ref[...] = jnp.zeros_like(accb_ref)

    # shared between both streams: positions, mask, (optionally) noise
    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kv_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = kv_pos < seq_kv                             # padding
    if causal:
        mask &= q_pos >= kv_pos
    if window > 0:
        mask &= (q_pos - kv_pos) < window
    noise = None
    if perturb_a or perturb_b:
        # canonical (n_heads*Sq, Skv) field; batch-independent, so the
        # direction is one field per layer regardless of batch size
        h = bh % n_heads
        noise = uniform_noise(seed_ref[0, 0], (bq, bk),
                              row_offset=off_ref[0, 0] + h * seq_q + qi * bq,
                              col_offset=ki * bk)

    def stream(q_ref2, kk_ref, vv_ref, m_ref, l_ref, acc_ref, o_ref,
               pert: bool, mu_ix: int):
        q = q_ref2[0].astype(jnp.float32)              # (bq, D)
        k = kk_ref[0].astype(jnp.float32)              # (bk, D)
        v = vv_ref[0].astype(jnp.float32)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        if cap > 0:
            s = cap * jnp.tanh(s / cap)
        if pert:
            # post-softcap, pre-mask: an additive fixed-coordinate
            # direction on the score field (masked positions never see it)
            s = s + mu_ref[0, mu_ix] * noise
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

        @pl.when(ki == nk - 1)
        def _done():
            l = jnp.maximum(l_ref[...], 1e-30)
            o_ref[0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)

    stream(qa_ref, k_ref, v_ref, ma_ref, la_ref, acca_ref, oa_ref,
           perturb_a, 0)
    stream(qb_ref, kb_ref, vb_ref, mb_ref, lb_ref, accb_ref, ob_ref,
           perturb_b, 1)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "cap", "scale", "bq", "bk", "interpret",
    "perturb_a", "perturb_b"))
def zo_dual_flash_attention(qa, qb, k, v, kb=None, vb=None, seed=0,
                            mu_a=0.0, mu_b=0.0, row_offset=0, *,
                            causal=True, window=0, cap=0.0, scale=None,
                            bq=512, bk=512, interpret=True,
                            perturb_a=False, perturb_b=True):
    """Fused dual-probe flash attention: (oa, ob) in one KV pass.

    qa, qb: (B, Sq, H, D) clean / perturbed query streams; k, v:
    (B, Skv, Kv, D) and (B, Skv, Kv, Dv), where the value head dim Dv may
    differ from D (MLA).  Two modes:

    * **score probe** (``kb is None``) — both streams attend the SAME
      k/v, every K/V VMEM load is shared, and the perturbed stream adds
      ``mu * U(seed)`` to its pre-softmax scores (``perturb_a``/
      ``perturb_b`` select which stream; clean+perturbed by default,
      ``perturb_a=True, mu_b=-mu_a`` for the antithetic pair).  U is the
      global-coordinate hash field (n_heads*Sq, Skv) at ``row_offset``
      (stacked scan layers: rep r passes ``r * n_heads * Sq``).
    * **weight probe** (``kb``/``vb`` given) — the streams carry their
      own K/V (weight noise was applied upstream by ``zo_dual_matmul``);
      the fusion still halves the number of grid steps and shares the
      mask/position work, and each stream is bit-identical to a separate
      ``flash_attention`` call over its own (q, k, v).

    Returns (oa, ob), each (B, Sq, H, Dv).
    """
    B, Sq, H, D = qa.shape
    assert qb.shape == qa.shape, (qa.shape, qb.shape)
    assert (kb is None) == (vb is None)
    Skv, Kv, Dv = k.shape[1], k.shape[2], v.shape[-1]
    G = H // Kv
    scale = float(scale) if scale is not None else D ** -0.5
    (bq, Sq_p), (bk, Skv_p) = _seq_tiles(Sq, Skv, bq, bk, interpret)
    nq, nk = Sq_p // bq, Skv_p // bk

    def kv_index(bh, qi, ki):
        b = bh // H
        h = bh % H
        return b * Kv + h // G, ki, 0

    shared = kb is None
    seed_arr = jnp.asarray([[seed]], jnp.int32)
    mu_arr = jnp.asarray([[mu_a, mu_b]], jnp.float32)
    off_arr = jnp.asarray([[row_offset]], jnp.int32)
    q_spec = pl.BlockSpec((1, bq, D), lambda bh, qi, ki: (bh, qi, 0))
    o_spec = pl.BlockSpec((1, bq, Dv), lambda bh, qi, ki: (bh, qi, 0))
    k_spec = pl.BlockSpec((1, bk, D), kv_index)
    v_spec = pl.BlockSpec((1, bk, Dv), kv_index)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    in_specs = [smem, smem, smem, q_spec, q_spec, k_spec, v_spec]
    args = [seed_arr, mu_arr, off_arr, _flat(qa, Sq_p), _flat(qb, Sq_p),
            _flat(k, Skv_p), _flat(v, Skv_p)]
    if not shared:
        in_specs += [k_spec, v_spec]
        args += [_flat(kb, Skv_p), _flat(vb, Skv_p)]
    kernel = functools.partial(
        _zo_dual_fa_kernel, nk=nk, bq=bq, bk=bk, causal=causal,
        window=window, cap=float(cap), scale=scale, seq_kv=Skv,
        n_heads=H, seq_q=Sq, shared_kv=shared, perturb_a=perturb_a,
        perturb_b=perturb_b)
    oa, ob = pl.pallas_call(
        kernel,
        grid=(B * H, nq, nk),
        in_specs=in_specs,
        out_specs=[o_spec, o_spec],
        out_shape=[jax.ShapeDtypeStruct((B * H, Sq_p, Dv), qa.dtype),
                   jax.ShapeDtypeStruct((B * H, Sq_p, Dv), qb.dtype)],
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, Dv), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, Dv), jnp.float32),
        ],
        interpret=interpret,
    )(*args)
    return _unflat(oa, B, Sq), _unflat(ob, B, Sq)
