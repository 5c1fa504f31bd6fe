"""Pallas TPU kernels: blocked flash attention (online softmax) with GQA,
causal masking, local windows, and gemma2-style logit soft-capping —
the single-stream :func:`flash_attention`, differentiable, and the fused
ZO dual-probe variant :func:`zo_dual_flash_attention` that carries the
clean and ±mu-perturbed streams of the two-point estimator through ONE
sequential pass over the K/V blocks.

The single-stream kernel is a ``custom_vjp`` in FlashAttention-2 form:
the forward (grid (BH / hb, nq, nk), kv innermost, ``hb`` heads a step)
keeps each row's log-sum-exp; the backward recomputes the probabilities
tile by tile from q, k and that statistic in a ``dq`` kernel (kv
innermost) and a ``dk, dv`` kernel (q innermost).

The dual kernel's grid is (B * H, nq, nk), the kv loop innermost; m/l/acc
live in VMEM scratch and persist across kv steps (sequential TPU grid).
Its kv-head BlockSpec index map folds the GQA group: q head h reads kv
head h // (H // Kv).

In both kernels a block that the causal mask or the window hides whole
moves no data and does no work: the index maps clamp it onto the nearest
live block, so that it shares that block's load, and ``pl.when`` skips
its body.  At 8192 tokens on 512-token tiles that is 120 of a head's 256
blocks (:func:`dual_live_blocks`).

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
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.zo_matmul import VMEM_LIMIT, pad_to, tile, uniform_noise

NEG_INF = -2.0e38
LANES = 128
# score elements (heads x bq x bk) one grid step of the single-stream
# kernels takes: enough work that the step's fixed cost stays small,
# a few f32 tiles of VMEM per head
STEP_SCORES = 2 ** 20
# their sequence tile; models/attention keeps a sequence within one tile
# on XLA, where it ran faster
FLASH_BLOCK = 512


# ---------------------------------------------------------------------------
# single-stream flash attention with its backward pass (FlashAttention-2)
# ---------------------------------------------------------------------------

class _Plan(NamedTuple):
    """The static part of one call: ``hb`` heads of (bq, bk) tiles per
    grid step over the (nq, nk) blocks of the padded score plane."""
    hb: int
    bq: int
    bk: int
    nq: int
    nk: int
    seq_kv: int
    causal: bool
    window: int
    cap: float
    scale: float
    interpret: bool


def _kv_range(p: _Plan, qi):
    """First and last kv block that query block ``qi`` sees unmasked."""
    lo, hi = 0, p.nk - 1
    if p.causal:
        hi = jnp.minimum(hi, (qi * p.bq + p.bq - 1) // p.bk)
    if p.window > 0:
        lo = jnp.maximum(0, (qi * p.bq - p.window + 1) // p.bk)
    return lo, hi


def _q_range(p: _Plan, ki):
    """First and last query block that sees kv block ``ki`` unmasked."""
    lo, hi = 0, p.nq - 1
    if p.causal:
        lo = (ki * p.bk) // p.bq
    if p.window > 0:
        hi = jnp.minimum(hi, (ki * p.bk + p.bk + p.window - 2) // p.bq)
    return lo, hi


def _mask(p: _Plan, qi, ki, transposed: bool):
    """(bq, bk) validity of block (qi, ki); (bk, bq) when transposed."""
    shape = (p.bk, p.bq) if transposed else (p.bq, p.bk)
    rows = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    q_pos = qi * p.bq + (cols if transposed else rows)
    kv_pos = ki * p.bk + (rows if transposed else cols)
    mask = kv_pos < p.seq_kv                           # padding
    if p.causal:
        mask &= q_pos >= kv_pos
    if p.window > 0:
        mask &= (q_pos - kv_pos) < p.window
    return mask


def _live(p: _Plan, qi, ki):
    """Whether block (qi, ki) holds an unmasked pair.  A block that is
    not live moves no data (the index maps clamp it onto a live
    neighbour) and does no work."""
    lo, hi = _kv_range(p, qi)
    return (ki >= lo) & (ki <= hi)


def _heads(hb: int, body):
    """``body(h)`` for each of a block's ``hb`` heads."""
    if hb == 1:
        body(0)
        return
    def one(h, carry):
        body(h)
        return carry

    jax.lax.fori_loop(0, hb, one, 0, unroll=hb <= 4)


def _scores(p: _Plan, a, b):
    """a @ b.T on the MXU in the operands' dtype, f32 out, scaled and
    soft-capped; also returns tanh of the capped scores (None uncapped)
    for the cap's derivative."""
    s = jnp.dot(a, b.T, preferred_element_type=jnp.float32) * p.scale
    if p.cap > 0:
        t = jnp.tanh(s / p.cap)
        return p.cap * t, t
    return s, None


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref,
                *, p: _Plan):
    qi, ki = pl.program_id(1), pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(_live(p, qi, ki))
    def _step():
        mask = _mask(p, qi, ki, transposed=False)

        def head(h):
            v = v_ref[h]
            s, _ = _scores(p, q_ref[h], k_ref[h])
            s = jnp.where(mask, s, NEG_INF)
            m_prev = m_ref[h]                          # (bq, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            e = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[h] = l_ref[h] * alpha + jnp.sum(e, axis=1, keepdims=True)
            acc_ref[h] = acc_ref[h] * alpha + jnp.dot(
                e.astype(v.dtype), v, preferred_element_type=jnp.float32)
            m_ref[h] = m_new
        _heads(p.hb, head)

    @pl.when(ki == p.nk - 1)
    def _done():
        def head(h):
            l = jnp.maximum(l_ref[h], 1e-30)
            o_ref[h] = (acc_ref[h] / l).astype(o_ref.dtype)
            lse_ref[h] = (m_ref[h] + jnp.log(l)).reshape(1, p.bq)
        _heads(p.hb, head)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dlt_ref, dq_ref,
               acc_ref, *, p: _Plan):
    qi, ki = pl.program_id(1), pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(_live(p, qi, ki))
    def _step():
        mask = _mask(p, qi, ki, transposed=False)

        def head(h):
            k = k_ref[h]
            s, t = _scores(p, q_ref[h], k)
            e = jnp.where(mask, jnp.exp(s - lse_ref[h].reshape(p.bq, 1)),
                          0.0)
            dp = jnp.dot(do_ref[h], v_ref[h].T,
                         preferred_element_type=jnp.float32)
            ds = e * (dp - dlt_ref[h].reshape(p.bq, 1))
            if t is not None:
                ds = ds * (1.0 - t * t)
            acc_ref[h] += jnp.dot(ds.astype(k.dtype), k,
                                  preferred_element_type=jnp.float32)
        _heads(p.hb, head)

    @pl.when(ki == p.nk - 1)
    def _done():
        dq_ref[...] = (acc_ref[...] * p.scale).astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dlt_ref, dk_ref,
                dv_ref, dk_acc, dv_acc, *, p: _Plan):
    """The kv block's gradients over the query blocks, on transposed
    (bk, bq) tiles, so that the row statistics broadcast as rows and
    every product is a plain or a transposed-rhs one."""
    ki, qi = pl.program_id(1), pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    @pl.when(_live(p, qi, ki))
    def _step():
        mask = _mask(p, qi, ki, transposed=True)

        def head(h):
            q, do = q_ref[h], do_ref[h]
            s, t = _scores(p, k_ref[h], q)             # (bk, bq)
            e = jnp.where(mask, jnp.exp(s - lse_ref[h]), 0.0)
            dv_acc[h] += jnp.dot(e.astype(do.dtype), do,
                                 preferred_element_type=jnp.float32)
            dp = jnp.dot(v_ref[h], do.T, preferred_element_type=jnp.float32)
            ds = e * (dp - dlt_ref[h])
            if t is not None:
                ds = ds * (1.0 - t * t)
            dk_acc[h] += jnp.dot(ds.astype(q.dtype), q,
                                 preferred_element_type=jnp.float32)
        _heads(p.hb, head)

    @pl.when(qi == p.nq - 1)
    def _done():
        dk_ref[...] = (dk_acc[...] * p.scale).astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)


def _clamp(i, lo, hi):
    return jnp.minimum(jnp.maximum(i, lo), hi)


def _call(kernel, name, p: _Plan, grid, in_specs, out_specs, out_shape,
          scratch):
    return pl.pallas_call(
        functools.partial(kernel, p=p), grid=grid, in_specs=in_specs,
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM(s, jnp.float32) for s in scratch],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT),
        interpret=p.interpret, name=name)


def _fwd(q, k, v, p: _Plan):
    """(o, lse): the output and each row's log-sum-exp, (BH, 1, Sq_p)."""
    BH, Sq_p, D = q.shape
    Dv = v.shape[-1]

    def kv_map(b, qi, ki):
        return (b, _clamp(ki, *_kv_range(p, qi)), 0)

    rows = lambda b, qi, ki: (b, qi, 0)
    return _call(
        _fwd_kernel, "flash_attention_fwd", p, (BH // p.hb, p.nq, p.nk),
        [pl.BlockSpec((p.hb, p.bq, D), rows),
         pl.BlockSpec((p.hb, p.bk, D), kv_map),
         pl.BlockSpec((p.hb, p.bk, Dv), kv_map)],
        [pl.BlockSpec((p.hb, p.bq, Dv), rows),
         pl.BlockSpec((p.hb, 1, p.bq), lambda b, qi, ki: (b, 0, qi))],
        [jax.ShapeDtypeStruct((BH, Sq_p, Dv), q.dtype),
         jax.ShapeDtypeStruct((BH, 1, Sq_p), jnp.float32)],
        [(p.hb, p.bq, 1), (p.hb, p.bq, 1), (p.hb, p.bq, Dv)])(q, k, v)


def _bwd(p: _Plan, res, do):
    """dq over the kv blocks, then dk and dv over the query blocks, each
    tile's probabilities recomputed from q, k and the saved lse, with
    ``D = rowsum(dO * O)``."""
    q, k, v, o, lse = res
    BH, Sq_p, D = q.shape
    Skv_p, Dv = k.shape[1], v.shape[-1]
    dlt = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                  axis=-1)[:, None, :]
    hb, bq, bk = p.hb, p.bq, p.bk

    def kv_of_q(b, qi, ki):
        return (b, _clamp(ki, *_kv_range(p, qi)), 0)

    q_rows = lambda b, qi, ki: (b, qi, 0)
    q_stat = lambda b, qi, ki: (b, 0, qi)
    dq = _call(
        _dq_kernel, "flash_attention_dq", p, (BH // hb, p.nq, p.nk),
        [pl.BlockSpec((hb, bq, D), q_rows),
         pl.BlockSpec((hb, bk, D), kv_of_q),
         pl.BlockSpec((hb, bk, Dv), kv_of_q),
         pl.BlockSpec((hb, bq, Dv), q_rows),
         pl.BlockSpec((hb, 1, bq), q_stat),
         pl.BlockSpec((hb, 1, bq), q_stat)],
        pl.BlockSpec((hb, bq, D), q_rows),
        jax.ShapeDtypeStruct(q.shape, q.dtype),
        [(hb, bq, D)])(q, k, v, do, lse, dlt)

    def q_of_kv(b, ki, qi):
        return (b, _clamp(qi, *_q_range(p, ki)), 0)

    def stat_of_kv(b, ki, qi):
        return (b, 0, _clamp(qi, *_q_range(p, ki)))

    kv_rows = lambda b, ki, qi: (b, ki, 0)
    dk, dv = _call(
        _dkv_kernel, "flash_attention_dkv", p, (BH // hb, p.nk, p.nq),
        [pl.BlockSpec((hb, bq, D), q_of_kv),
         pl.BlockSpec((hb, bk, D), kv_rows),
         pl.BlockSpec((hb, bk, Dv), kv_rows),
         pl.BlockSpec((hb, bq, Dv), q_of_kv),
         pl.BlockSpec((hb, 1, bq), stat_of_kv),
         pl.BlockSpec((hb, 1, bq), stat_of_kv)],
        [pl.BlockSpec((hb, bk, D), kv_rows),
         pl.BlockSpec((hb, bk, Dv), kv_rows)],
        [jax.ShapeDtypeStruct((BH, Skv_p, D), k.dtype),
         jax.ShapeDtypeStruct((BH, Skv_p, Dv), v.dtype)],
        [(hb, bk, D), (hb, bk, Dv)])(q, k, v, do, lse, dlt)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _flash(q, k, v, p: _Plan):
    return _fwd(q, k, v, p)[0]


def _flash_fwd(q, k, v, p: _Plan):
    o, lse = _fwd(q, k, v, p)
    return o, (q, k, v, o, lse)


_flash.defvjp(_flash_fwd, _bwd)


def _vmem_bytes(hb: int, bq: int, bk: int, d: int, dv: int,
               itemsize: int) -> int:
    """Scoped VMEM of the largest of the three kernels at ``hb`` heads of
    (bq, bk) tiles, counted from above: the q-side and kv-side blocks in
    and out, double-buffered, with lanes padded to 128; the f32
    statistics rows; the f32 accumulators and row statistics; one head's
    f32 (bq, bk) temporaries."""
    lanes = lambda n: -(-n // LANES) * LANES
    width = lanes(d) + lanes(dv)
    io = 2 * 2 * hb * (bq + bk) * width * itemsize
    stats = 2 * 2 * hb * 8 * lanes(bq) * 4
    acc = hb * (bq + bk) * (width + 2 * LANES) * 4
    temps = 8 * max(bq, bk) * lanes(max(bq, bk)) * 4
    return io + stats + acc + temps


def _tiles(BH, Sq, Skv, D, Dv, itemsize, bq=None, bk=None, hb=None,
           interpret=False):
    """(hb, (bq, Sq_p), (bk, Skv_p)): sequence blocks of up to
    :data:`FLASH_BLOCK` aligned to 128 lanes (a block the caller gives
    keeps its size in interpret mode), and, unless given, the most heads
    per grid step, a divisor of BH, within :data:`STEP_SCORES` and
    :data:`VMEM_LIMIT`."""
    lane = 1 if interpret else LANES
    tq = tile(Sq, FLASH_BLOCK, LANES) if bq is None else tile(Sq, bq, lane)
    tk = tile(Skv, FLASH_BLOCK, LANES) if bk is None else tile(Skv, bk,
                                                                lane)
    if hb is None:
        hb = max(h for h in range(1, BH + 1) if BH % h == 0 and (
            h == 1 or (h * tq[0] * tk[0] <= STEP_SCORES and _vmem_bytes(
                h, tq[0], tk[0], D, Dv, itemsize) <= VMEM_LIMIT)))
    return hb, tq, tk


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "cap", "scale", "bq", "bk", "hb", "interpret"))
def flash_attention(q, k, v, *, causal=True, window=0, cap=0.0,
                    scale=None, bq=None, bk=None, hb=None, interpret=True):
    """q, k: (B, Sq|Skv, H|Kv, D); v: (B, Skv, Kv, Dv) -> (B, Sq, H, Dv).

    Differentiable: the forward keeps each row's log-sum-exp, and the
    backward recomputes the probabilities tile by tile in two kernels
    (``dq``; ``dk, dv``), so no score matrix reaches HBM either way.
    Blocks that the causal mask or the window hide move no data and do
    no work.  The value head dim may differ from the query/key one
    (MLA).  Under GQA each query head reads its group's K/V through a
    repeat, whose transpose sums dk and dv over the group."""
    B, Sq, H, D = q.shape
    Skv, Kv, Dv = k.shape[1], k.shape[2], v.shape[-1]
    if Kv != H:
        k = jnp.repeat(k, H // Kv, axis=2)
        v = jnp.repeat(v, H // Kv, axis=2)
    hb, (bq, Sq_p), (bk, Skv_p) = _tiles(
        B * H, Sq, Skv, D, Dv, q.dtype.itemsize, bq, bk, hb, interpret)
    p = _Plan(hb, bq, bk, Sq_p // bq, Skv_p // bk, Skv, bool(causal),
              int(window), float(cap),
              float(scale) if scale is not None else D ** -0.5, interpret)
    o = _flash(_flat(q, Sq_p), _flat(k, Skv_p), _flat(v, Skv_p), p)
    return _unflat(o, B, Sq)


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
                       v_ref, *refs, p: _Plan, n_heads: int, seq_q: int,
                       shared_kv: bool, perturb_a: bool, perturb_b: bool):
    """Two online-softmax streams per grid step.

    Scratch layout is two full (m, l, acc) sets — the clean stream's set
    updates with the op sequence of :func:`_fwd_kernel` on f32 operands,
    so with ``perturb_a=False`` its output bit-matches a separate
    ``flash_attention`` call over f32 inputs.  The position iotas and the
    mask are computed once and shared; in ``shared_kv`` mode the K/V
    block loads are shared too (the score-probe mode), otherwise the
    b-stream gets its own K/V blocks (the weight-probe mode, where k/v
    diverged upstream) and the fusion still halves the grid-step count.
    A step the causal mask or the window hides whole does no work (its
    K/V blocks are clamped onto the nearest live step's, so none moves).
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

    @pl.when(_live(p, qi, ki))
    def _step():
        # shared between both streams: positions, mask, (optionally) noise
        mask = _mask(p, qi, ki, transposed=False)
        noise = None
        if perturb_a or perturb_b:
            # canonical (n_heads*Sq, Skv) field; batch-independent, so the
            # direction is one field per layer regardless of batch size
            h = bh % n_heads
            noise = uniform_noise(
                seed_ref[0, 0], (p.bq, p.bk),
                row_offset=off_ref[0, 0] + h * seq_q + qi * p.bq,
                col_offset=ki * p.bk)

        def stream(q_ref2, kk_ref, vv_ref, m_ref, l_ref, acc_ref,
                   pert: bool, mu_ix: int):
            q = q_ref2[0].astype(jnp.float32)          # (bq, D)
            k = kk_ref[0].astype(jnp.float32)          # (bk, D)
            v = vv_ref[0].astype(jnp.float32)
            s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * p.scale
            if p.cap > 0:
                s = p.cap * jnp.tanh(s / p.cap)
            if pert:
                # post-softcap, pre-mask: an additive fixed-coordinate
                # direction on the score field (masked positions never
                # see it)
                s = s + mu_ref[0, mu_ix] * noise
            s = jnp.where(mask, s, NEG_INF)
            m_prev = m_ref[...]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
            e = jnp.exp(s - m_new[:, None])
            alpha = jnp.exp(m_prev - m_new)
            l_ref[...] = l_ref[...] * alpha + jnp.sum(e, axis=1)
            acc_ref[...] = acc_ref[...] * alpha[:, None] + jnp.dot(
                e, v, preferred_element_type=jnp.float32)
            m_ref[...] = m_new

        stream(qa_ref, k_ref, v_ref, ma_ref, la_ref, acca_ref, perturb_a, 0)
        stream(qb_ref, kb_ref, vb_ref, mb_ref, lb_ref, accb_ref, perturb_b,
               1)

    # the last kv step may be dead (causal: on every query block but the
    # last), so the outputs are written whatever its liveness
    @pl.when(ki == p.nk - 1)
    def _done():
        for l_ref, acc_ref, o_ref in ((la_ref, acca_ref, oa_ref),
                                      (lb_ref, accb_ref, ob_ref)):
            l = jnp.maximum(l_ref[...], 1e-30)
            o_ref[0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def _dual_plan(Sq, Skv, bq, bk, causal, window, cap, scale, interpret):
    """The :class:`_Plan` of one dual-kernel call: one head a step."""
    (bq, Sq_p), (bk, Skv_p) = _seq_tiles(Sq, Skv, bq, bk, interpret)
    return _Plan(1, bq, bk, Sq_p // bq, Skv_p // bk, Skv, bool(causal),
                 int(window), float(cap), float(scale), interpret)


def dual_live_blocks(Sq, Skv, bq=512, bk=512, causal=True, window=0):
    """(live, total): the (q, kv) blocks of one head that the compiled
    :func:`zo_dual_flash_attention` computes, of all its grid's, at the
    tiles it picks for these shapes."""
    p = _dual_plan(Sq, Skv, bq, bk, causal, window, 0.0, 1.0, False)
    live = 0
    for qi in range(p.nq):
        lo, hi = _kv_range(p, qi)
        live += int(hi) - int(lo) + 1
    return live, p.nq * p.nk


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
      ``flash_attention`` call over its own (q, k, v) in f32.

    As in :func:`flash_attention`, blocks that the causal mask or the
    window hide move no data and do no work (:func:`dual_live_blocks`
    counts them); the outputs are those of computing every block.
    Returns (oa, ob), each (B, Sq, H, Dv).
    """
    B, Sq, H, D = qa.shape
    assert qb.shape == qa.shape, (qa.shape, qb.shape)
    assert (kb is None) == (vb is None)
    Skv, Kv, Dv = k.shape[1], k.shape[2], v.shape[-1]
    G = H // Kv
    p = _dual_plan(Sq, Skv, bq, bk, causal, window, cap,
                   scale if scale is not None else D ** -0.5, interpret)
    bq, bk = p.bq, p.bk
    Sq_p, Skv_p = p.nq * bq, p.nk * bk

    def kv_index(bh, qi, ki):
        b = bh // H
        h = bh % H
        return b * Kv + h // G, _clamp(ki, *_kv_range(p, qi)), 0

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
        _zo_dual_fa_kernel, p=p, n_heads=H, seq_q=Sq, shared_kv=shared,
        perturb_a=perturb_a, perturb_b=perturb_b)
    oa, ob = pl.pallas_call(
        kernel,
        grid=(B * H, p.nq, p.nk),
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
