"""Pallas TPU kernels: fused ZO-perturbed matmul  y = x @ (W + mu * U(seed))
and the fused dual probe  (ya, yb) = (x_a @ (W + mu_a*U), x_b @ (W + mu_b*U)).

The TPU-native adaptation of the paper's lean-client mechanism (DESIGN.md
§3): the perturbation U is generated *tile-by-tile in VMEM* from a
counter-based hash while the tile is being fed to the MXU — U never
exists in HBM, so the perturbed forward pass costs exactly the HBM
traffic of an ordinary matmul.  The dual-probe kernel goes one step
further: both loss evaluations of the two-point estimator (clean +
perturbed, or the +mu/-mu antithetic pair) share a single read of each W
tile and a single noise generation, so the estimator costs ONE weight
read instead of two.

U entries are uniform(-sqrt(3), +sqrt(3)) (unit variance); the paper's
estimator admits uniform perturbations, and a uniform tile is one
multiply-add from raw hash bits, keeping the generator off the critical
MXU path.

The noise stream is addressed by GLOBAL (row, col) coordinates of the
weight matrix mixed with the seed — NOT by tile indices — so it is
invariant to the block sizes bm/bn/bk, identical between compiled TPU
and ``interpret=True`` CPU execution, and bit-exactly reproducible by
the pure-jnp :func:`uniform_noise` below.  That last property is what
makes server-side seed-replay possible: ``replay_gradient`` /
``seed_replay_aggregate`` regenerate the exact kernel directions from
``(seed, shape)`` without ever running the kernel.

``row_offset`` shifts the global row coordinate: a layer stacked along a
leading scan axis (reps, K, N) treats rep r as rows [r*K, (r+1)*K) of
one canonical (reps*K, N) noise field, so sliced-per-rep kernel calls
and whole-leaf replay see the same stream.

The MXU feed: the v5e MXU multiplies bf16, and an f32 x f32 ``jnp.dot``
in a kernel runs on it as one bf16 pass that rounds both operands, which
would round ``W + mu*U`` to bf16 and lose most of a small perturbation.
When the operands are bf16, the kernels keep them bf16: the clean stream
``x @ W`` is one bf16 pass, and the perturbed stream splits the f32
``W + mu*U`` of each tile into three bf16 limbs ``hi = bf16(w')``,
``mid = bf16(w' - hi)``, ``lo = bf16(w' - hi - mid)`` and sums three
passes in f32.  Nothing is lost: an
f32 significand (24 bits) is at most three bf16 significands (8 bits
each), so the limbs hold ``w'`` exactly, and a bf16 x bf16 product is
exact in f32: the only roundings are those of the f32 sums, as in an
exact f32 contraction.  Other operand dtypes contract in f32.

Grid: (nm, nn, nk) with the k loop innermost; f32 VMEM scratch
accumulates partial products across k steps (TPU grid iteration is
sequential, so scratch carries state).  Blocks a caller leaves out come
from the shape (:func:`choose_blocks`): 128-aligned lane blocks of 256
to 512 that divide K and N (or whole axes), and as many rows as fit
:data:`VMEM_LIMIT`, up to the whole M, so that each W tile's noise and
limbs are made once for many rows.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

SQRT3 = 1.7320508075688772


def tile(dim: int, pref: int, align: int) -> tuple[int, int]:
    """(block, padded extent) for one grid axis.

    The whole axis when it fits in one block; else the largest multiple
    of ``align`` in [pref/2, pref] that divides it; else ``pref``
    (rounded down to ``align``) with the axis padded up to whole blocks.
    Compiled kernels pass Mosaic's tiling (8 or 16 rows, 128 lanes) as
    ``align``, so no block they see is unaligned; interpret mode passes
    1 for the blocks a caller gives and keeps its exact divisors.
    """
    if dim <= pref:
        return dim, dim
    top = max(align, pref - pref % align)
    for b in range(top, top // 2 - 1, -align):
        if dim % b == 0:
            return b, dim
    return top, -(-dim // top) * top


def pad_to(x, shape):
    """Zero-pad ``x`` at the end of every axis up to ``shape``."""
    if tuple(x.shape) == tuple(shape):
        return x
    return jnp.pad(x, [(0, t - s) for s, t in zip(x.shape, shape)])


def _mix_bits(seed_u32, r_u32, c_u32):
    """murmur3-style finalizer over (seed, global row, global col)."""
    x = (r_u32 * jnp.uint32(0x9E3779B9)) ^ (c_u32 * jnp.uint32(0x85EBCA6B))
    x = x ^ (seed_u32 * jnp.uint32(0x27D4EB2F) + jnp.uint32(0x165667B1))
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    x = x ^ (x >> 16)
    return x


def _bits_to_uniform(bits):
    """Top 24 hash bits -> uniform(-sqrt3, sqrt3).  The bits go through
    int32 (Mosaic lowers no uint32 -> f32 cast); every step before the
    final scale is exact in f32, so XLA, interpret mode and the compiled
    kernel round the same single product."""
    u01 = (bits >> 8).astype(jnp.int32).astype(jnp.float32) * (2.0 ** -24)
    return (u01 * 2.0 - 1.0) * SQRT3


def uniform_noise(seed, shape, row_offset=0, col_offset=0):
    """U(seed) for a (rows, cols) window at a global offset — unit-variance
    uniform(-sqrt3, sqrt3), f32.

    Pure jnp and elementwise in the global coordinates, so the same
    function is the in-kernel tile generator (with offsets derived from
    the grid position) AND the server-side replay oracle (whole leaf at
    offset 0).  ``seed``/offsets may be traced int32.
    """
    rows, cols = shape
    r = jax.lax.broadcasted_iota(jnp.uint32, (rows, cols), 0) \
        + jnp.asarray(row_offset).astype(jnp.uint32)
    c = jax.lax.broadcasted_iota(jnp.uint32, (rows, cols), 1) \
        + jnp.asarray(col_offset).astype(jnp.uint32)
    return _bits_to_uniform(_mix_bits(jnp.asarray(seed).astype(jnp.uint32),
                                      r, c))


def uniform_noise_at(seed, rows, cols):
    """Gathered noise entries U[rows, cols] (broadcasting int arrays) —
    the embedding-lookup form: noise for table row ids without
    materializing the (vocab, d) field."""
    r = jnp.asarray(rows).astype(jnp.uint32)
    c = jnp.asarray(cols).astype(jnp.uint32)
    return _bits_to_uniform(_mix_bits(jnp.asarray(seed).astype(jnp.uint32),
                                      r, c))


# ---------------------------------------------------------------------------
# the MXU feed and the blocks, shared by both matmul kernels
# ---------------------------------------------------------------------------

# Scoped VMEM the matmul kernels may ask Mosaic for (the v5e has 128 MiB
# per core), and so the budget of the row blocks: on one TPU v5e the dual
# kernel at the fed cells' shapes ran as fast with 2048 rows as with
# 4096, which took twice the VMEM and three times the compile.
VMEM_LIMIT = 48 * 2 ** 20


def _limbs(w):
    """Three bf16 limbs whose sum is the f32 ``w`` exactly: each limb
    rounds what the limbs before it left over, and an f32 significand
    (24 bits) is at most three bf16 significands (8 bits each) long."""
    hi = w.astype(jnp.bfloat16)
    rest = w - hi.astype(jnp.float32)
    mid = rest.astype(jnp.bfloat16)
    lo = (rest - mid.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, mid, lo


def _tile_product(x, w, mu, u):
    """One probe stream's partial product ``x @ (w + mu*u)`` for one tile,
    in f32 (``u is None``: the clean ``x @ w``).

    bf16 operands feed the MXU at its native rate and lose nothing: a
    bf16 x bf16 product is exact in f32, so ``x @ w`` is one bf16 pass,
    and the f32 ``w + mu*u`` enters as its three exact bf16 limbs, three
    passes summed in f32.  Other dtypes contract in f32."""
    f32 = jnp.float32
    if x.dtype == w.dtype == jnp.bfloat16:
        if u is None:
            return jnp.dot(x, w, preferred_element_type=f32)
        hi, mid, lo = _limbs(w.astype(f32) + mu * u)
        return jnp.dot(x, hi, preferred_element_type=f32) + (
            jnp.dot(x, mid, preferred_element_type=f32)
            + jnp.dot(x, lo, preferred_element_type=f32))
    w = w.astype(f32)
    if u is not None:
        w = w + mu * u
    return jnp.dot(x.astype(f32), w, preferred_element_type=f32)


def vmem_bytes(bm: int, bk: int, bn: int, itemsize: int) -> int:
    """Scoped VMEM of the dual kernel at blocks (bm, bk, bn), counted
    from above: xa, xb, w, ya and yb double-buffered, the two f32
    accumulators, and one step's f32 temporaries (three dot results,
    the noise tile with its hash words, ``w + mu*U`` and its limbs)."""
    io = 2 * (2 * bm * bk + bk * bn + 2 * bm * bn) * itemsize
    acc = 2 * bm * bn * 4
    temps = 3 * bm * bn * 4 + 8 * bk * bn * 4
    return io + acc + temps


def _lane_block(dim: int) -> int:
    """bn or bk: the whole axis up to 512; else a 128-aligned divisor in
    [256, 512]; else the whole axis up to 1024 (576 = 3·3·64, an im2col
    width, has no such divisor); else 512, padded."""
    if dim <= 512:
        return dim
    for b in (512, 384, 256):
        if dim % b == 0:
            return b
    return dim if dim <= 1024 else 512


def choose_blocks(M: int, K: int, N: int, itemsize: int):
    """(bm, bk, bn) for an (M, K) @ (K, N) matmul kernel: lane blocks
    from :func:`_lane_block`, and the most rows a block can take within
    :data:`VMEM_LIMIT`, whole M first, so that each W tile's noise and
    limbs serve as many rows as fit.  The single probe takes the dual
    kernel's blocks, so that a dual pass equals two single passes bit
    for bit."""
    bn, bk = _lane_block(N), _lane_block(K)
    for pref in (M, 4096, 2048, 1024, 512, 256, 128):
        bm = tile(M, pref, 16)[0]
        if vmem_bytes(bm, bk, bn, itemsize) <= VMEM_LIMIT:
            break
    return bm, bk, bn


def _matmul_tiles(M, N, K, bm, bn, bk, itemsize, interpret):
    """Per-axis (block, padded extent).  A block the caller leaves out
    is chosen (:func:`choose_blocks`) and aligned as on the chip, so
    interpret mode runs the compiled path's grid; a block given keeps
    Mosaic's tiling (8 rows, 128 lanes) on the compiled path only."""
    cm, ck, cn = choose_blocks(M, K, N, itemsize)
    row, lane = (1, 1) if interpret else (8, 128)
    return (tile(M, cm, 16) if bm is None else tile(M, bm, row),
            tile(N, cn, 128) if bn is None else tile(N, bn, lane),
            tile(K, ck, 128) if bk is None else tile(K, bk, lane))


# ---------------------------------------------------------------------------
# single-probe kernel: y = x @ (W + mu*U)
# ---------------------------------------------------------------------------

def _zo_matmul_kernel(seed_ref, mu_ref, off_ref, x_ref, w_ref, o_ref,
                      acc_ref, *, nk: int, bk: int, bn: int,
                      gen_noise: bool):
    ki = pl.program_id(2)
    ni = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    u = None
    if gen_noise:
        u = uniform_noise(seed_ref[0, 0], (bk, bn),
                          row_offset=off_ref[0, 0] + ki * bk,
                          col_offset=ni * bn)
    acc_ref[...] += _tile_product(x_ref[...], w_ref[...], mu_ref[0, 0], u)

    @pl.when(ki == nk - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk",
                                             "interpret", "perturb"))
def zo_matmul(x, w, seed, mu, *, row_offset=0, bm: int | None = None,
              bn: int | None = None, bk: int | None = None,
              interpret: bool = True, perturb: bool = True):
    """y = x @ (W + mu*U(seed)); x: (M, K), w: (K, N).

    ``interpret=True`` executes on CPU for validation; on TPU pass
    ``interpret=False``.  ``perturb=False`` degenerates to a plain
    blocked matmul (the clean forward of the two-point estimator).
    ``row_offset`` shifts the global noise rows (stacked scan leaves).
    ``bm``/``bn``/``bk`` are preferred block sizes (see :func:`tile`);
    those left out are chosen from the shape (:func:`choose_blocks`).
    Padded rows/columns are zeros and are sliced off the result.
    """
    M, K = x.shape
    K2, N = w.shape
    assert K == K2
    (bm, Mp), (bn, Np), (bk, Kp) = _matmul_tiles(
        M, N, K, bm, bn, bk, x.dtype.itemsize, interpret)
    x, w = pad_to(x, (Mp, Kp)), pad_to(w, (Kp, Np))
    nm, nn, nk = Mp // bm, Np // bn, Kp // bk
    seed_arr = jnp.asarray([[seed]], jnp.int32)
    mu_arr = jnp.asarray([[mu]], jnp.float32)
    off_arr = jnp.asarray([[row_offset]], jnp.int32)
    kernel = functools.partial(_zo_matmul_kernel, nk=nk, bk=bk, bn=bn,
                               gen_noise=perturb)
    y = pl.pallas_call(
        kernel,
        grid=(nm, nn, nk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((bm, bk), lambda mi, ni, ki: (mi, ki)),
            pl.BlockSpec((bk, bn), lambda mi, ni, ki: (ki, ni)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda mi, ni, ki: (mi, ni)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(seed_arr, mu_arr, off_arr, x, w)
    return y[:M, :N]


# ---------------------------------------------------------------------------
# fused dual-probe kernel: both estimator evals in one pass over W
# ---------------------------------------------------------------------------

def _zo_dual_kernel(seed_ref, mu_ref, off_ref, xa_ref, xb_ref, w_ref,
                    oa_ref, ob_ref, acca_ref, accb_ref, *, nk: int,
                    bk: int, bn: int, perturb_a: bool, perturb_b: bool):
    ki = pl.program_id(2)
    ni = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        acca_ref[...] = jnp.zeros_like(acca_ref)
        accb_ref[...] = jnp.zeros_like(accb_ref)

    w = w_ref[...]
    u = None
    if perturb_a or perturb_b:
        u = uniform_noise(seed_ref[0, 0], (bk, bn),
                          row_offset=off_ref[0, 0] + ki * bk,
                          col_offset=ni * bn)
    acca_ref[...] += _tile_product(xa_ref[...], w, mu_ref[0, 0],
                                   u if perturb_a else None)
    accb_ref[...] += _tile_product(xb_ref[...], w, mu_ref[0, 1],
                                   u if perturb_b else None)

    @pl.when(ki == nk - 1)
    def _done():
        oa_ref[...] = acca_ref[...].astype(oa_ref.dtype)
        ob_ref[...] = accb_ref[...].astype(ob_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret",
                                             "perturb_a", "perturb_b"))
def zo_dual_matmul(xa, xb, w, seed, mu_a, mu_b, *, row_offset=0,
                   bm: int | None = None, bn: int | None = None,
                   bk: int | None = None, interpret: bool = True,
                   perturb_a: bool = False,
                   perturb_b: bool = True):
    """(ya, yb) = (xa @ (W + mu_a*U), xb @ (W + mu_b*U)) in ONE pass.

    Each W tile is read once and the noise tile generated once; both
    branches stream through the MXU back to back.  This halves the HBM
    weight traffic of the two-point estimator relative to two separate
    ``zo_matmul`` calls:

    * clean + perturbed (Eq. 2): ``perturb_a=False, mu_b=mu``
    * antithetic +mu/-mu pair:   ``perturb_a=True, mu_a=mu, mu_b=-mu``

    The per-branch results are bit-identical to the corresponding
    single-probe ``zo_matmul`` calls (same tile schedule, same stream).
    """
    M, K = xa.shape
    assert xb.shape == xa.shape, (xa.shape, xb.shape)
    K2, N = w.shape
    assert K == K2
    (bm, Mp), (bn, Np), (bk, Kp) = _matmul_tiles(
        M, N, K, bm, bn, bk, xa.dtype.itemsize, interpret)
    xa, xb = pad_to(xa, (Mp, Kp)), pad_to(xb, (Mp, Kp))
    w = pad_to(w, (Kp, Np))
    nm, nn, nk = Mp // bm, Np // bn, Kp // bk
    seed_arr = jnp.asarray([[seed]], jnp.int32)
    mu_arr = jnp.asarray([[mu_a, mu_b]], jnp.float32)
    off_arr = jnp.asarray([[row_offset]], jnp.int32)
    kernel = functools.partial(_zo_dual_kernel, nk=nk, bk=bk, bn=bn,
                               perturb_a=perturb_a, perturb_b=perturb_b)
    ya, yb = pl.pallas_call(
        kernel,
        grid=(nm, nn, nk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((bm, bk), lambda mi, ni, ki: (mi, ki)),
            pl.BlockSpec((bm, bk), lambda mi, ni, ki: (mi, ki)),
            pl.BlockSpec((bk, bn), lambda mi, ni, ki: (ki, ni)),
        ],
        out_specs=[
            pl.BlockSpec((bm, bn), lambda mi, ni, ki: (mi, ni)),
            pl.BlockSpec((bm, bn), lambda mi, ni, ki: (mi, ni)),
        ],
        out_shape=[jax.ShapeDtypeStruct((Mp, Np), xa.dtype),
                   jax.ShapeDtypeStruct((Mp, Np), xb.dtype)],
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32),
                        pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(seed_arr, mu_arr, off_arr, xa, xb, w)
    return ya[:M, :N], yb[:M, :N]


# ---------------------------------------------------------------------------
# noise materialization (tests / replay cross-checks only)
# ---------------------------------------------------------------------------

def _noise_kernel(seed_ref, u_ref, *, bk: int, bn: int):
    ki = pl.program_id(1)
    ni = pl.program_id(0)
    u_ref[...] = uniform_noise(seed_ref[0, 0], (bk, bn),
                               row_offset=ki * bk,
                               col_offset=ni * bn).astype(u_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bn", "bk", "interpret"))
def zo_noise(w_shape_like, seed, *, bn: int = 128, bk: int = 128,
             interpret: bool = True):
    """Materialize U(seed) with the kernel's exact PRNG stream
    (test/debug only — production never materializes U).  Because the
    stream is addressed by global coordinates, the result is independent
    of ``bn``/``bk`` and equals ``uniform_noise(seed, w.shape)``."""
    K, N = w_shape_like.shape
    _, (bn, Np), (bk, Kp) = _matmul_tiles(1, N, K, 1, bn, bk, 4, interpret)
    nn, nk = Np // bn, Kp // bk
    seed_arr = jnp.asarray([[seed]], jnp.int32)
    u = pl.pallas_call(
        functools.partial(_noise_kernel, bk=bk, bn=bn),
        grid=(nn, nk),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=pl.BlockSpec((bk, bn), lambda ni, ki: (ki, ni)),
        out_shape=jax.ShapeDtypeStruct((Kp, Np), jnp.float32),
        interpret=interpret,
    )(seed_arr)
    return u[:K, :N]
