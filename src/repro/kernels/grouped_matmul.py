"""Pallas TPU kernel: the grouped dual-probe ZO matmul over the experts a
chip holds,

    ya[r] = xa[r] @ (W[e(r)] + mu_a * U_e(r))
    yb[r] = xb[r] @ (W[e(r)] + mu_b * U_e(r))

for every routed row r of each stream, e(r) the held expert the row was
sent to.  The clean and perturbed streams route differently (the router
and its inputs differ between them), so each stream brings its own rows,
sorted by expert; one call serves both.

Layout (:func:`group_layout`).  Each stream's rows lie in a buffer of
``bm``-row tiles, every expert's group starting on a tile boundary; the
last tile is a dummy that takes the writes of an absent side.  A *pair
tile* joins the j-th tile of expert e in both streams, so the grid walks
pair tiles: each step reads one W tile of expert e and makes its noise
once, for both streams (a side with fewer tiles of that expert computes
nothing and writes the dummy).  The buffer and the grid are sized for
the worst case (every token sends ``min(k, held)`` rows here), so nothing
is dropped; the steps past the pair tiles in use repeat the block
indices of the last one, so they move no data, and compute nothing.

Noise.  Expert e's weight ``W[e]`` (K, N) is rows ``(expert_offset + e)
* K`` onward of the layer's canonical 2-D field, shifted by
``row_offset`` (``rep * held * K`` for a leaf stacked over scan
repeats): with ``expert_offset`` 0 that is the canonical view of the
held (E, K, N) leaf, which is what the server's seed replay regenerates
(:func:`repro.kernels.ops.leaf_noise`).

The MXU feed is :func:`repro.kernels.zo_matmul._tile_product`'s: bf16
operands, the perturbed weight as three exact bf16 limbs.  Grid: (pair
tiles, N blocks), the whole K in one block, the row and expert of each
step read from scalar-prefetched metadata; under ``vmap`` a leading grid
axis walks the batch (each client's layout, weights and seed).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.zo_matmul import VMEM_LIMIT, _tile_product, uniform_noise

# the metadata rows of a layout (``group_layout``), one entry per pair tile
META = ("expert", "tile_a", "tile_b", "valid_a", "valid_b")


# VMEM the kernel's double-buffered blocks may take, of the VMEM_LIMIT the
# call asks for: the rest is the step's noise, limbs and products
BLOCK_BYTES = 24 * 2 ** 20


def block_bytes(bm: int, k: int, bn: int, itemsize: int = 2) -> int:
    """The double-buffered blocks of one step: both streams' rows in and
    out, and the W tile."""
    return 2 * (2 * bm * k + k * bn + 2 * bm * bn) * itemsize


def row_block(n_tokens: int, top_k: int, n_experts: int, widths) -> int:
    """Rows per tile: a quarter more than an expert's expected load
    (``n_tokens * top_k / n_experts``), in whole 128s, from 128 to 1024,
    so that an expert's group is mostly one tile and each W tile's noise
    and limbs are made once; halved while the blocks of a projection
    from any of ``widths`` (K, N pairs) would pass :data:`BLOCK_BYTES`."""
    want = 1.25 * n_tokens * top_k / n_experts
    bm = int(min(1024, max(128, -(-int(want) // 128) * 128)))
    while bm > 128 and any(block_bytes(bm, k, lane_block(n)) > BLOCK_BYTES
                           for k, n in widths):
        bm //= 2
    return bm


def capacity_tiles(n_tokens: int, top_k: int, held: int, bm: int) -> int:
    """Tiles of one stream's buffer, the dummy left out: the worst case
    of ``min(top_k, held)`` rows per token, plus one partial tile per
    expert."""
    return -(-n_tokens * min(top_k, held) // bm) + held


def pair_tiles(n_tokens: int, top_k: int, held: int, bm: int) -> int:
    """Grid length: at most ``ceil(n_tokens / bm)`` tiles per expert, and
    at most the tiles of both buffers."""
    return min(held * -(-n_tokens // bm),
               2 * capacity_tiles(n_tokens, top_k, held, bm))


def group_layout(sizes_a, sizes_b, bm: int, n_tiles: int, n_pairs: int):
    """The tile layout of both streams, from their rows per held expert
    ``sizes_a``, ``sizes_b`` (E,) int32.

    Returns (starts_a, starts_b, meta): each stream's first row of every
    expert's group (E,), and ``meta`` (5, n_pairs) int32 whose rows are
    :data:`META`: the expert, each side's tile (the dummy tile
    ``n_tiles`` where that side has none) and whether each side computes.
    Pair tiles past those in use repeat the last one in use, with both
    sides off."""
    ta = -(-sizes_a // bm)
    tb = -(-sizes_b // bm)
    first_a = jnp.cumsum(ta) - ta
    first_b = jnp.cumsum(tb) - tb
    per = jnp.maximum(ta, tb)
    ends = jnp.cumsum(per)
    used = ends[-1]
    g = jnp.arange(n_pairs)
    last = jnp.maximum(used - 1, 0)
    g = jnp.where(g < used, g, last)
    e = jnp.minimum(jnp.searchsorted(ends, g, side="right"), per.shape[0] - 1)
    j = g - (ends[e] - per[e])
    live = jnp.arange(n_pairs) < used
    va = live & (j < ta[e])
    vb = live & (j < tb[e])
    tile_a = jnp.where(j < ta[e], first_a[e] + j, n_tiles)
    tile_b = jnp.where(j < tb[e], first_b[e] + j, n_tiles)
    meta = jnp.stack([e, tile_a, tile_b, va, vb]).astype(jnp.int32)
    return first_a * bm, first_b * bm, meta


def lane_block(n: int) -> int:
    """N block: 128 lanes where they divide N, else the whole axis (a
    block equal to the axis is always legal); the x block stays resident
    across N blocks, so narrow W tiles cost no extra reads."""
    return 128 if n % 128 == 0 else n


def _kernel(meta_ref, seed_ref, mu_ref, off_ref, xa_ref, xb_ref, w_ref,
            oa_ref, ob_ref, *, k: int, bn: int, n_pairs: int,
            perturb_a: bool, perturb_b: bool):
    c = pl.program_id(0)
    g = pl.program_id(1)
    ni = pl.program_id(2)

    def at(row):
        return meta_ref[(c * len(META) + row) * n_pairs + g]

    va = at(3) != 0
    vb = at(4) != 0

    @pl.when(va | vb)
    def _():
        u = None
        if perturb_a or perturb_b:
            u = uniform_noise(seed_ref[c, 0], (k, bn),
                              row_offset=off_ref[c, 0] + at(0) * k,
                              col_offset=ni * bn)
        w = w_ref[0, 0]

        @pl.when(va)
        def _a():
            oa_ref[0] = _tile_product(
                xa_ref[0], w, mu_ref[0, 0],
                u if perturb_a else None).astype(oa_ref.dtype)

        @pl.when(vb)
        def _b():
            ob_ref[0] = _tile_product(
                xb_ref[0], w, mu_ref[0, 1],
                u if perturb_b else None).astype(ob_ref.dtype)


def _call(xa, xb, w, meta, seed, mu, off, *, bm: int, interpret: bool,
          perturb_a: bool, perturb_b: bool):
    """The kernel over a leading batch of B problems (a vmapped cohort):
    xa, xb (B, R, K); w (B, E, K, N); meta (B, 5, P); seed, off (B,)."""
    B, R, K = xa.shape
    N = w.shape[-1]
    bn = lane_block(N)
    nn = N // bn
    n_pairs = meta.shape[-1]
    rows = len(META)

    def at(m, c, row, g):
        return m[(c * rows + row) * n_pairs + g]

    def lane(c, g, ni, m):
        # a step past the pair tiles in use keeps the last step's blocks
        live = (at(m, c, 3, g) != 0) | (at(m, c, 4, g) != 0)
        return jnp.where(live, ni, nn - 1)

    def x_map(row):
        def f(c, g, ni, m):
            return (c, at(m, c, row, g), 0)
        return f

    def o_map(row):
        def f(c, g, ni, m):
            return (c, at(m, c, row, g), lane(c, g, ni, m))
        return f

    def w_map(c, g, ni, m):
        return (c, at(m, c, 0, g), 0, lane(c, g, ni, m))

    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    kernel = functools.partial(_kernel, k=K, bn=bn, n_pairs=n_pairs,
                               perturb_a=perturb_a, perturb_b=perturb_b)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, n_pairs, nn),
            in_specs=[smem, smem, smem,
                      pl.BlockSpec((1, bm, K), x_map(1)),
                      pl.BlockSpec((1, bm, K), x_map(2)),
                      pl.BlockSpec((1, 1, K, bn), w_map)],
            out_specs=[pl.BlockSpec((1, bm, bn), o_map(1)),
                       pl.BlockSpec((1, bm, bn), o_map(2))]),
        out_shape=[jax.ShapeDtypeStruct((B, R, N), xa.dtype),
                   jax.ShapeDtypeStruct((B, R, N), xb.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(meta.reshape(-1), seed.reshape(B, 1), mu.reshape(1, 2),
      off.reshape(B, 1), xa, xb, w)


@functools.lru_cache(maxsize=None)
def _batched(bm: int, interpret: bool, perturb_a: bool, perturb_b: bool):
    """The call for one problem, whose ``vmap`` is one call over the batch
    (a grid axis), not JAX's loop of calls for a batched scalar prefetch:
    that loop's slices are fused into the call, which then gets XLA's
    scoped VMEM and no name of its own in the compiled program."""
    call = functools.partial(_call, bm=bm, interpret=interpret,
                             perturb_a=perturb_a, perturb_b=perturb_b)

    @jax.custom_batching.custom_vmap
    def one(xa, xb, w, meta, seed, mu, off):
        ya, yb = call(xa[None], xb[None], w[None], meta[None], seed[None],
                      mu, off[None])
        return ya[0], yb[0]

    @one.def_vmap
    def many(size, batched, xa, xb, w, meta, seed, mu, off):
        if batched[5]:
            raise NotImplementedError("mu is one pair for the whole batch")
        xa, xb, w, meta, seed, off = (
            x if b else jnp.broadcast_to(x, (size,) + x.shape)
            for x, b in zip((xa, xb, w, meta, seed, off),
                            batched[:5] + batched[6:]))
        return call(xa, xb, w, meta, seed, mu, off), (True, True)

    return one


@functools.partial(jax.jit, static_argnames=("bm", "interpret", "perturb_a",
                                             "perturb_b"))
def zo_dual_grouped_matmul(xa, xb, w, meta, seed, mu_a, mu_b, *,
                           row_offset=0, expert_offset=0, bm: int,
                           interpret: bool = True, perturb_a: bool = False,
                           perturb_b: bool = True):
    """(ya, yb) for both streams' grouped rows in one pass over W.

    xa, xb: (R, K) rows in the layout of ``meta`` (:func:`group_layout`),
    R a whole number of ``bm`` tiles with the dummy last; w: (E, K, N),
    the held experts.  Returns (R, N) per stream; rows of tiles no pair
    tile computes are left unwritten, so a caller reads only its rows.
    Under ``vmap`` (the cohort) the batch is a leading grid axis.
    """
    R, K = xa.shape
    assert xb.shape == xa.shape and R % bm == 0, (xa.shape, xb.shape, bm)
    assert w.shape[1] == K
    off = jnp.asarray(row_offset, jnp.int32) + jnp.int32(expert_offset * K)
    mu = jnp.stack([jnp.asarray(mu_a, jnp.float32),
                    jnp.asarray(mu_b, jnp.float32)])
    return _batched(bm, interpret, perturb_a, perturb_b)(
        xa, xb, w, meta.astype(jnp.int32), jnp.asarray(seed, jnp.int32), mu,
        off)
