"""The grouped dual-probe ZO matmul over held experts: both streams' rows,
each in its own grouping, through one pass over W.  The Pallas kernel in
interpret mode and the jnp emulation are held to the float64 product of
the same bf16 values, and each stream's result to a pass that carries
that stream alone."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import grouped_matmul as GM
from repro.kernels import ops as O

E, K, N, BM = 4, 64, 48, 16
MU = 1e-3


def _layout(sizes_a, sizes_b, n_tiles):
    sa = jnp.asarray(sizes_a, jnp.int32)
    sb = jnp.asarray(sizes_b, jnp.int32)
    n_pairs = E * n_tiles
    return GM.group_layout(sa, sb, BM, n_tiles, n_pairs)


def _rows(key, starts, sizes, n_rows):
    """A buffer with each expert's rows at its start; the rest zero."""
    x = jax.random.normal(key, (n_rows, K), jnp.float32).astype(
        jnp.bfloat16)
    live = np.zeros(n_rows, bool)
    for s, n in zip(np.asarray(starts), sizes):
        live[s:s + n] = True
    return jnp.where(jnp.asarray(live)[:, None], x, 0), live


def _expected(x, w, starts, sizes, seed, mu, row_offset, expert_offset):
    """float64 x @ (W_e + mu U_e) for every live row, by expert."""
    u = np.asarray(O.uniform_noise(
        seed, (E * K, N), row_offset=row_offset + expert_offset * K),
        np.float64).reshape(E, K, N)
    wf = np.asarray(w.astype(jnp.float32), np.float64) + mu * u
    xf = np.asarray(x.astype(jnp.float32), np.float64)
    out = {}
    for e, (s, n) in enumerate(zip(np.asarray(starts), sizes)):
        for r in range(s, s + n):
            out[r] = xf[r] @ wf[e]
    return out


CASES = {
    "empty_groups": ([5, 0, 17, 0], [0, 3, 0, 9]),
    "all_to_one": ([0, 0, 40, 0], [0, 0, 40, 0]),
    "different_routing": ([16, 1, 33, 7], [2, 31, 0, 20]),
}


@pytest.mark.parametrize("impl", ["interpret", "xla"])
@pytest.mark.parametrize("offsets", [(0, 0), (3 * E * K, 0), (0, 2)],
                         ids=["plain", "rep_offset", "expert_offset"])
@pytest.mark.parametrize("case", list(CASES))
def test_dual_grouped_matches_float64(impl, offsets, case):
    sizes_a, sizes_b = CASES[case]
    row_offset, expert_offset = offsets
    n_tiles = GM.capacity_tiles(max(sum(sizes_a), sum(sizes_b)), 1, E, BM) \
        + E
    n_rows = (n_tiles + 1) * BM
    starts_a, starts_b, meta = _layout(sizes_a, sizes_b, n_tiles)
    xa, _ = _rows(jax.random.PRNGKey(1), starts_a, sizes_a, n_rows)
    xb, _ = _rows(jax.random.PRNGKey(2), starts_b, sizes_b, n_rows)
    w = (0.05 * jax.random.normal(jax.random.PRNGKey(3), (E, K, N))
         ).astype(jnp.bfloat16)
    seed = jnp.int32(11)
    ya, yb = O.zo_dual_grouped_matmul(
        xa, xb, w, meta, seed, 0.0, MU, bm=BM, row_offset=row_offset,
        expert_offset=expert_offset, impl=impl)
    for y, x, st, sz, mu in ((ya, xa, starts_a, sizes_a, 0.0),
                             (yb, xb, starts_b, sizes_b, MU)):
        want = _expected(x, w, st, sz, seed, mu, row_offset, expert_offset)
        y = np.asarray(y.astype(jnp.float32))
        for r, v in want.items():
            # the only rounding beyond f32 sums is the bf16 result's
            np.testing.assert_allclose(y[r], v, rtol=2 ** -8, atol=1e-3)


@pytest.mark.parametrize("case", list(CASES))
def test_each_stream_equals_its_single_pass(case):
    """A stream's rows give the same result whatever the other stream
    routes: the dual pass against a pass in which both sides carry that
    stream alone."""
    sizes_a, sizes_b = CASES[case]
    n_tiles = 12
    n_rows = (n_tiles + 1) * BM
    starts_a, starts_b, meta = _layout(sizes_a, sizes_b, n_tiles)
    xa, _ = _rows(jax.random.PRNGKey(4), starts_a, sizes_a, n_rows)
    xb, _ = _rows(jax.random.PRNGKey(5), starts_b, sizes_b, n_rows)
    w = (0.05 * jax.random.normal(jax.random.PRNGKey(6), (E, K, N))
         ).astype(jnp.bfloat16)
    kw = dict(bm=BM, impl="interpret", perturb_a=True, perturb_b=True)
    ya, yb = O.zo_dual_grouped_matmul(xa, xb, w, meta, 7, MU, -MU, **kw)
    _, _, meta_a = _layout(sizes_a, sizes_a, n_tiles)
    _, _, meta_b = _layout(sizes_b, sizes_b, n_tiles)
    sa, _ = O.zo_dual_grouped_matmul(xa, xa, w, meta_a, 7, MU, MU, **kw)
    sb, _ = O.zo_dual_grouped_matmul(xb, xb, w, meta_b, 7, -MU, -MU, **kw)
    for y, s, st, sz in ((ya, sa, starts_a, sizes_a),
                         (yb, sb, starts_b, sizes_b)):
        for s0, n in zip(np.asarray(st), sz):
            np.testing.assert_array_equal(np.asarray(y[s0:s0 + n]),
                                          np.asarray(s[s0:s0 + n]))


def test_layout_pairs_tiles_and_parks_the_rest():
    """Pair tiles join the j-th tile of an expert in both streams; a side
    without one writes the dummy tile; steps past those in use repeat the
    last and compute nothing."""
    n_tiles = 8
    _, _, meta = _layout([20, 0, 5, 0], [3, 0, 0, 17], n_tiles)
    e, ta, tb, va, vb = (np.asarray(r) for r in meta)
    used = 2 + 1 + 2                         # max(ceil(n / 16)) per expert
    assert list(e[:used]) == [0, 0, 2, 3, 3]
    assert list(ta[:used]) == [0, 1, 2, n_tiles, n_tiles]
    assert list(tb[:used]) == [0, n_tiles, n_tiles, 1, 2]
    assert list(va[:used]) == [1, 1, 1, 0, 0]
    assert list(vb[:used]) == [1, 0, 0, 1, 1]
    assert not va[used:].any() and not vb[used:].any()
    assert (e[used:] == e[used - 1]).all()
    assert (tb[used:] == tb[used - 1]).all()


def test_sizes_from_the_shape():
    # Moonlight at 8192 tokens: 768 rows expected per expert, a quarter
    # more in whole 128s; halved where the blocks would not fit
    widths = ((2048, 1408), (1408, 2048))
    assert GM.row_block(8192, 6, 64, widths) == 1024
    assert GM.row_block(8192, 6, 64, ((8192, 1408),)) == 256
    assert GM.capacity_tiles(8192, 6, 8, 1024) == 48 + 8
    assert GM.pair_tiles(8192, 6, 8, 1024) == 64
    assert GM.lane_block(1408) == 128 and GM.lane_block(48) == 48


def test_vmap_is_one_call_over_the_batch():
    """A cohort's calls, vmapped (each client its own routing, weights
    and seed), equal the calls one at a time."""
    n_tiles = 12
    n_rows = (n_tiles + 1) * BM
    routes = [([16, 1, 33, 7], [2, 31, 0, 20]), ([0, 0, 40, 0], [5, 0, 17, 0])]
    xa, xb, metas = [], [], []
    for c, (sa, sb) in enumerate(routes):
        st_a, st_b, meta = _layout(sa, sb, n_tiles)
        xa.append(_rows(jax.random.PRNGKey(10 + c), st_a, sa, n_rows)[0])
        xb.append(_rows(jax.random.PRNGKey(20 + c), st_b, sb, n_rows)[0])
        metas.append(meta)
    xa, xb, metas = jnp.stack(xa), jnp.stack(xb), jnp.stack(metas)
    w = (0.05 * jax.random.normal(jax.random.PRNGKey(6), (2, E, K, N))
         ).astype(jnp.bfloat16)
    seeds = jnp.asarray([3, 4], jnp.int32)

    def one(a, b, ww, m, s):
        return GM.zo_dual_grouped_matmul(a, b, ww, m, s, 0.0, MU, bm=BM,
                                         row_offset=E * K)

    ya, yb = jax.vmap(one)(xa, xb, w, metas, seeds)
    for c, (sa, sb) in enumerate(routes):
        ra, rb = one(xa[c], xb[c], w[c], metas[c], seeds[c])
        st_a, st_b, _ = _layout(sa, sb, n_tiles)
        for y, r, st, sz in ((ya[c], ra, st_a, sa), (yb[c], rb, st_b, sb)):
            for s0, n in zip(np.asarray(st), sz):
                np.testing.assert_array_equal(np.asarray(y[s0:s0 + n]),
                                              np.asarray(r[s0:s0 + n]))
