"""Per-kernel validation: shape/dtype sweeps vs the pure-jnp oracles
(interpret mode executes the kernel body on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


@pytest.mark.parametrize("m,k,n", [(32, 128, 128), (64, 256, 128),
                                   (128, 128, 256)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_zo_matmul_shapes_dtypes(m, k, n, dtype):
    x = jax.random.normal(jax.random.PRNGKey(0), (m, k)).astype(dtype)
    w = jax.random.normal(jax.random.PRNGKey(1), (k, n)).astype(dtype)
    u = ops.zo_noise(w, 7, bk=128, bn=128)
    y_k = ops.zo_matmul(x, w, 7, 0.05, bm=32, bn=128, bk=128)
    y_r = ref.zo_matmul_ref(x, w, u, 0.05)
    tol = 5e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(y_k, np.float32),
                               np.asarray(y_r, np.float32),
                               rtol=tol, atol=tol * 10)


def test_zo_matmul_seed_determinism_and_variation():
    w = jax.random.normal(jax.random.PRNGKey(1), (128, 128))
    u1 = ops.zo_noise(w, 7)
    u2 = ops.zo_noise(w, 7)
    u3 = ops.zo_noise(w, 8)
    np.testing.assert_array_equal(np.asarray(u1), np.asarray(u2))
    assert float(jnp.max(jnp.abs(u1 - u3))) > 0.1


def test_zo_noise_statistics():
    w = jnp.zeros((512, 512))
    u = ops.zo_noise(w, 123)
    assert abs(float(u.mean())) < 0.02
    assert abs(float(u.var()) - 1.0) < 0.05    # unit variance uniform


def test_zo_clean_path_is_plain_matmul():
    x = jax.random.normal(jax.random.PRNGKey(0), (32, 128))
    w = jax.random.normal(jax.random.PRNGKey(1), (128, 128))
    y = ops.zo_matmul(x, w, 0, 0.0, perturb=False, bm=32)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref.matmul_ref(
        x, w)), rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("seq,h,kv,d", [(64, 4, 2, 32), (48, 4, 4, 16),
                                        (64, 8, 1, 32)])
@pytest.mark.parametrize("kwargs", [dict(causal=True),
                                    dict(causal=True, window=17),
                                    dict(causal=True, cap=30.0),
                                    dict(causal=False)])
def test_flash_attention_sweep(seq, h, kv, d, kwargs):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (2, seq, h, d))
    k = jax.random.normal(ks[1], (2, seq, kv, d))
    v = jax.random.normal(ks[2], (2, seq, kv, d))
    o_k = ops.flash_attention(q, k, v, bq=16, bk=16, **kwargs)
    o_r = ref.flash_attention_ref(q, k, v, **kwargs)
    np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_r),
                               rtol=2e-4, atol=2e-5)


def test_flash_attention_bf16():
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (1, 32, 4, 32)).astype(jnp.bfloat16)
    k = jax.random.normal(ks[1], (1, 32, 2, 32)).astype(jnp.bfloat16)
    v = jax.random.normal(ks[2], (1, 32, 2, 32)).astype(jnp.bfloat16)
    o_k = ops.flash_attention(q, k, v, bq=16, bk=16, causal=True)
    o_r = ref.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(o_k, np.float32),
                               np.asarray(o_r, np.float32),
                               rtol=5e-2, atol=5e-2)


@pytest.mark.parametrize("b,s,w,bt,bw", [(2, 64, 32, 16, 16),
                                         (1, 128, 64, 32, 64),
                                         (3, 32, 16, 8, 16)])
def test_rg_lru_scan_sweep(b, s, w, bt, bw):
    a = jax.random.uniform(jax.random.PRNGKey(5), (b, s, w),
                           minval=0.3, maxval=0.999)
    bb = jax.random.normal(jax.random.PRNGKey(6), (b, s, w))
    h_k = ops.rg_lru_scan(a, bb, bt=bt, bw=bw)
    h_r = ref.rg_lru_scan_ref(a, bb)
    np.testing.assert_allclose(np.asarray(h_k), np.asarray(h_r),
                               rtol=2e-4, atol=2e-5)

# --- fused dual probe -------------------------------------------------------

_BS = dict(bm=16, bn=32, bk=32)


@pytest.mark.parametrize("m,k,n,dtype,bs", [
    pytest.param(32, 128, 128, jnp.float32, _BS, id="32-128-128"),
    pytest.param(64, 256, 128, jnp.float32, _BS, id="64-256-128"),
    pytest.param(16, 96, 64, jnp.float32, _BS, id="16-96-64"),
    pytest.param(64, 256, 128, jnp.bfloat16, _BS, id="bf16-64-256-128"),
    pytest.param(16, 96, 64, jnp.bfloat16, _BS, id="bf16-16-96-64"),
    pytest.param(48, 640, 256, jnp.bfloat16, {}, id="bf16-chosen-48-640-256"),
])
def test_zo_dual_matmul_matches_two_single_passes(m, k, n, dtype, bs):
    """One fused pass == two independent zo_matmul calls, bitwise, for
    the f32 contraction and for the bf16 limb feed alike."""
    xa = jax.random.normal(jax.random.PRNGKey(0), (m, k), dtype)
    xb = jax.random.normal(jax.random.PRNGKey(1), (m, k), dtype)
    w = jax.random.normal(jax.random.PRNGKey(2), (k, n), dtype)
    ya, yb = ops.zo_dual_matmul(xa, xb, w, 11, 0.0, 0.05,
                                impl="interpret", **bs)
    ya1 = ops.zo_matmul(xa, w, 11, 0.0, impl="interpret", perturb=False,
                        **bs)
    yb1 = ops.zo_matmul(xb, w, 11, 0.05, impl="interpret", **bs)
    np.testing.assert_array_equal(np.asarray(ya), np.asarray(ya1))
    np.testing.assert_array_equal(np.asarray(yb), np.asarray(yb1))


def test_zo_dual_matmul_vs_ref_oracle():
    xa = jax.random.normal(jax.random.PRNGKey(0), (32, 128))
    xb = jax.random.normal(jax.random.PRNGKey(1), (32, 128))
    w = jax.random.normal(jax.random.PRNGKey(2), (128, 64))
    u = ops.zo_noise(w, 9)
    ya, yb = ops.zo_dual_matmul(xa, xb, w, 9, 0.0, 0.1, bm=32)
    ra, rb = ref.zo_dual_matmul_ref(xa, xb, w, u, 0.0, 0.1)
    np.testing.assert_allclose(np.asarray(ya), np.asarray(ra),
                               rtol=5e-5, atol=5e-4)
    np.testing.assert_allclose(np.asarray(yb), np.asarray(rb),
                               rtol=5e-5, atol=5e-4)


def _float64_dual(xa, xb, w, seed, mu_a, mu_b, perturb_a):
    """The dual probe in float64 on the operands' own values, and the
    bound on the f32 accumulation error (K·2^-24 · |x| @ |w'|)."""
    u = np.asarray(ops.uniform_noise(seed, w.shape), np.float64)
    w64 = np.asarray(w, np.float64)
    out = []
    for x, mu, pert in ((xa, mu_a, perturb_a), (xb, mu_b, True)):
        x64 = np.asarray(x, np.float64)
        wp = w64 + mu * u if pert else w64
        out.append((x64 @ wp,
                    x.shape[1] * 2.0 ** -24 * (np.abs(x64) @ np.abs(wp))))
    return out


@pytest.mark.parametrize("m,k,n,bs", [
    (64, 256, 128, _BS),
    (32, 128, 64, dict(bm=32)),
    (40, 27, 64, {}),          # chosen: whole axes (an im2col conv's K, N)
    (48, 1100, 1030, {}),      # chosen: no aligned divisor, padded
])
@pytest.mark.parametrize("antithetic", [False, True])
def test_zo_dual_matmul_bf16_matches_float64(m, k, n, bs, antithetic):
    """bf16 operands through the limb feed: each stream is the float64
    product of the same bf16 values with ``w + mu*U``, within the bf16
    rounding of the output (half an ulp) and the f32 accumulation."""
    xa = jax.random.normal(jax.random.PRNGKey(3), (m, k), jnp.bfloat16)
    xb = jax.random.normal(jax.random.PRNGKey(4), (m, k), jnp.bfloat16)
    w = (0.05 * jax.random.normal(jax.random.PRNGKey(5), (k, n))).astype(
        jnp.bfloat16)
    mu_a, mu_b = (1e-3, -1e-3) if antithetic else (0.0, 1e-3)
    ys = ops.zo_dual_matmul(xa, xb, w, 19, mu_a, mu_b, impl="interpret",
                            perturb_a=antithetic, **bs)
    for y, (r, acc) in zip(ys, _float64_dual(xa, xb, w, 19, mu_a, mu_b,
                                             antithetic)):
        assert y.dtype == jnp.bfloat16
        err = np.abs(np.asarray(y, np.float64) - r)
        assert np.all(err <= 2.0 ** -8 * np.abs(r) + acc), err.max()


def test_limb_feed_is_exact():
    """The three bf16 limbs sum to the f32 ``w + mu*U`` exactly, and a
    tile's product through them loses nothing: an identity ``x`` gives
    back ``w + mu*U`` bit for bit (and ``w`` itself on the clean
    stream), which an f32 weight rounded to bf16, or a limb left out,
    would not."""
    from repro.kernels import zo_matmul as ZM
    k, n = 64, 128
    w = (0.05 * jax.random.normal(jax.random.PRNGKey(7), (k, n))).astype(
        jnp.bfloat16)
    u = ZM.uniform_noise(23, (k, n))
    wp = w.astype(jnp.float32) + 1e-3 * u
    limbs = ZM._limbs(wp)
    assert all(l.dtype == jnp.bfloat16 for l in limbs)
    np.testing.assert_array_equal(
        sum(np.asarray(l, np.float64) for l in limbs), np.asarray(wp))
    eye = jnp.eye(k, dtype=jnp.bfloat16)
    np.testing.assert_array_equal(
        np.asarray(ZM._tile_product(eye, w, 1e-3, u)), np.asarray(wp))
    np.testing.assert_array_equal(
        np.asarray(ZM._tile_product(eye, w, 1e-3, None)),
        np.asarray(w, np.float32))


def test_zo_dual_matmul_antithetic_pair():
    x = jax.random.normal(jax.random.PRNGKey(0), (16, 64))
    w = jax.random.normal(jax.random.PRNGKey(1), (64, 64))
    ya, yb = ops.zo_dual_matmul(x, x, w, 3, 0.05, -0.05,
                                perturb_a=True, perturb_b=True, bm=16)
    yp = ops.zo_matmul(x, w, 3, 0.05, bm=16)
    ym = ops.zo_matmul(x, w, 3, -0.05, bm=16)
    np.testing.assert_array_equal(np.asarray(ya), np.asarray(yp))
    np.testing.assert_array_equal(np.asarray(yb), np.asarray(ym))


@pytest.mark.parametrize("bs", [dict(bm=16, bn=32, bk=32),
                                dict(bm=32, bn=64, bk=128),
                                dict(bm=64, bn=128, bk=64)])
def test_noise_block_size_invariance(bs):
    """The hash-noise field is a function of global (row, col) only —
    re-tiling must not change a bit of it.  The matmul result is only
    allclose across bk (the K-reduction split changes summation order)."""
    x = jax.random.normal(jax.random.PRNGKey(0), (64, 128))
    w = jax.random.normal(jax.random.PRNGKey(1), (128, 128))
    base = ops.zo_matmul(x, w, 21, 0.1, bm=64, bn=128, bk=128)
    y = ops.zo_matmul(x, w, 21, 0.1, impl="interpret", **bs)
    np.testing.assert_allclose(np.asarray(y), np.asarray(base),
                               rtol=1e-5, atol=1e-4)
    u = ops.zo_noise(w, 21)
    u2 = ops.zo_noise(w, 21, bn=bs["bn"], bk=bs["bk"])
    np.testing.assert_array_equal(np.asarray(u), np.asarray(u2))


def test_xla_emulation_matches_kernel():
    """impl="xla" consumes the identical hash-noise stream (bitwise);
    the matmul itself differs only by contraction/FMA order."""
    from repro.kernels import zo_matmul as ZM
    x = jax.random.normal(jax.random.PRNGKey(0), (32, 128))
    w = jax.random.normal(jax.random.PRNGKey(1), (128, 64))
    u_jnp = ZM.uniform_noise(5, w.shape)           # pure-jnp stream
    u_kern = ops.zo_noise(w, 5)                    # interpret kernel
    np.testing.assert_array_equal(np.asarray(u_jnp), np.asarray(u_kern))
    yk = ops.zo_matmul(x, w, 5, 0.07, impl="interpret", bm=32)
    ye = ops.zo_matmul(x, w, 5, 0.07, impl="xla")
    np.testing.assert_allclose(np.asarray(yk), np.asarray(ye),
                               rtol=1e-5, atol=1e-4)
    da, db = ops.zo_dual_matmul(x, x, w, 5, 0.0, 0.07, impl="interpret",
                                bm=32)
    ea, eb = ops.zo_dual_matmul(x, x, w, 5, 0.0, 0.07, impl="xla")
    np.testing.assert_allclose(np.asarray(da), np.asarray(ea),
                               rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(np.asarray(db), np.asarray(eb),
                               rtol=1e-5, atol=1e-4)


def test_row_offset_addresses_global_rows():
    """row_offset r*K must reproduce rows [r*K, (r+1)*K) of the stacked
    field — the contract scan-stacked layers rely on."""
    from repro.kernels import zo_matmul as ZM
    K, N = 64, 64
    stacked = ZM.uniform_noise(13, (3 * K, N))
    for r in range(3):
        u_r = ZM.uniform_noise(13, (K, N), row_offset=r * K)
        np.testing.assert_array_equal(np.asarray(u_r),
                                      np.asarray(stacked[r * K:(r + 1) * K]))


# the fed cells' per-client dense shapes (M = 4096 tokens per client) and
# ResNet-18's im2col convs at CIFAR size (M = 8 images x H x W)
_CELL_SHAPES = [(4096, 1024, 1024), (4096, 1024, 4096), (4096, 4096, 1024),
                (4096, 768, 768), (4096, 768, 3072), (4096, 3072, 768)]
_RESNET_SHAPES = [(8192, 27, 64), (8192, 576, 64), (2048, 576, 128),
                  (2048, 1152, 128), (2048, 64, 128), (512, 1152, 256),
                  (512, 2304, 256), (128, 2304, 512), (128, 4608, 512),
                  (128, 256, 512)]


@pytest.mark.parametrize("m,k,n", _CELL_SHAPES + _RESNET_SHAPES)
@pytest.mark.parametrize("itemsize", [2, 4])
def test_choose_blocks(m, k, n, itemsize):
    """Lane blocks are 128-aligned or whole axes, row blocks take at least
    512 rows where M has them, and the kernel's VMEM estimate stays under
    the limit handed to Mosaic.  The cells' shapes need no padding."""
    from repro.kernels import zo_matmul as ZM
    bm, bk, bn = ZM.choose_blocks(m, k, n, itemsize)
    for b, dim in ((bk, k), (bn, n)):
        assert b == dim or (b % 128 == 0 and 256 <= b <= 512)
    assert bm == m or bm % 16 == 0
    assert bm >= min(m, 512)
    assert ZM.vmem_bytes(bm, bk, bn, itemsize) <= ZM.VMEM_LIMIT
    if (m, k, n) in _CELL_SHAPES:
        assert m % bm == 0 and k % bk == 0 and n % bn == 0
