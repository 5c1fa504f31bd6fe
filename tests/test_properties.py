"""Hypothesis property tests on system invariants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.core.split import (combine, dequantize_smashed, partition,
                              quantize_smashed)
from repro.core.zo import add_scaled
from repro.models.layers import rmsnorm, softcap

SETTINGS = dict(max_examples=25, deadline=None)

floats = st.floats(min_value=-50.0, max_value=50.0, allow_nan=False,
                   width=32)


@given(st.lists(floats, min_size=4, max_size=32),
       st.floats(min_value=1.0, max_value=100.0))
@settings(**SETTINGS)
def test_softcap_bounded_and_monotone(xs, cap):
    x = jnp.asarray(xs, jnp.float32)
    y = softcap(x, cap)
    assert float(jnp.max(jnp.abs(y))) <= cap + 1e-4
    order_x = jnp.argsort(x)
    assert bool(jnp.all(jnp.diff(y[order_x]) >= -1e-6))


@given(st.integers(min_value=1, max_value=8),
       st.integers(min_value=2, max_value=64), st.integers(0, 2 ** 31 - 1))
@settings(**SETTINGS)
def test_quantize_smashed_error_bound(b, d, seed):
    x = jax.random.normal(jax.random.PRNGKey(seed), (b, d))
    q, scale = quantize_smashed(x)
    back = dequantize_smashed(q, scale, jnp.float32)
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    # int8 symmetric quantization: error <= amax/254 per element
    assert bool(jnp.all(jnp.abs(back - x) <= amax / 127.0 + 1e-6))


@given(st.integers(0, 2 ** 31 - 1), st.floats(-2.0, 2.0, allow_nan=False))
@settings(**SETTINGS)
def test_add_scaled_linear(seed, s):
    from repro.kernels import ops as O
    tree = {"a": jax.random.normal(jax.random.PRNGKey(seed), (4, 2))}
    u = O.kernel_direction_tree(tree, O.leaf_seed_tree(tree, seed))
    out = add_scaled(tree, u, s)
    expect = tree["a"] + s * u["a"]
    np.testing.assert_allclose(np.asarray(out["a"]), np.asarray(expect),
                               rtol=1e-5, atol=1e-6)


@given(st.integers(0, 2 ** 31 - 1))
@settings(**SETTINGS)
def test_partition_combine_roundtrip(seed):
    tree = {"wq": {"w": jnp.ones((2, 2))}, "mlp": {"up": {"w":
            jnp.zeros(3)}}, "norm": {"scale": jnp.ones(4)}}
    k = jax.random.randint(jax.random.PRNGKey(seed), (), 0, 3)
    preds = [lambda p: "wq" in p, lambda p: "mlp" in p, lambda p: True]
    sel, rest = partition(tree, preds[int(k)])
    merged = combine(sel, rest)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(merged)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@given(st.integers(1, 6), st.integers(2, 32), st.integers(0, 2 ** 31 - 1))
@settings(**SETTINGS)
def test_rmsnorm_scale_invariant_rows(b, d, seed):
    """rmsnorm(c*x) == rmsnorm(x) for c>0 (per-row scale invariance)."""
    x = jax.random.normal(jax.random.PRNGKey(seed), (b, d)) + 0.1
    p = {"scale": jnp.zeros(d)}
    y1 = rmsnorm(p, x)
    y2 = rmsnorm(p, 3.7 * x)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               rtol=2e-3, atol=2e-4)


@given(st.integers(2, 6), st.integers(0, 2 ** 31 - 1))
@settings(**SETTINGS)
def test_fedavg_in_convex_hull(n, seed):
    from repro.core.aggregate import fedavg
    stacked = {"w": jax.random.normal(jax.random.PRNGKey(seed), (n, 5))}
    avg = fedavg(stacked)
    lo = jnp.min(stacked["w"], axis=0) - 1e-6
    hi = jnp.max(stacked["w"], axis=0) + 1e-6
    assert bool(jnp.all((avg["w"] >= lo) & (avg["w"] <= hi)))


@given(st.integers(1, 9), st.integers(1, 3), st.integers(1, 3),
       st.integers(0, 2 ** 31 - 1), st.integers(1, 12))
@settings(**SETTINGS)
def test_seed_replay_chunked_bit_exact(n, h, pairs, seed, chunk):
    """For any cohort shape (n, h, n_pairs) and any chunk size, chunked
    streaming continues the same scan carry as the one-shot replay —
    bit-for-bit, because the fp32 add order is preserved."""
    from repro.core.aggregate import seed_replay_aggregate
    from repro.kernels import ops as O
    params = {"w": jnp.ones((4, 3)), "b": jnp.linspace(-1.0, 1.0, 5)}
    seeds = O.fold_seed(jnp.int32(seed), jnp.arange(n))
    coeffs = jax.random.normal(jax.random.PRNGKey(seed + 1),
                               (n, h, pairs))
    mask = (jax.random.uniform(jax.random.PRNGKey(seed + 2), (n,))
            > 0.3).astype(jnp.float32)
    one = seed_replay_aggregate(params, seeds, coeffs, 1e-2, mask)
    chunked = seed_replay_aggregate(params, seeds, coeffs, 1e-2, mask,
                                    chunk=chunk)
    for a, b in zip(jax.tree.leaves(one), jax.tree.leaves(chunked)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@given(st.integers(0, 2 ** 31 - 1))
@settings(**SETTINGS)
def test_lm_loss_mask_respected(seed):
    from repro.models.transformer import lm_loss
    logits = jax.random.normal(jax.random.PRNGKey(seed), (2, 5, 11))
    labels = jax.random.randint(jax.random.PRNGKey(seed + 1), (2, 5),
                                0, 7)
    l1 = lm_loss(logits, labels, 7)
    # flipping masked positions must not change the loss
    labels_masked = labels.at[:, 0].set(-100)
    l2a = lm_loss(logits, labels_masked, 7)
    logits_perturbed = logits.at[:, 0].add(100.0)
    # only masked row perturbed => same masked loss
    l2b = lm_loss(logits_perturbed, labels_masked, 7)
    np.testing.assert_allclose(float(l2a), float(l2b), rtol=1e-5)
    assert jnp.isfinite(l1)
