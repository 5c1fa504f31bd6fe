"""ZO estimator: direction quality, determinism, seed replay."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import zo as Z
from repro.kernels import ops as O


def quad_loss(params):
    # f(x) = 0.5 ||x - c||^2 with pytree params
    loss = 0.0
    for i, l in enumerate(jax.tree.leaves(params)):
        loss = loss + 0.5 * jnp.sum((l - 0.1 * (i + 1)) ** 2)
    return loss


def sin_loss(params):
    return jnp.sum(params["a"] ** 2) + jnp.sum(jnp.sin(params["b"]["c"]))


def dual_of(loss):
    """A dual-probe loss from a plain one: both losses of a pair, the
    perturbed one at the materialized ``theta + mu*U(seeds)``."""
    def dual_loss(params, seeds, mu):
        return (loss(params), loss(O.perturb_tree(params, seeds, mu)),
                None, {})
    return dual_loss


def norm(tree):
    return jnp.sqrt(sum(jnp.sum(jnp.square(l))
                        for l in jax.tree.leaves(tree)))


def make_params():
    return {"a": jnp.ones((8, 4)), "b": {"c": jnp.full((6,), -1.0)}}


def test_zo_gradient_descends_quadratic():
    params = make_params()
    zo = Z.ZOConfig(mu=1e-4, n_pairs=8)
    g, info = Z.zo_gradient(dual_of(quad_loss), params, jnp.int32(0), zo)
    true_g = jax.grad(quad_loss)(params)
    # cosine similarity between ZO estimate and true gradient
    num = sum(jnp.sum(a * b) for a, b in zip(jax.tree.leaves(g),
                                             jax.tree.leaves(true_g)))
    cos = num / (norm(g) * norm(true_g))
    assert cos > 0.25, float(cos)   # d=38, 8 pairs: positive alignment
    # a small step along -g decreases the loss
    l0 = quad_loss(params)
    l1 = quad_loss(Z.add_scaled(params, g, -1e-2 / norm(g)))
    assert l1 < l0


def test_zo_estimator_unbiased_direction():
    """Averaged over many seeds, the ZO estimate approaches grad f."""
    params = {"x": jnp.array([1.0, -2.0, 0.5, 3.0])}
    zo = Z.ZOConfig(mu=1e-5, n_pairs=1)
    grad = jax.jit(lambda s: Z.zo_gradient(dual_of(quad_loss), params, s,
                                           zo)[0]["x"])
    n = 300
    est = sum(grad(jnp.int32(s)) for s in range(n)) / n
    true = jax.grad(quad_loss)(params)["x"]
    assert float(jnp.linalg.norm(est - true) / jnp.linalg.norm(true)) < 0.35


def test_perturbation_determinism_and_norm():
    """One seed gives one direction, bit for bit; its entries are iid
    with unit variance (E[u u^T] = I, so the estimator needs no dimension
    factor), and another seed gives another direction."""
    params = {"a": jnp.zeros((64, 32)), "b": {"c": jnp.zeros((1024,))}}
    seeds = O.leaf_seed_tree(params, jnp.int32(3))
    u1 = O.kernel_direction_tree(params, seeds)
    u2 = O.kernel_direction_tree(params, O.leaf_seed_tree(params,
                                                          jnp.int32(3)))
    for a, b in zip(jax.tree.leaves(u1), jax.tree.leaves(u2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    flat = np.concatenate([np.ravel(l) for l in jax.tree.leaves(u1)])
    assert abs(float(np.mean(flat ** 2)) - 1.0) < 0.05
    assert abs(float(np.mean(flat))) < 0.05
    u3 = O.kernel_direction_tree(params, O.leaf_seed_tree(params,
                                                          jnp.int32(4)))
    assert not np.array_equal(np.asarray(u1["a"]), np.asarray(u3["a"]))


REPLAY_CASES = {
    # (params, loss, mu, n_pairs, base seed, (rtol, atol) on the params)
    "quadratic-2pairs": (make_params, quad_loss, 1e-4, 2, 11, (1e-5, 1e-6)),
    "quadratic-3pairs": (lambda: {"w": jnp.ones((6, 3)), "b": {
        "c": jnp.linspace(-1.0, 1.0, 5)}}, quad_loss, 1e-4, 3, 11,
        (0, 1e-7)),
    "frozen-leaf": (lambda: {
        "a": jax.random.normal(jax.random.PRNGKey(0), (4, 8)),
        "b": {"c": jax.random.normal(jax.random.PRNGKey(1), (8,)),
              "froz": None}}, sin_loss, 1e-3, 2, 9, (1e-5, 1e-6)),
}


@pytest.mark.parametrize("case", list(REPLAY_CASES))
def test_replay_update_matches_gradient_update(case):
    """theta - lr*g == theta - lr*replay_gradient(theta, seed, coeffs):
    (base_seed, coeffs) alone regenerate the estimator's gradient — the
    replay scan is its accumulation minus the forwards, the directions
    bit-identical, the sum equal to FMA rounding; frozen (None) leaves
    stay None."""
    make, loss, mu, n_pairs, base, (rtol, atol) = REPLAY_CASES[case]
    params = make()
    zo = Z.ZOConfig(mu=mu, n_pairs=n_pairs)
    g, info = Z.zo_gradient(dual_of(loss), params, jnp.int32(base), zo)
    g2 = Z.replay_gradient(params, jnp.int32(base), info["coeffs"])
    for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(g2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)
    lr = 1e-3
    direct = Z.add_scaled(params, g, -lr)
    replayed = Z.add_scaled(params, g2, -lr)
    for a, b in zip(jax.tree.leaves(direct), jax.tree.leaves(replayed)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=rtol, atol=atol)
    if "froz" in params.get("b", {}):
        assert g2["b"]["froz"] is None
