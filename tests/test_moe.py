"""MoE: routing, capacity semantics, dispatch paths agree."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.distributed.sharding import AxisRules
from repro.kernels import ops as O
from repro.models import layers as L
from repro.models import moe as M
from repro.models.config import LayerSpec, ModelConfig, MoECfg
from repro.models.layers import ParamBuilder


def make(moe=None, d=32):
    cfg = ModelConfig(name="t", n_layers=1, d_model=d, n_heads=4,
                      n_kv_heads=4, d_ff=0, vocab=64,
                      moe=moe or MoECfg(n_experts=8, top_k=2,
                                        d_ff_expert=16,
                                        capacity_factor=4.0),
                      param_dtype="float32", compute_dtype="float32")
    pb = ParamBuilder(jax.random.PRNGKey(0), "init", jnp.float32)
    return cfg, M.init_moe(pb, "moe", cfg)


def test_route_normalized():
    cfg, params = make()
    x = jax.random.normal(jax.random.PRNGKey(1), (24, 32))
    gates, idx = M.route(params["router"], x, cfg)
    assert gates.shape == (24, 2) and idx.shape == (24, 2)
    np.testing.assert_allclose(np.asarray(jnp.sum(gates, -1)), 1.0,
                               rtol=1e-5)
    assert bool(jnp.all(idx >= 0)) and bool(jnp.all(idx < 8))


def test_xla_matches_reference_high_capacity():
    """With capacity_factor high enough nothing drops => exact match."""
    cfg, params = make()
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 16, 32))
    rules = AxisRules(mesh=None)
    ref = M.moe_reference(params, x, cfg)
    xla = M.moe_xla(params, x, cfg, rules)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(xla),
                               rtol=2e-4, atol=2e-5)


def test_capacity_drops_tokens():
    cfg, params = make(MoECfg(n_experts=2, top_k=1, d_ff_expert=16,
                              capacity_factor=0.25))
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 32, 32))
    rules = AxisRules(mesh=None)
    out = M.moe_xla(params, x, cfg, rules)
    assert out.shape == x.shape
    assert bool(jnp.all(jnp.isfinite(out)))
    # some tokens must pass through as zero contribution (dropped)
    norms = jnp.linalg.norm(out.reshape(32, 32), axis=-1)
    assert int(jnp.sum(norms < 1e-9)) > 0


def test_shared_expert_branch():
    cfg, params = make(MoECfg(n_experts=4, top_k=2, d_ff_expert=16,
                              capacity_factor=4.0, n_shared_experts=1))
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 8, 32))
    rules = AxisRules(mesh=None)
    out = M.moe_xla(params, x, cfg, rules)
    assert "shared" in params
    assert bool(jnp.all(jnp.isfinite(out)))


# --- the dropless layer over held experts (DeepSeek-V3 routing) ----------

def make_sigmoid(held=0, offset=0, n_experts=16, d=32, shared=2, seed=0):
    moe = MoECfg(n_experts=n_experts, top_k=4, d_ff_expert=16,
                 capacity_factor=None, n_shared_experts=shared,
                 scoring="sigmoid", routed_scale=2.5, n_held=held,
                 expert_offset=offset)
    cfg = ModelConfig(name="t", n_layers=1, d_model=d, n_heads=4,
                      n_kv_heads=4, d_ff=0, vocab=64, moe=moe,
                      param_dtype="float32", compute_dtype="float32")
    pb = ParamBuilder(jax.random.PRNGKey(seed), "init", jnp.float32)
    return cfg, M.init_moe(pb, "moe", cfg)


def test_sigmoid_route_against_hand_restatement():
    """Sigmoid scores, the top-k chosen on scores + bias, the gates the
    chosen experts' unbiased scores normalized over them and scaled: a
    bias moves the choice, not the gates."""
    cfg, params = make_sigmoid()
    x = jax.random.normal(jax.random.PRNGKey(1), (24, 32))
    xs = np.asarray(x, np.float64) @ np.asarray(params["router"],
                                                np.float64)
    scores = 1 / (1 + np.exp(-xs))
    bias = np.zeros(16)
    bias[3] = 10.0                       # expert 3 always chosen
    for b in (None, bias):
        gates, idx = M.route(params["router"], x, cfg,
                             None if b is None else jnp.asarray(b))
        pick = scores + (0 if b is None else b)
        want = np.argsort(-pick, axis=1)[:, :4]
        np.testing.assert_array_equal(np.sort(np.asarray(idx), 1),
                                      np.sort(want, 1))
        sel = np.take_along_axis(scores, np.asarray(idx), 1)
        np.testing.assert_allclose(np.asarray(gates),
                                   sel / sel.sum(1, keepdims=True) * 2.5,
                                   rtol=1e-5)
    assert (np.asarray(idx) == 3).any(axis=1).all()


def test_held_layer_is_dropless_reference():
    """The dropless layer holding every expert equals the all-experts
    combine, even where one expert takes every token."""
    cfg, params = make_sigmoid()
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 12, 32))
    out, rows = M.moe_held(params, x, cfg)
    assert rows is None
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(M.moe_reference(params, x, cfg)),
                               rtol=2e-4, atol=2e-5)
    skew = dict(params, router=params["router"].at[:, 5].add(50.0))
    np.testing.assert_allclose(
        np.asarray(M.moe_held(skew, x, cfg)[0]),
        np.asarray(M.moe_reference(skew, x, cfg)), rtol=2e-4, atol=2e-5)


def test_shares_sum_to_the_uncut_layer():
    """8 chips, 2 experts each: the routed parts every share computes for
    its own experts, with the shared experts counted once, add up to the
    uncut layer."""
    cfg, params = make_sigmoid()
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 16, 32))
    full, _ = M.moe_held(params, x, cfg)
    shared = L.mlp(params["shared"], x, cfg.activation, jnp.float32)
    total = shared
    for c in range(8):
        cc, _ = make_sigmoid(held=2, offset=2 * c)
        part = {k: params[k][2 * c:2 * c + 2] for k in ("up", "gate", "down")}
        part["router"] = params["router"]
        out, _ = M.moe_held(part, x, cc)
        total = total + out
    np.testing.assert_allclose(np.asarray(total), np.asarray(full),
                               rtol=2e-4, atol=2e-5)


def test_dual_probe_rows_equal_a_hand_count():
    """``moe_rows``: the (token, held expert) pairs of both streams, each
    routed with its own router weights, padding not counted."""
    cfg, params = make_sigmoid(held=6, shared=0)
    x = jax.random.normal(jax.random.PRNGKey(4), (4, 10, 32))
    seeds = O.leaf_seed_tree(params, jnp.int32(5))
    mu = 0.5
    pz = O.Perturb(seeds=seeds, mu=mu, dual=True, impl="xla")
    _, rows = M.moe_held(params, x, cfg, pz)
    pert = O.perturb_tree(params, seeds, mu)
    want = 0
    for p, half in ((params, x[:2]), (pert, x[2:])):
        _, idx = M.route(p["router"], half.reshape(-1, 32), cfg)
        want += int(np.sum(np.asarray(idx) < 6))
    assert int(rows) == want
