"""Multi-head latent attention and the dropless held-expert MoE through the
client's dual probe: MLA against a plain restatement, a perturbed MLA or
MoE block through the kernel path against the materialized fallback, no
such block left to the fallback, decoding through the cache against the
full forward, and the lean replay against the dense update over the
(E, K, N) expert leaves."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.core import protocols as P
from repro.core import zo as Z
from repro.distributed.sharding import AxisRules
from repro.kernels import ops as O
from repro.models import attention as A
from repro.models import layers as L
from repro.models import transformer as T
from repro.models.config import LayerSpec
from repro.optim.optimizers import make_optimizer

RULES = AxisRules(mesh=None)
ARCHS = ["moonlight-16b-a3b", "kimi-k2-1t-a32b"]


def _mla_numpy(p, x, cfg):
    """MLA restated in float64: q (optionally low-rank), latent c =
    RMSNorm, per-head k_nope / v from c, one rope key for all heads,
    halves rotated, causal softmax scaled by 1/sqrt(nope + rope)."""
    f = lambda a: np.asarray(a, np.float64)
    B, S, _ = x.shape
    H, dn, dr, dv, r = (cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim,
                        cfg.v_head_dim, cfg.kv_lora_rank)
    eps = cfg.norm_eps

    def rms(w, a):
        return a / np.sqrt(np.mean(a * a, -1, keepdims=True) + eps) \
            * (1 + f(w["scale"]))

    def rope(a):
        half = a.shape[-1] // 2
        freq = cfg.rope_theta ** (-np.arange(half) / half)
        ang = np.arange(S)[:, None] * freq
        s, c = np.sin(ang)[None, :, None], np.cos(ang)[None, :, None]
        a1, a2 = a[..., :half], a[..., half:]
        return np.concatenate([a1 * c - a2 * s, a1 * s + a2 * c], -1)

    x = f(x)
    if "wq" in p:
        q = x @ f(p["wq"]["w"])
    else:
        q = rms(p["norm_q"], x @ f(p["wq_a"]["w"])) @ f(p["wq_b"]["w"])
    q = q.reshape(B, S, H, dn + dr)
    kv_a = x @ f(p["wkv_a"]["w"])
    kv = (rms(p["norm_kv"], kv_a[..., :r]) @ f(p["wkv_b"]["w"])).reshape(
        B, S, H, dn + dv)
    q = np.concatenate([q[..., :dn], rope(q[..., dn:])], -1)
    kr = rope(kv_a[..., None, r:])
    k = np.concatenate([kv[..., :dn], np.broadcast_to(kr, (B, S, H, dr))],
                       -1)
    s = np.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(dn + dr)
    s = np.where(np.tril(np.ones((S, S), bool)), s, -np.inf)
    a = np.exp(s - s.max(-1, keepdims=True))
    a /= a.sum(-1, keepdims=True)
    o = np.einsum("bhqk,bkhd->bqhd", a, kv[..., dn:]).reshape(B, S, H * dv)
    return o @ f(p["wo"]["w"])


@pytest.mark.parametrize("arch", ARCHS)
def test_mla_matches_plain_restatement(arch):
    cfg = get_config(arch, smoke=True)
    pb = L.ParamBuilder(jax.random.PRNGKey(0), "init", jnp.float32)
    p = A.init_mla(pb, "attn", cfg)
    p = jax.tree.map(lambda a: a + 0.1 * jax.random.normal(
        jax.random.PRNGKey(1), a.shape), p)          # norm scales off 0
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 12, cfg.d_model))
    out, _ = A.mla_layer(p, x, cfg, RULES)
    np.testing.assert_allclose(np.asarray(out), _mla_numpy(p, x, cfg),
                               rtol=2e-4, atol=2e-5)


def _block(cfg, spec, seed=0):
    pb = L.ParamBuilder(jax.random.PRNGKey(seed), "init", jnp.float32)
    return T.init_block(pb, "blk", spec, cfg)


@pytest.mark.parametrize("impl", ["xla", "interpret"])
@pytest.mark.parametrize("ffn", ["moe", "dense"])
def test_perturbed_block_kernel_path_equals_fallback(ffn, impl):
    """The dual probe of an MLA block (with either FFN) through the
    kernels equals the fallback that materializes theta + mu U, at the
    same seeds and scan repeat."""
    cfg = get_config("moonlight-16b-a3b", smoke=True)
    spec = LayerSpec("mla", ffn)
    params = _block(cfg, spec)
    x = jax.random.normal(jax.random.PRNGKey(3), (4, 16, cfg.d_model))
    seeds = O.leaf_seed_tree(params, jnp.int32(13))
    pz = O.Perturb(seeds=seeds, mu=1e-2, rep=1, dual=True, impl=impl)
    got, nc = T.apply_block(params, x, spec, cfg, RULES, perturb=pz)
    want, _ = T._block_fallback(params, x, spec, cfg, RULES, pz)
    # f32 sums in another order, on outputs of size ~10
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
    assert (nc is not None and nc["moe_rows"] > 0) == (ffn == "moe")


@pytest.mark.parametrize("arch", ARCHS)
def test_no_mla_or_moe_block_reaches_the_fallback(arch, monkeypatch):
    cfg = dataclasses.replace(get_config(arch, smoke=True),
                              forward_impl="kernel")
    cfg = cfg.replace(cut_layers=2, aux_layers=1)

    def refuse(*a, **k):
        raise AssertionError("an MLA or MoE block took _block_fallback")

    monkeypatch.setattr(T, "_block_fallback", refuse)
    params = T.init_lm(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 17), 0, cfg.vocab)
    batch = {"inputs": toks[:, :-1], "labels": toks[:, 1:]}
    seeds = O.leaf_seed_tree(params["client"], jnp.int32(5))
    l0, lp, _, stats = P.lm_api(cfg, RULES).client_dual_loss(
        params["client"], batch, seeds, 1e-3)
    assert np.isfinite(float(l0)) and float(lp) != float(l0)
    assert int(stats["moe_rows"]) > 0


def test_decode_through_the_cache_matches_the_full_forward():
    """Prefill, then one step through the per-slot cache of decompressed
    k (nope + rope) and v per head, against the whole sequence's logits."""
    cfg = get_config("moonlight-16b-a3b", smoke=True)
    params = T.init_lm(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 9), 0, cfg.vocab)
    full = T.full_forward(params, cfg, RULES, toks)
    caches = P.init_serve_caches(cfg, 2, 16)
    k = caches["client"][0][0]["attn"]["k"]
    assert k.shape[-2:] == (cfg.n_heads, cfg.qk_nope_dim + cfg.qk_rope_dim)
    _, caches = P.make_cached_prefill_step(cfg, RULES)(params, caches,
                                                        toks[:, :-1])
    step, _ = P.make_serve_step(cfg, RULES)(params, caches, toks[:, -1:])
    np.testing.assert_allclose(np.asarray(step[:, 0]),
                               np.asarray(full[:, -1]), rtol=2e-4,
                               atol=2e-4)


def test_lean_replay_equals_the_dense_update():
    """At h = 1 the Fed-Server's replay of (seed, coeffs) rebuilds the
    dense FedAvg of the clients' steps, the (reps, E, K, N) expert leaves
    included: the grouped kernel's noise is their canonical view."""
    cfg = dataclasses.replace(get_config("moonlight-16b-a3b", smoke=True),
                              forward_impl="kernel")
    api = P.lm_api(cfg, RULES)
    params = T.init_lm(jax.random.PRNGKey(0), cfg)
    sopt = make_optimizer("adamw", 1e-3)
    state = {"client": params["client"], "server": params["server"],
             "opt_server": sopt.init(params["server"])}
    toks = jax.random.randint(jax.random.PRNGKey(2), (2, 1, 2, 17), 0,
                              cfg.vocab)
    rb = {"inputs": toks[..., :-1], "labels": toks[..., 1:]}
    lr = 1e-2
    zo = Z.ZOConfig(mu=1e-3, n_pairs=1)
    fed = P.FedConfig(n_clients=2, h=1)
    copt = make_optimizer("zo_sgd", lr)
    dense = jax.jit(P.make_fed_round(api, "heron", zo, fed, copt, sopt))
    lean = jax.jit(P.make_fed_round(api, "heron", zo, fed, copt, sopt,
                                    uplink="seed_replay", client_lr=lr))
    sd, md = dense(state, rb, jax.random.PRNGKey(9))
    sl, ml = lean(state, rb, jax.random.PRNGKey(9))
    moved = 0
    for a, b, c in zip(jax.tree.leaves(sd["client"]),
                       jax.tree.leaves(sl["client"]),
                       jax.tree.leaves(state["client"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=1e-6)
        moved += a.ndim == 4 and not np.array_equal(np.asarray(a),
                                                    np.asarray(c))
    assert moved == 3 * 2          # up, gate, down of both MoE layers
    assert int(ml["moe_rows"]) == int(md["moe_rows"]) > 0
