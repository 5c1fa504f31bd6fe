"""The kernel-backed client forward end-to-end: forward_impl="kernel"
routes the ZO dual probe through the Pallas matmuls, the per-layer hash
seeds are replayable server-side, and the estimator keeps the two-point
contract.  Everything runs in interpret mode on CPU."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import protocols as P
from repro.core import zo as Z
from repro.distributed.sharding import AxisRules
from repro.kernels import ops as O
from repro.kernels import ref
from repro.kernels import zo_matmul as ZM
from repro.models import cnn as CNN


def _cnn_cfg(impl="kernel_interpret"):
    return CNN.CNNConfig(widths=(8, 16), blocks_per_stage=1, classes=4,
                         client_blocks=1, forward_impl=impl)


def _lm_cfg(impl="kernel_interpret"):
    from repro.configs.gpt2 import gpt2_tiny
    return dataclasses.replace(gpt2_tiny(), forward_impl=impl)


def _cnn_batch(b=8, hw=8):
    x = jax.random.normal(jax.random.PRNGKey(1), (b, hw, hw, 3))
    y = jax.random.randint(jax.random.PRNGKey(2), (b,), 0, 4)
    return {"inputs": x, "labels": y}


def _lm_batch(cfg, b=2, s=16):
    toks = jax.random.randint(jax.random.PRNGKey(3), (b, s + 1), 0,
                              cfg.vocab)
    return {"inputs": toks[:, :-1], "labels": toks[:, 1:]}


# --- mu=0 equivalence: the kernel path degenerates to the plain forward


def test_cnn_dual_loss_matches_xla_at_mu0():
    cfg = _cnn_cfg()
    params = CNN.init_cnn(jax.random.PRNGKey(0), cfg)
    api = P.cnn_api(cfg)
    batch = _cnn_batch()
    seeds = O.leaf_seed_tree(params["client"], jnp.int32(7))
    l0, lp, s, _ = api.client_dual_loss(params["client"], batch, seeds,
                                        0.0)
    lx, sx = api.client_loss(params["client"], batch)
    np.testing.assert_allclose(float(l0), float(lx), rtol=2e-5)
    np.testing.assert_allclose(float(lp), float(lx), rtol=2e-5)
    np.testing.assert_allclose(np.asarray(s), np.asarray(sx),
                               rtol=2e-5, atol=1e-5)


def test_lm_dual_loss_matches_xla_at_mu0():
    cfg = _lm_cfg()
    rules = AxisRules(mesh=None)
    from repro.models import transformer as T
    params = T.init_lm(jax.random.PRNGKey(0), cfg)
    api = P.lm_api(cfg, rules)
    batch = _lm_batch(cfg)
    seeds = O.leaf_seed_tree(params["client"], jnp.int32(7))
    l0, lp, s, _ = api.client_dual_loss(params["client"], batch, seeds,
                                        0.0)
    lx, sx = api.client_loss(params["client"], batch)
    np.testing.assert_allclose(float(l0), float(lx), rtol=2e-5)
    np.testing.assert_allclose(float(lp), float(lx), rtol=2e-5)
    np.testing.assert_allclose(np.asarray(s), np.asarray(sx),
                               rtol=2e-5, atol=1e-5)


# --- dual halves: clean == plain forward, perturbed == materialized tree


def test_cnn_dual_halves_match_materialized_perturbation():
    cfg = _cnn_cfg()
    client = CNN.init_cnn(jax.random.PRNGKey(0), cfg)["client"]
    x = _cnn_batch()["inputs"]
    mu = 0.02
    seeds = O.leaf_seed_tree(client, jnp.int32(11))
    pz = O.Perturb(seeds=seeds, mu=mu, dual=True, impl="interpret")
    y2 = CNN.client_forward(client, x, cfg, pz)
    B = x.shape[0]
    y_plain = CNN.client_forward(client, x, cfg)
    y_pert = CNN.client_forward(O.perturb_tree(client, seeds, mu), x, cfg)
    np.testing.assert_allclose(np.asarray(y2[:B]), np.asarray(y_plain),
                               rtol=2e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(y2[B:]), np.asarray(y_pert),
                               rtol=2e-5, atol=1e-5)


# the dual forward of each block kind and position path: the kernel
# lowering (GPT-2, in interpret mode), the generic fallback (recurrent
# and xLSTM mixers, cross-attention, the capacity MoE) and M-RoPE's
# (3, B, S) positions (the smoke configs, in the jnp emulation)
DUAL_HALVES_ARCHS = ("gpt2-tiny", "recurrentgemma-9b", "xlstm-1.3b",
                     "seamless-m4t-medium", "qwen3-moe-30b-a3b",
                     "qwen2-vl-2b")


@pytest.mark.parametrize("arch", DUAL_HALVES_ARCHS)
def test_lm_dual_halves_match_materialized_perturbation(arch):
    from repro.configs.registry import get_config
    from repro.models import transformer as T
    from test_configs_smoke import smoke_batch
    if arch == "gpt2-tiny":
        cfg, impl = _lm_cfg(), "interpret"
        batch = _lm_batch(cfg)
    else:
        cfg, impl = get_config(arch, smoke=True), "xla"
        batch = smoke_batch(cfg)
    rules = AxisRules(mesh=None)
    client = T.init_lm(jax.random.PRNGKey(0), cfg)["client"]
    toks, pos = batch["inputs"], batch.get("positions")
    mu = 0.01
    seeds = O.leaf_seed_tree(client, jnp.int32(13))
    pz = O.Perturb(seeds=seeds, mu=mu, dual=True, impl=impl)
    s2, _ = T.client_forward(client, cfg, rules, toks, pos, perturb=pz)
    B = toks.shape[0]
    s_plain, _ = T.client_forward(client, cfg, rules, toks, pos)
    s_pert, _ = T.client_forward(O.perturb_tree(client, seeds, mu), cfg,
                                 rules, toks, pos)
    np.testing.assert_allclose(np.asarray(s2[:B]), np.asarray(s_plain),
                               rtol=2e-5, atol=1e-5)
    # scan-stacked layer leaves replay through per-rep row offsets —
    # this is the canonical-coordinate contract
    np.testing.assert_allclose(np.asarray(s2[B:]), np.asarray(s_pert),
                               rtol=2e-4, atol=2e-5)


# --- per-layer seed derivation ----------------------------------------------


def test_leaf_seeds_distinct_and_deterministic():
    cfg = _cnn_cfg()
    client = CNN.init_cnn(jax.random.PRNGKey(0), cfg)["client"]
    s1 = O.leaf_seed_tree(client, jnp.int32(5))
    s2 = O.leaf_seed_tree(client, jnp.int32(5))
    seeds1 = [int(s) for s in jax.tree.leaves(s1)]
    seeds2 = [int(s) for s in jax.tree.leaves(s2)]
    assert seeds1 == seeds2                       # path-hash determinism
    assert len(set(seeds1)) == len(seeds1)        # one stream per leaf
    s3 = [int(s) for s in jax.tree.leaves(O.leaf_seed_tree(
        client, jnp.int32(6)))]
    assert all(a != b for a, b in zip(seeds1, s3))


def test_direction_block_size_invariance():
    """The direction a coefficient multiplies is a pure function of
    (seed, global coords) — kernel tiling must not leak into it."""
    w = jnp.zeros((96, 160))
    u = ZM.uniform_noise(17, w.shape)
    for bn, bk in ((32, 32), (160, 96), (80, 48)):
        uk = O.zo_noise(w, 17, bn=bn, bk=bk)
        np.testing.assert_array_equal(np.asarray(u), np.asarray(uk))


# --- estimator contract ------------------------------------------------------


def test_zo_gradient_kernel_coeff_contract():
    """g == sum_p coeff_p * U(seed_p) with coeff = (lp-l0)/mu/n_pairs.

    The estimator evaluates its losses inside a jitted scan, the oracle
    eagerly; XLA may round each f32 loss an ulp apart, and the difference
    quotient scales that by 1/(mu*n_pairs).  So each coefficient is held
    to a few ulps of the loss over mu*n_pairs, and the gradient to the
    coefficients the estimator reported."""
    params = {"w": jax.random.normal(jax.random.PRNGKey(0), (8, 16)),
              "frozen": None}
    tgt = jax.random.normal(jax.random.PRNGKey(1), (8, 16))

    def loss_of(p):
        return jnp.mean((p["w"] - tgt) ** 2)

    def dual_loss(p, seeds, mu):
        pp = O.perturb_tree(p, seeds, mu)
        return loss_of(p), loss_of(pp), None, {}

    zo = Z.ZOConfig(mu=1e-3, n_pairs=3)
    base = jnp.int32(42)
    g, info = Z.zo_gradient(dual_loss, params, base, zo)
    assert g["frozen"] is None
    assert info["coeffs"].shape == (3,)
    acc = jnp.zeros_like(params["w"])
    for p, seed in enumerate(np.asarray(Z.pair_seeds(base, 3))):
        seeds = O.leaf_seed_tree(params, jnp.int32(seed))
        l0, lp, _, _ = dual_loss(params, seeds, zo.mu)
        coeff = (lp - l0) / zo.mu / zo.n_pairs
        ulps = 4 * np.finfo(np.float32).eps * max(abs(float(l0)),
                                                  abs(float(lp)))
        np.testing.assert_allclose(float(info["coeffs"][p]), float(coeff),
                                   rtol=0, atol=ulps / zo.mu / zo.n_pairs)
        acc = acc + info["coeffs"][p] * O.kernel_direction_tree(
            params, seeds)["w"]
    np.testing.assert_allclose(np.asarray(g["w"]), np.asarray(acc),
                               rtol=1e-5, atol=1e-6)


# --- protocol integration ----------------------------------------------------


@pytest.mark.parametrize("api", ["lm", "cnn"])
def test_forward_impl_names_the_two_backends(api):
    """forward_impl is "kernel" (backend from the platform) or
    "kernel_interpret"; any other value is refused by name."""
    cfg = _lm_cfg("xla") if api == "lm" else _cnn_cfg("xla")
    make = (lambda c: P.lm_api(c, AxisRules(mesh=None))) if api == "lm" \
        else P.cnn_api
    with pytest.raises(ValueError, match="'kernel' or 'kernel_interpret'"):
        make(cfg)
    assert P.forward_impl_of(_lm_cfg("kernel")) == O.default_forward_impl()
    assert P.forward_impl_of(_cnn_cfg()) == "interpret"


def test_kernel_train_step_smoke():
    from repro.optim.optimizers import make_optimizer
    cfg = _cnn_cfg()
    api = P.cnn_api(cfg)
    assert api.client_dual_loss is not None
    params = CNN.init_cnn(jax.random.PRNGKey(0), cfg)
    copt = make_optimizer("zo_sgd", 1e-2)
    sopt = make_optimizer("adamw", 1e-3)
    state = P.init_train_state(jax.random.PRNGKey(4), params, copt, sopt)
    step = jax.jit(P.make_train_step(api, "heron",
                                     Z.ZOConfig(mu=1e-3, n_pairs=1),
                                     copt, sopt))
    state2, metrics = step(state, _cnn_batch())
    assert np.isfinite(float(metrics["loss"]))
    assert np.isfinite(float(metrics["client_loss"]))
    moved = [not np.array_equal(np.asarray(a), np.asarray(b))
             for a, b in zip(
                 jax.tree.leaves(state["params"]["client"]),
                 jax.tree.leaves(state2["params"]["client"]))]
    assert any(moved)


def test_kernel_fed_round_seed_replay_matches_dense_at_h1():
    """With forward_impl="kernel" the lean uplink still reconstructs the
    dense aggregate: the server replays the hash-noise directions from
    (client seed, coeffs) alone."""
    from repro.data.pipeline import round_batches
    from repro.data.synthetic import GaussianMixtureImages
    from repro.optim.optimizers import make_optimizer
    cfg = _cnn_cfg()
    api = P.cnn_api(cfg)
    ds = GaussianMixtureImages(classes=4, hw=8, noise=0.5)
    params = CNN.init_cnn(jax.random.PRNGKey(0), cfg)
    sopt = make_optimizer("adamw", 2e-3)
    state = {"client": params["client"], "server": params["server"],
             "opt_server": sopt.init(params["server"])}
    lr = 2e-2
    zo = Z.ZOConfig(mu=1e-3, n_pairs=2)
    fed = P.FedConfig(n_clients=2, h=1)
    rb = round_batches(ds, jax.random.PRNGKey(3), 2, 1, 16)
    copt = make_optimizer("zo_sgd", lr)
    dense = jax.jit(P.make_fed_round(api, "heron", zo, fed, copt, sopt))
    lean = jax.jit(P.make_fed_round(api, "heron", zo, fed, copt, sopt,
                                    uplink="seed_replay", client_lr=lr))
    sd, md = dense(state, rb, jax.random.PRNGKey(9))
    sl, ml = lean(state, rb, jax.random.PRNGKey(9))
    for a, b in zip(jax.tree.leaves(sd["client"]),
                    jax.tree.leaves(sl["client"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=1e-6)
    assert float(ml["uplink_bytes"]) < float(ml["uplink_bytes_dense"])


def test_kernel_train_step_respects_lora_freeze():
    from repro.models import lora as LoRA
    from repro.models import transformer as T
    from repro.optim.optimizers import make_optimizer
    cfg = _lm_cfg()
    rules = AxisRules(mesh=None)
    params = LoRA.add_lora(jax.random.PRNGKey(2),
                           T.init_lm(jax.random.PRNGKey(0), cfg), rank=4)
    api = P.lm_api(cfg, rules)
    copt = make_optimizer("zo_sgd", 1e-2)
    sopt = make_optimizer("adamw", 1e-3)
    state = P.init_train_state(jax.random.PRNGKey(4), params, copt, sopt,
                               tc_pred=LoRA.lora_pred,
                               ts_pred=LoRA.lora_pred)
    step = jax.jit(P.make_train_step(api, "heron",
                                     Z.ZOConfig(mu=1e-3, n_pairs=1),
                                     copt, sopt, tc_pred=LoRA.lora_pred,
                                     ts_pred=LoRA.lora_pred))
    state2, metrics = step(state, _lm_batch(cfg))
    assert np.isfinite(float(metrics["client_loss"]))
    # frozen (non-LoRA) leaves must be bit-untouched, LoRA leaves move
    from repro.core.split import partition
    tc1, fc1 = partition(state["params"]["client"], LoRA.lora_pred)
    tc2, fc2 = partition(state2["params"]["client"], LoRA.lora_pred)
    for a, b in zip(jax.tree.leaves(fc1), jax.tree.leaves(fc2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert any(not np.array_equal(np.asarray(a), np.asarray(b))
               for a, b in zip(jax.tree.leaves(tc1),
                               jax.tree.leaves(tc2)))


# --- every client-side layer shape of the paper configs vs the oracles ------


def _client_matrix_shapes(tree):
    shapes = set()
    for leaf in jax.tree.leaves(tree):
        if leaf is not None and leaf.ndim >= 2:
            shapes.add((int(np.prod(leaf.shape[:-1])),
                        int(leaf.shape[-1])))
    return sorted(shapes)


def _resnet18_client_shapes():
    from repro.configs.resnet18_cifar import full_config
    cfg = full_config()
    client = CNN.init_cnn(jax.random.PRNGKey(0), cfg)["client"]
    # convs lower via im2col: the matmul K-dim is kh*kw*cin
    shapes = set()
    shapes.add((3 * 3 * 3, cfg.widths[0]))             # stem
    for p in client["blocks"]:
        kh, kw, cin, cout = p["c1"].shape
        shapes.add((kh * kw * cin, cout))
        kh, kw, cin, cout = p["c2"].shape
        shapes.add((kh * kw * cin, cout))
        if "proj" in p:
            kh, kw, cin, cout = p["proj"].shape
            shapes.add((kh * kw * cin, cout))
    shapes.add(tuple(int(d) for d in client["aux"]["fc"]["w"].shape))
    return sorted(shapes)


def _gpt2_client_shapes():
    from repro.configs.gpt2 import gpt2_small
    full = gpt2_small()
    d, f = full.d_model, full.d_ff
    return [(d, d), (d, f), (f, d), (full.vocab, d)]


@pytest.mark.parametrize("k,n", _resnet18_client_shapes())
def test_resnet18_layer_shapes_vs_oracle(k, n):
    """Interpret-mode kernel vs the materialized-noise oracle for every
    client-side matmul shape of the paper's ResNet-18 split."""
    x = jax.random.normal(jax.random.PRNGKey(0), (4, k))
    w = jax.random.normal(jax.random.PRNGKey(1), (k, n)) * 0.1
    u = ZM.uniform_noise(31, w.shape)
    y = O.zo_matmul(x, w, 31, 0.05, impl="interpret")
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(ref.zo_matmul_ref(x, w, u, 0.05)),
        rtol=5e-5, atol=5e-5)
    np.testing.assert_array_equal(np.asarray(u),
                                  np.asarray(O.zo_noise(w, 31)))


@pytest.mark.parametrize("k,n", _gpt2_client_shapes())
def test_gpt2_layer_shapes_vs_oracle(k, n):
    """GPT2-Small client shapes (attention proj, MLP, tied embed): the
    jnp noise stream is the oracle; the xla impl consumes it verbatim
    and the interpret kernel agrees on a shape-preserving slice."""
    x = jax.random.normal(jax.random.PRNGKey(0), (2, k)) * 0.05
    w = jax.random.normal(jax.random.PRNGKey(1), (k, n)) * 0.02
    u = ZM.uniform_noise(37, w.shape)
    y = O.zo_matmul(x, w, 37, 0.01, impl="xla")
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(ref.zo_matmul_ref(x, w, u, 0.01)),
        rtol=1e-5, atol=1e-5)
    # interpret kernel spot-check on a 128x128 window of the same field
    ks, ns = min(k, 128), min(n, 128)
    uk = O.zo_noise(w[:ks, :ns], 37)
    np.testing.assert_array_equal(np.asarray(u[:ks, :ns]),
                                  np.asarray(uk))
