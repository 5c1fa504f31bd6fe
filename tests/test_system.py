"""End-to-end behaviour tests for the paper's system.

The headline claims, in miniature:
1. HERON-SFL converges comparably to FO baselines (Fig. 2).
2. HERON's client update is forward-only (ZO coefficients present).
3. Client resource accounting matches Table I's ordering:
   HERON peak-mem < CSE-FSL peak-mem; HERON FLOPs < CSE-FSL FLOPs.
4. Train driver checkpoints and resumes deterministically.
"""
import subprocess
import sys
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import protocols as P
from repro.core import zo as Z
from repro.core.split import client_costs
from repro.data.synthetic import BigramLM
from repro.distributed.sharding import AxisRules
from repro.models import transformer as T
from repro.models.config import ModelConfig
from repro.optim.optimizers import make_optimizer

RULES = AxisRules(mesh=None)


def tiny_cfg():
    return ModelConfig(name="tiny", n_layers=2, d_model=32, n_heads=4,
                       n_kv_heads=2, d_ff=64, vocab=31, cut_layers=1,
                       param_dtype="float32", compute_dtype="float32")


def _train(method, steps=40, seed=0):
    cfg = tiny_cfg()
    params = T.init_lm(jax.random.PRNGKey(seed), cfg)
    api = P.lm_api(cfg, RULES)
    copt = make_optimizer("zo_sgd" if method == "heron" else "adamw",
                          5e-3 if method == "heron" else 1e-3)
    sopt = make_optimizer("adamw", 2e-3)
    state = P.init_train_state(jax.random.PRNGKey(1), params, copt, sopt)
    step = jax.jit(P.make_train_step(api, method,
                                     Z.ZOConfig(mu=1e-3, n_pairs=2),
                                     copt, sopt))
    ds = BigramLM(vocab=cfg.vocab, seq_len=17, seed=0)
    losses = []
    for i in range(steps):
        batch = ds.batch(jax.random.PRNGKey(100 + i), 16)
        state, m = step(state, batch)
        losses.append(float(m["loss"]))
    return losses


def test_heron_convergence_comparable_to_fo():
    lh = _train("heron")
    lf = _train("cse_fsl")
    assert lh[-1] < lh[0]
    assert lf[-1] < lf[0]
    assert np.mean(lh[-5:]) < np.mean(lf[-5:]) + 0.5


def test_heron_client_update_is_forward_only():
    cfg = tiny_cfg()
    params = T.init_lm(jax.random.PRNGKey(0), cfg)
    api = P.lm_api(cfg, RULES)
    copt = make_optimizer("zo_sgd", 1e-3)
    sopt = make_optimizer("adamw", 1e-3)
    state = P.init_train_state(jax.random.PRNGKey(1), params, copt, sopt)
    step = P.make_train_step(api, "heron", Z.ZOConfig(n_pairs=2),
                             copt, sopt)
    batch = {"inputs": jnp.zeros((2, 8), jnp.int32),
             "labels": jnp.zeros((2, 8), jnp.int32)}
    _, metrics = jax.jit(step)(state, batch)
    # ZO projected-gradient coefficients exist => estimator path was used
    assert "zo_coeff_abs" in metrics
    assert bool(jnp.isfinite(metrics["zo_coeff_abs"]))


def test_table1_resource_ordering():
    costs = {m: client_costs(m, p_batch_bytes=1000, q_smashed_bytes=5000,
                             client_params=10000, aux_params=2000,
                             f_c=1e9, f_a=2e8, n_pairs=1)
             for m in ("sflv2", "cse_fsl", "heron")}
    assert costs["heron"]["peak_mem_bytes"] < costs["cse_fsl"][
        "peak_mem_bytes"]
    assert costs["heron"]["flops"] < costs["cse_fsl"]["flops"]
    # HERON flops = 2(Fc+Fa) at n_pairs=1 (Table I)
    assert costs["heron"]["flops"] == pytest.approx(2 * 1.2e9)


def test_train_driver_checkpoint_resume(tmp_path):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..",
                                     "src")
    # the entry point's compile cache goes where the variable says
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "jax_cache")
    base = [sys.executable, "-m", "repro.launch.train", "--arch",
            "qwen2-1.5b", "--smoke", "--batch", "2", "--seq", "16",
            "--ckpt-dir", str(tmp_path), "--ckpt-every", "4"]
    r1 = subprocess.run(base + ["--steps", "6"], env=env, timeout=600,
                        capture_output=True, text=True)
    assert r1.returncode == 0, r1.stdout + r1.stderr
    r2 = subprocess.run(base + ["--steps", "10"], env=env, timeout=600,
                        capture_output=True, text=True)
    assert r2.returncode == 0, r2.stdout + r2.stderr
    assert "restored checkpoint" in r2.stdout
    assert any((tmp_path / "jax_cache").iterdir())


@pytest.mark.parametrize("env_dir", [False, True])
def test_compile_cache_placement(monkeypatch, tmp_path, env_dir):
    """The entry points' compile cache: where the variable says, and
    untouched, when it is set; else one fixed directory in the checkout."""
    from repro.launch import compile_cache
    prev = jax.config.jax_compilation_cache_dir
    if env_dir:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        want = str(tmp_path)
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = os.path.abspath(os.path.join(os.path.dirname(__file__), "..",
                                            ".jax_cache"))
    try:
        assert compile_cache.enable() == want
        assert jax.config.jax_compilation_cache_dir == (
            prev if env_dir else want)
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
