"""Distributed correctness on a multi-device CPU mesh (subprocess with
--xla_force_host_platform_device_count, since the main process is locked
to 1 device)."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_py(code: str, devices: int = 8, timeout: int = 600):
    env = dict(os.environ)
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count={devices}")
    env["PYTHONPATH"] = SRC
    env.setdefault("JAX_PLATFORMS", "cpu")
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=timeout)
    assert r.returncode == 0, r.stdout + r.stderr
    return r.stdout


def test_moe_ep_matches_xla_path():
    out = run_py(textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.distributed.sharding import AxisRules, auto_mesh
        from repro.models import moe as M
        from repro.models.config import ModelConfig, MoECfg
        from repro.models.layers import ParamBuilder
        mesh = auto_mesh((2, 4), ("data", "model"))
        cfg = ModelConfig(name="t", n_layers=1, d_model=32, n_heads=4,
                          n_kv_heads=4, d_ff=0, vocab=64,
                          moe=MoECfg(n_experts=8, top_k=2, d_ff_expert=16,
                                     capacity_factor=8.0),
                          param_dtype="float32", compute_dtype="float32")
        pb = ParamBuilder(jax.random.PRNGKey(0), "init", jnp.float32)
        params = M.init_moe(pb, "moe", cfg)
        x = jax.random.normal(jax.random.PRNGKey(2), (4, 16, 32))
        rules = AxisRules(mesh=mesh, enable_fsdp=False)
        with mesh:
            ep = M.moe_ep(params, x, cfg, rules)
        ref = M.moe_reference(params, x, cfg)
        err = float(jnp.max(jnp.abs(ep - ref)))
        print("ERR", err)
        assert err < 2e-3, err
    """))
    assert "ERR" in out


def test_sharded_heron_step_matches_single_device():
    out = run_py(textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.distributed.sharding import AxisRules, auto_mesh
        from repro.core import protocols as P, zo as Z
        from repro.models import transformer as T
        from repro.models.config import ModelConfig
        from repro.optim.optimizers import make_optimizer
        cfg = ModelConfig(name="t", n_layers=2, d_model=32, n_heads=4,
                          n_kv_heads=2, d_ff=64, vocab=64, cut_layers=1,
                          param_dtype="float32", compute_dtype="float32",
                          remat=False)
        params = T.init_lm(jax.random.PRNGKey(0), cfg)
        toks = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, 64)
        lbl = jax.random.randint(jax.random.PRNGKey(2), (8, 16), 0, 64)
        batch = {"inputs": toks, "labels": lbl}
        copt = make_optimizer("zo_sgd", 1e-3)
        sopt = make_optimizer("adamw", 1e-3)

        def run(mesh):
            rules = AxisRules(mesh=mesh, enable_fsdp=False)
            api = P.lm_api(cfg, rules)
            st = P.init_train_state(jax.random.PRNGKey(3), params, copt,
                                    sopt)
            # mu must keep the ZO finite difference l(theta+mu*u)-l(theta)
            # well above the f32 rounding floor of the loss (~1 ulp of
            # ~4.2 = 5e-7): cross-mesh reduction order perturbs each loss
            # by a few ulps, and the coefficient amplifies that noise by
            # d/mu.  At mu=1e-2 the signal (~4e-5) dominates.
            step = P.make_train_step(api, "heron", Z.ZOConfig(mu=1e-2),
                                     copt, sopt)
            if mesh is not None:
                with mesh:
                    st2, m = jax.jit(step)(st, batch)
            else:
                st2, m = jax.jit(step)(st, batch)
            return float(m["loss"]), st2

        mesh = auto_mesh((2, 4), ("data", "model"))
        l1, st1 = run(None)
        l2, st2 = run(mesh)
        print("LOSSES", l1, l2)
        assert abs(l1 - l2) < 1e-3, (l1, l2)
        a = jax.tree.leaves(st1["params"])[3]
        b = jax.tree.leaves(st2["params"])[3]
        err = float(jnp.max(jnp.abs(jnp.asarray(a, jnp.float32)
                                    - jnp.asarray(b, jnp.float32))))
        print("PARAM ERR", err)
        assert err < 1e-3, err
    """))
    assert "PARAM ERR" in out


def test_dryrun_small_mesh_lower_compile():
    """A miniature of the production dry-run on an 8-device host mesh."""
    out = run_py(textwrap.dedent("""
        import jax, jax.numpy as jnp
        from repro.distributed.sharding import AxisRules, auto_mesh
        from repro.core import protocols as P, zo as Z
        from repro.models import transformer as T
        from repro.configs.registry import get_config
        from repro.optim.optimizers import make_optimizer
        cfg = get_config("qwen2-1.5b", smoke=True)
        mesh = auto_mesh((2, 4), ("data", "model"))
        rules = AxisRules(mesh=mesh, enable_fsdp=False)
        api = P.lm_api(cfg, rules)
        copt = make_optimizer("zo_sgd", 1e-3)
        sopt = make_optimizer("adamw", 1e-3)
        params_sds = T.init_lm(None, cfg, mode="shape")
        state_sds = jax.eval_shape(
            lambda: P.init_train_state(jax.random.PRNGKey(0),
                                       jax.tree.map(lambda s: jnp.zeros(
                                           s.shape, s.dtype), params_sds),
                                       copt, sopt))
        batch = {"inputs": jax.ShapeDtypeStruct((8, 32), jnp.int32),
                 "labels": jax.ShapeDtypeStruct((8, 32), jnp.int32)}
        step = P.make_train_step(api, "heron", Z.ZOConfig(), copt, sopt)
        with mesh:
            compiled = jax.jit(step).lower(state_sds, batch).compile()
        print("MEM", compiled.memory_analysis().temp_size_in_bytes)
    """))
    assert "MEM" in out
