"""chip_smoke.py off the chip: it refuses to run without a TPU, and its
phase functions run end to end at a tiny width on the CPU (interpret-mode
kernels, virtual devices for the four-chip phases)."""
import importlib.util
import os
import shutil
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
SMOKE = os.path.join(ROOT, "chip_smoke.py")


def _run(args, cwd, devices=1, timeout=600):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    return subprocess.run([sys.executable] + args, cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=timeout)


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SMOKE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("chips", [[], ["--chips", "4"]])
def test_smoke_refuses_cpu(chips):
    r = _run([SMOKE] + chips, cwd=ROOT, devices=4)
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout
    assert "needs a TPU" in r.stderr


def test_smoke_alone_fails(tmp_path):
    shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    r = _run(["chip_smoke.py"], cwd=tmp_path)
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout


def test_smoke_one_chip_phases_tiny(smoke):
    smoke.noise_phase(shape=(16, 256))
    from repro.configs.gpt2 import gpt2_tiny
    cfg = gpt2_tiny().replace(forward_impl="kernel_interpret",
                              param_dtype="bfloat16", compute_dtype="bfloat16")
    params, rep = smoke.train_phase(cfg, impl="interpret", clients=2,
                                    batch=1, seq=16, lean_rounds=2)
    assert rep["impl"] == "interpret"
    assert rep["lean_vs_dense_ulps"] <= smoke.MAX_ULPS
    assert rep["client_elements_changed"] > 0
    assert len(rep["losses"]) == 2
    with pytest.raises(RuntimeError, match="resolved to"):
        smoke.train_phase(cfg, impl="pallas", clients=2, batch=1, seq=16)
    out = smoke.serve_phase(params, cfg, prompt_lens=(4, 8), per_len=2,
                            max_new=4, slots=2)
    assert out["requests"] == 4
    assert out["worst_reference_rank"] < smoke.REF_TOP_K


def test_smoke_four_chip_phases_tiny():
    code = textwrap.dedent(f"""
        import importlib.util
        spec = importlib.util.spec_from_file_location("chip_smoke",
                                                      {SMOKE!r})
        smoke = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(smoke)
        from repro.configs.gpt2 import gpt2_tiny
        cfg = gpt2_tiny().replace(param_dtype="bfloat16",
                                  compute_dtype="bfloat16")
        rep = smoke.replay_phase(cfg)
        assert rep["tree_bytes"] > 0, rep
        smoke.mesh_step_phase(cfg, batch=4, seq=16)
        print("PHASES OK")
    """)
    r = _run(["-c", code], cwd=ROOT, devices=4)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "PHASES OK" in r.stdout
    assert '"devices": 4' in r.stdout
