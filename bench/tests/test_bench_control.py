"""The comparison that decides ``correct`` fails what it must: the control
(the plain reference in float8, one step below the configuration's
bfloat16, in the program's place) and the faults a cell can have, each
planted under the timed path of a whole run that skips only the look for
a chip.  A sound run of the same size passes.  Sizes are the tiny
registry's; the limits there were set from CPU readings of this size."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import run
import tiny

SEED = 2 ** 31 + 11


def _run(tmp_path, workload, wrap_step=None, seconds=0.5):
    reg = tiny.registry(tmp_path)
    res, _ = run.run_cell(reg, workload, SEED, seconds, False,
                          "TPU v5 lite", wrap_step=wrap_step)
    return res


def test_fed_sound_run_is_correct(tmp_path):
    res = _run(tmp_path, "fed-tiny")
    assert res.correct, [(c.name, c.value, c.limit) for c in res.checks]
    assert res.attempted >= 1 and res.failed == 0
    assert res.metrics["round_s"] > 0


def test_fed_traced_run_records_its_programs_and_client_bytes(tmp_path):
    """A traced run's record holds the Pallas calls of each program the
    window drove and what one client's program holds; a reader that finds
    nothing to read (no Pallas call runs on the CPU) returns nothing."""
    reg = tiny.registry(tmp_path)
    res, _ = run.run_cell(reg, "fed-tiny", SEED, 0.5, True, "TPU v5 lite")
    assert list(res.record["programs"]) == ["jit_round_fn"]
    assert reg.metric("client_peak_bytes").read(res.record) > 0
    assert reg.metric("zo_dual_matmul_roofline").read(res.record) is None
    assert res.correct


def _control(step, cell):
    """The reference in float8 in the program's place."""
    return tiny.reference_round(cell, "fp8")


def _state_unchanged(step, cell):
    def f(state, batch, key):
        _, m = step(jax.tree.map(jnp.copy, state), batch, key)
        return state, m
    return f


def _half_batch(step, cell):
    half = jax.jit(cell.round_fn)

    def f(state, batch, key):
        b = batch["inputs"].shape[2] // 2
        return half(state, jax.tree.map(lambda x: x[:, :, :b], batch), key)
    return f


@pytest.mark.parametrize("broken", [_control, _state_unchanged, _half_batch],
                         ids=["control_fp8", "state_unchanged", "half_batch"])
def test_fed_broken_timed_path_is_not_correct(tmp_path, broken):
    res = _run(tmp_path, "fed-tiny", wrap_step=broken)
    assert not res.correct, [(c.name, c.value, c.limit) for c in res.checks]


def test_serve_sound_run_is_correct(tmp_path):
    res = _run(tmp_path, "serve-tiny", seconds=1.0)
    assert res.correct, [(c.name, c.value, c.limit) for c in res.checks]
    assert res.failed == 0 and res.attempted == 20
    assert res.metrics["ttft_p95_ms"] > 0


class _AlterTokens:
    """The engine, with each finished request's last token altered where
    the engine hands it out."""

    def __init__(self, engine, vocab):
        self._engine, self._vocab = engine, vocab

    def __getattr__(self, name):
        return getattr(self._engine, name)

    def step(self):
        done = self._engine.step()
        for r in done:
            r.tokens[-1] = (r.tokens[-1] + 1) % self._vocab
        return done


def test_serve_token_altered_is_not_correct(tmp_path):
    res = _run(tmp_path, "serve-tiny", seconds=1.0,
               wrap_step=lambda engine, srv: _AlterTokens(
                   engine, srv.cfg_json["vocab_size"]))
    assert not res.correct


def test_serve_control_reads_above_the_limit(tmp_path):
    """At each position of a sound run's served sequences, the token the
    float8 reference puts first lies further below the reference's best
    than the limit allows."""
    reg = tiny.registry(tmp_path)
    drv = reg.driver("serve_open_loop")
    cfg, t = reg.config("tiny"), reg.traffic("serve-tiny")
    from lib import harness as H
    root = H.root_key(SEED)
    srv = drv.Server(reg.model(cfg["model"]), cfg, t, root)
    srv.warm_up()
    reqs = drv.schedule(t, 1.0, SEED, cfg["vocab_size"])
    drv.open_loop(srv.engine, reqs, 1.0, t["drain_s"], cfg, srv.model)
    picked = drv.sample(reqs, SEED, t["check_tokens"])
    ref = drv.Reference(reg, cfg, t["capacity"])
    got = ref.gaps(srv.init_params(root), picked, precision="fp8")
    assert got["logit_gap"] > tiny.SERVE_LIMITS["logit_gap"], got
    assert np.isfinite(got["logit_gap"])
