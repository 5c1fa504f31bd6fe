"""The HERON round names its phases inside the program: every matrix
product of the compiled round falls under exactly one of the cohort, the
server's FO steps and the seed replay, and the aux head sits inside the
cohort.  A profiler trace of the round can then be split by phase."""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

import hlo_scopes as H
from repro.core import protocols as P
from repro.core import zo as Z
from repro.distributed.sharding import AxisRules
from repro.models import cnn as CNN
from repro.models import transformer as T
from repro.optim.optimizers import make_optimizer


def _lm():
    from repro.configs.gpt2 import gpt2_tiny
    cfg = dataclasses.replace(gpt2_tiny(), forward_impl="kernel")
    p = T.init_lm(jax.random.PRNGKey(0), cfg)
    tok = jnp.zeros((2, 2, 2, 16), jnp.int32)
    return P.lm_api(cfg, AxisRules(mesh=None)), p, {"inputs": tok,
                                                     "labels": tok}


def _moonlight():
    from repro.configs.moonlight_16b_a3b import smoke_config
    cfg = dataclasses.replace(smoke_config(), forward_impl="kernel")
    p = T.init_lm(jax.random.PRNGKey(0), cfg)
    tok = jnp.zeros((2, 2, 2, 16), jnp.int32)
    return P.lm_api(cfg, AxisRules(mesh=None)), p, {"inputs": tok,
                                                     "labels": tok}


def _cnn():
    cfg = CNN.CNNConfig(widths=(8, 16), blocks_per_stage=1, classes=4,
                        client_blocks=1, forward_impl="kernel")
    p = CNN.init_cnn(jax.random.PRNGKey(0), cfg)
    return P.cnn_api(cfg), p, {
        "inputs": jnp.zeros((2, 2, 4, 8, 8, 3)),
        "labels": jnp.zeros((2, 2, 4), jnp.int32)}


def _round_hlo(model):
    api, p, batch = model()
    sopt = make_optimizer("adamw", 1e-3)
    fn = P.make_fed_round(api, "heron", Z.ZOConfig(n_pairs=2),
                          P.FedConfig(n_clients=2, h=2),
                          make_optimizer("zo_sgd", 1e-3), sopt,
                          uplink="seed_replay", client_lr=1e-3)
    state = {"client": p["client"], "server": p["server"],
             "opt_server": sopt.init(p["server"])}
    return jax.jit(fn).lower(state, batch,
                             jax.random.PRNGKey(1)).compile().as_text()


def test_moe_and_mla_scopes_sit_inside_the_phases():
    """``heron_moe_route``, ``heron_moe_experts`` and ``heron_mla`` reach
    the compiled round, in the cohort and in the server's FO steps, and
    each of their matrix products under exactly one phase."""
    text = _round_hlo(_moonlight)
    assert H.phase_faults(text) == []
    inner = {"heron_moe_route", "heron_moe_experts", "heron_mla"}
    seen = {}
    for _, rest, op_name in H.instructions(text):
        s = H.scopes(op_name)
        for scope in s & inner:
            seen.setdefault(scope, set()).update(s & set(H.PHASES))
    assert set(seen) == inner
    for scope, phases in seen.items():
        assert phases == {"heron_cohort", "heron_server_fo"}, (scope, phases)


@pytest.mark.parametrize("model", [_lm, _cnn, _moonlight],
                         ids=["gpt2", "cnn", "moonlight"])
def test_compiled_round_names_each_phase(model):
    """Kernel client (the xla emulation off a TPU), lean uplink, two
    clients and two local steps."""
    text = _round_hlo(model)
    assert H.phase_faults(text) == []
    assert H.named(text) == {*H.PHASES, H.AUX_HEAD}
