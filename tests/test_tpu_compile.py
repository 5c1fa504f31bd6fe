"""Compiles for a described TPU v5e (no chip attached) at GPT-2 Medium
width: what Mosaic and the TPU compiler refuse, interpret mode never
shows (unaligned blocks, casts Mosaic cannot lower, blocks a vmap makes
illegal).  Each case asserts the Pallas kernel is in the compiled HLO.

The topology is described inside a fixture, never at import time: only
one process may load the TPU library, so the tests stay in this one file
and run in the process of the worker that is given it."""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import hlo_scopes
from repro.kernels import flash_attention as FA
from repro.kernels import grouped_matmul as GM
from repro.kernels import zo_matmul as ZM

B, S, H, D = 4, 1024, 16, 64          # GPT-2 Medium attention
M, DM, FF = B * S, 1024, 4096         # tokens, d_model, d_ff


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _hlo(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _sds(sh, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)


@pytest.mark.parametrize("n", [FF, 50257])
def test_zo_matmul_compiles(one_chip, n):
    """The single probe, at an MLP width and at GPT-2's unpadded vocab
    (no 128-lane divisor: the wrapper pads instead)."""
    fn = functools.partial(ZM.zo_matmul, seed=3, mu=1e-3, interpret=False)
    assert "tpu_custom_call" in _hlo(lambda x, w: fn(x, w),
                                     _sds(one_chip, (M, DM)),
                                     _sds(one_chip, (DM, n)))


def test_zo_dual_matmul_with_noise_compiles(one_chip):
    fn = functools.partial(ZM.zo_dual_matmul, seed=3, mu_a=0.0, mu_b=1e-3,
                           interpret=False)
    x = _sds(one_chip, (M, DM))
    assert "tpu_custom_call" in _hlo(lambda a, b, w: fn(a, b, w), x, x,
                                     _sds(one_chip, (DM, FF)))


@pytest.mark.parametrize("k,n", [(DM, DM), (DM, FF), (FF, DM),      # Medium
                                 (768, 768), (768, 3072), (3072, 768)])
def test_zo_dual_matmul_vmapped_over_clients_compiles(one_chip, k, n):
    """The federated round vmaps the client forward over the cohort, so
    each client's seed reaches the kernel batched.  At every dense shape
    of the two fed cells (4096 tokens per client) the chosen blocks
    compile within the kernel's VMEM limit, with no padding round the
    call."""
    def one(xa, xb, w, seed):
        return ZM.zo_dual_matmul(xa, xb, w, seed, 0.0, 1e-3,
                                 interpret=False)

    x = _sds(one_chip, (4, M, k))
    text = _hlo(jax.vmap(one), x, x, _sds(one_chip, (4, k, n)),
                _sds(one_chip, (4,), jnp.int32))
    assert "tpu_custom_call" in text
    assert " pad(" not in text


@pytest.mark.parametrize("seq", [S, 1001])
def test_flash_attention_compiles(one_chip, seq):
    q = _sds(one_chip, (B, seq, H, D))
    fn = functools.partial(FA.flash_attention, interpret=False)
    assert "tpu_custom_call" in _hlo(fn, q, q, q)


@pytest.mark.parametrize("probe", ["weights", "scores"])
def test_zo_dual_flash_attention_compiles(one_chip, probe):
    q = _sds(one_chip, (B, S, H, D))
    if probe == "weights":
        def fn(qa, qb, k, v, kb, vb):
            return FA.zo_dual_flash_attention(qa, qb, k, v, kb, vb,
                                              perturb_b=False,
                                              interpret=False)
        args = (q,) * 6
    else:
        def fn(qa, qb, k, v):
            return FA.zo_dual_flash_attention(qa, qb, k, v, seed=5,
                                              mu_b=1e-3, interpret=False)
        args = (q,) * 4
    assert "tpu_custom_call" in _hlo(fn, *args)


# Moonlight-16B-A3B's cell: 4 clients x 8192 tokens, 8 of 64 experts held
# (6 per token) of width 2048 -> 1408, MLA heads of 192 / 128 dims
ML_CLIENTS, ML_SEQ, ML_WIDTHS = 4, 8192, ((2048, 1408), (1408, 2048))


@pytest.mark.parametrize("k,n", ML_WIDTHS)
def test_zo_dual_grouped_matmul_compiles(one_chip, k, n):
    """The grouped dual probe over the held experts at the cell's widths,
    its worst-case buffers and grid, vmapped over the cohort as the round
    calls it (the batched scalar prefetch makes JAX loop over clients)."""
    bm = GM.row_block(ML_SEQ, 6, 64, ML_WIDTHS)
    rows = (GM.capacity_tiles(ML_SEQ, 6, 8, bm) + 1) * bm
    pairs = GM.pair_tiles(ML_SEQ, 6, 8, bm)

    def one(xa, xb, w, meta, seed):
        return GM.zo_dual_grouped_matmul(xa, xb, w, meta, seed, 0.0, 1e-3,
                                         bm=bm, interpret=False)

    c = ML_CLIENTS
    x = _sds(one_chip, (c, rows, k))
    text = _hlo(jax.vmap(one), x, x, _sds(one_chip, (c, 8, k, n)),
                _sds(one_chip, (c, len(GM.META), pairs), jnp.int32),
                _sds(one_chip, (c,), jnp.int32))
    # one call over the cohort, named by its jitted wrapper (which the
    # roofline reader matches), not a loop whose slices fuse into it
    calls = [op for _, rest, op in hlo_scopes.instructions(text)
             if 'custom_call_target="tpu_custom_call"' in rest]
    assert len(calls) == 1 and calls[0].endswith(
        "vmap(jit(zo_dual_grouped_matmul))/pallas_call")
    assert "kind=kCustom" not in text and " while(" not in text


def test_zo_dual_flash_attention_two_head_dims_compiles(one_chip):
    """MLA's weight probe: queries and keys of 192 dims, values of 128,
    8192 tokens, vmapped over the cohort.  The index maps that clamp the
    masked kv blocks keep the cohort one call, not a loop over clients."""
    qk = _sds(one_chip, (ML_CLIENTS, 1, ML_SEQ, 16, 192))
    v = _sds(one_chip, (ML_CLIENTS, 1, ML_SEQ, 16, 128))

    def one(qa, qb, k, v, kb, vb):
        return FA.zo_dual_flash_attention(qa, qb, k, v, kb, vb,
                                          perturb_b=False, interpret=False)

    text = _hlo(jax.vmap(one), qk, qk, qk, v, qk, v)
    calls = [op for _, rest, op in hlo_scopes.instructions(text)
             if 'custom_call_target="tpu_custom_call"' in rest]
    assert len(calls) == 1 and calls[0].endswith(
        "vmap(jit(zo_dual_flash_attention))/pallas_call")
    assert " while(" not in text


def test_gpt2_medium_fed_round_compiles(one_chip, monkeypatch):
    """The jitted HERON round that ``chip_smoke.py`` runs (lean uplink,
    kernel client forward, its cohort and micro-batch) compiles with the
    kernels in it, every kernel under the cohort's scope and every matrix
    product under one phase's, and fits a 16 GiB chip."""
    import importlib.util
    import os

    from repro.configs.gpt2 import gpt2_medium
    from repro.core import protocols as P
    from repro.core import zo as Z
    from repro.distributed.sharding import AxisRules
    from repro.kernels import ops as O
    from repro.models import transformer as T
    from repro.optim.optimizers import make_optimizer

    path = os.path.join(os.path.dirname(__file__), "..", "chip_smoke.py")
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    # the host is a CPU: steer the backend choice to the chip's
    monkeypatch.setattr(O, "default_forward_impl", lambda: "pallas")
    monkeypatch.setattr(O, "_interpret", lambda: False)
    cfg = gpt2_medium().replace(forward_impl="kernel")
    sopt = make_optimizer("adamw", 1e-4)
    fn = P.make_fed_round(
        P.lm_api(cfg, AxisRules(mesh=None)), "heron", Z.ZOConfig(),
        P.FedConfig(n_clients=smoke.CLIENTS, h=1),
        make_optimizer("zo_sgd", 1e-4), sopt, uplink="seed_replay",
        client_lr=1e-4)
    state = jax.eval_shape(lambda p: {
        "client": p["client"], "server": p["server"],
        "opt_server": sopt.init(p["server"])},
        T.init_lm(None, cfg, mode="shape"))
    state = jax.tree.map(lambda s: _sds(one_chip, s.shape, s.dtype), state)
    tok = _sds(one_chip, (smoke.CLIENTS, 1, smoke.MICRO_BATCH, smoke.SEQ),
               jnp.int32)
    c = jax.jit(fn, donate_argnums=0).lower(
        state, {"inputs": tok, "labels": tok},
        _sds(one_chip, (2,), jnp.uint32)).compile()
    text = c.as_text()
    assert "tpu_custom_call" in text
    # the compiler's own custom-calls (buffers, bitcasts) carry no op_name
    assert hlo_scopes.phase_faults(text, targets={"tpu_custom_call"}) == []
    kernels = [op for _, rest, op in hlo_scopes.instructions(text)
               if 'custom_call_target="tpu_custom_call"' in rest]
    assert kernels and all("heron_cohort" in hlo_scopes.scopes(op)
                           for op in kernels)
    ma = c.memory_analysis()
    assert ma.argument_size_in_bytes + ma.temp_size_in_bytes < 14 * 2 ** 30


FLASH_CALLS = ("flash_attention_fwd", "flash_attention_dq",
               "flash_attention_dkv")


@pytest.mark.parametrize("layer,batch,seq", [("attention", B, S),
                                             ("mla", 1, ML_SEQ)])
def test_server_attention_gradient_takes_flash_kernels(one_chip,
                                                       monkeypatch, layer,
                                                       batch, seq):
    """On the chip the server's differentiated self-attention runs the
    flash kernel and its two backward kernels, at GPT-2 Medium's server
    shape and Moonlight's MLA, so no f32 score tile reaches HBM: no f32
    tensor of every head's (c, c) scores of a query block against a key
    block, c = min(seq, q_chunk), which for Medium is all B·H·S² scores
    (the XLA blocked path writes them in the forward, the remat forward
    and the backward)."""
    import math
    import re

    from repro.configs.gpt2 import gpt2_medium
    from repro.configs.moonlight_16b_a3b import chip_share
    from repro.distributed.sharding import AxisRules
    from repro.models import attention as A
    from repro.models import layers as L

    monkeypatch.setattr(A, "_fa_impl", lambda cfg: "pallas")
    if layer == "attention":
        cfg, fn, init = gpt2_medium(), A.attention_layer, A.init_attention
    else:
        cfg, fn, init = chip_share(), A.mla_layer, A.init_mla
    cfg = cfg.replace(forward_impl="kernel")
    params = jax.tree.map(
        lambda s: _sds(one_chip, s.shape, s.dtype),
        init(L.ParamBuilder(None, "shape", jnp.bfloat16), "attn", cfg))

    def loss(p, x):
        out, _ = fn(p, x, cfg, AxisRules(mesh=None))
        return jnp.sum(out.astype(jnp.float32))

    text = _hlo(jax.grad(jax.checkpoint(loss), argnums=(0, 1)), params,
                _sds(one_chip, (batch, seq, cfg.d_model)))
    calls = {op for _, rest, op in hlo_scopes.instructions(text)
             if 'custom_call_target="tpu_custom_call"' in rest}
    for name in FLASH_CALLS:
        assert any(f"/{name}/" in op for op in calls), (name, calls)
    c = min(seq, cfg.q_chunk)
    scores = [d for d in re.findall(r"\bf32\[([0-9,]+)\]", text)
              if d.endswith(f",{c},{c}")
              and math.prod(map(int, d.split(","))) >= batch * 16 * c * c]
    assert scores == []


def test_mesh_heron_step_keeps_mosaic_out(topo, one_chip, monkeypatch):
    """The datacenter HERON step on a ("data", "model") mesh of all four
    described chips (``chip_smoke.py --chips 4``, ``launch/train.py``)
    compiles with the default forward_impl: GSPMD cannot partition a
    Mosaic call, so on a multi-device mesh the dual probe runs the same
    stream's jnp emulation and the server's attention stays on XLA."""
    from jax.sharding import NamedSharding, PartitionSpec

    from repro.configs.gpt2 import gpt2_tiny
    from repro.core import protocols as P
    from repro.core import zo as Z
    from repro.distributed.sharding import AxisRules, auto_mesh
    from repro.kernels import ops as O
    from repro.models import attention as A
    from repro.models import transformer as T
    from repro.optim.optimizers import make_optimizer

    # the host is a CPU: steer the backend choice to the chip's
    monkeypatch.setattr(O, "default_forward_impl", lambda: "pallas")
    monkeypatch.setattr(O, "_interpret", lambda: False)
    monkeypatch.setattr(A, "_fa_impl", lambda cfg: "pallas")
    mesh = auto_mesh((2, 2), ("data", "model"), devices=topo.devices)
    rules = AxisRules(mesh=mesh, enable_fsdp=False)
    cfg = gpt2_tiny().replace(param_dtype="bfloat16",
                              compute_dtype="bfloat16")
    copt = make_optimizer("zo_sgd", 1e-4)
    sopt = make_optimizer("adamw", 1e-4)
    step = P.make_train_step(P.lm_api(cfg, rules), "heron",
                             Z.ZOConfig(mu=1e-2), copt, sopt)
    state = jax.eval_shape(
        lambda p: P.init_train_state(jnp.zeros((2,), jnp.uint32), p, copt,
                                     sopt), T.init_lm(None, cfg, mode="shape"))
    rep = NamedSharding(mesh, PartitionSpec())
    state = jax.tree.map(lambda s: _sds(rep, s.shape, s.dtype), state)
    tok = _sds(rules.sharding_for((8, 2 * S), ("batch", None)), (8, 2 * S),
               jnp.int32)
    text = _hlo(step, state, {"inputs": tok, "labels": tok})
    assert "tpu_custom_call" not in text
    assert "all-reduce" in text or "all-gather" in text
