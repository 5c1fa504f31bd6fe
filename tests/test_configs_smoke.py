"""Per-architecture smoke tests: reduced config, one forward + one HERON
train step on CPU; output shapes + finiteness.  (Full configs are only
exercised via the dry-run with ShapeDtypeStructs.)"""
import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import SHAPES, supports_shape
from repro.configs.registry import ARCH_IDS, get_config
from repro.core import protocols as P
from repro.core import zo as Z
from repro.distributed.sharding import AxisRules
from repro.models import transformer as T
from repro.optim.optimizers import make_optimizer

RULES = AxisRules(mesh=None)


def smoke_batch(cfg, B=2, S=16, seed=1):
    key = jax.random.PRNGKey(seed)
    if cfg.enc_dec:
        return {"inputs": jax.random.normal(key, (B, S, cfg.d_model)),
                "aux_labels": jax.random.randint(key, (B, S), 0,
                                                 cfg.vocab),
                "dec_tokens": jax.random.randint(key, (B, S), 0,
                                                 cfg.vocab),
                "labels": jax.random.randint(key, (B, S), 0, cfg.vocab)}
    if cfg.frontend == "vision":
        pos = jnp.broadcast_to(jnp.arange(S)[None, None],
                               (3, B, S)).astype(jnp.int32)
        return {"inputs": jax.random.normal(key, (B, S, cfg.d_model)),
                "positions": pos,
                "labels": jax.random.randint(key, (B, S), 0, cfg.vocab)}
    if cfg.frontend == "audio":
        return {"inputs": jax.random.normal(key, (B, S, cfg.d_model)),
                "labels": jax.random.randint(key, (B, S), 0, cfg.vocab)}
    return {"inputs": jax.random.randint(key, (B, S), 0, cfg.vocab),
            "labels": jax.random.randint(key, (B, S), 0, cfg.vocab)}


@pytest.mark.parametrize("arch", list(ARCH_IDS))
def test_arch_smoke_forward_and_heron_step(arch):
    cfg = get_config(arch, smoke=True)
    params = T.init_lm(jax.random.PRNGKey(0), cfg)
    B, S = 2, 16
    batch = smoke_batch(cfg, B, S)
    # forward
    logits = T.full_forward(params, cfg, RULES, batch["inputs"],
                            positions=batch.get("positions"),
                            dec_tokens=batch.get("dec_tokens"))
    assert logits.shape == (B, S, cfg.vocab_padded)
    assert bool(jnp.all(jnp.isfinite(logits)))
    # one HERON train step
    api = P.lm_api(cfg, RULES)
    copt = make_optimizer("zo_sgd", 1e-3)
    sopt = make_optimizer("adamw", 1e-3)
    state = P.init_train_state(jax.random.PRNGKey(2), params, copt, sopt)
    step = jax.jit(P.make_train_step(api, "heron", Z.ZOConfig(),
                                     copt, sopt))
    state2, m = step(state, batch)
    assert bool(jnp.isfinite(m["loss"]))
    assert bool(jnp.isfinite(m["client_loss"]))
    # params actually changed
    d0 = jax.tree.leaves(state["params"])[0]
    d1 = jax.tree.leaves(state2["params"])[0]
    assert d0.shape == d1.shape


@pytest.mark.parametrize("arch", list(ARCH_IDS))
def test_shape_support_table(arch):
    cfg = get_config(arch)
    ok_train, _ = supports_shape(cfg, SHAPES["train_4k"])
    assert ok_train
    ok_long, why = supports_shape(cfg, SHAPES["long_500k"])
    assert ok_long == cfg.subquadratic
    if not ok_long:
        assert "sub-quadratic" in why


def test_exact_assigned_configs():
    """The full configs carry the exact assigned hyperparameters."""
    expect = {
        "qwen2-1.5b": (28, 1536, 12, 2, 8960, 151936),
        "command-r-35b": (40, 8192, 64, 8, 22528, 256000),
        "qwen2.5-32b": (64, 5120, 40, 8, 27648, 152064),
        "gemma2-27b": (46, 4608, 32, 16, 36864, 256000),
        "kimi-k2-1t-a32b": (61, 7168, 64, 64, 18432, 163840),
        "moonlight-16b-a3b": (27, 2048, 16, 16, 11264, 163840),
        "qwen3-moe-30b-a3b": (48, 2048, 32, 4, 768, 151936),
        "seamless-m4t-medium": (24, 1024, 16, 16, 4096, 256206),
        "xlstm-1.3b": (48, 2048, 4, 4, 0, 50304),
        "recurrentgemma-9b": (38, 4096, 16, 1, 12288, 256000),
        "qwen2-vl-2b": (28, 1536, 12, 2, 8960, 151936),
    }
    for arch, (L, d, h, kv, ff, v) in expect.items():
        cfg = get_config(arch)
        assert cfg.n_layers == L, arch
        assert cfg.d_model == d, arch
        assert cfg.n_heads == h, arch
        assert cfg.n_kv_heads == kv, arch
        assert cfg.d_ff == ff, arch
        assert cfg.vocab == v, arch
    # MoE specifics
    kimi = get_config("kimi-k2-1t-a32b")
    assert kimi.moe.n_experts == 384 and kimi.moe.top_k == 8
    assert (kimi.q_lora_rank, kimi.kv_lora_rank) == (1536, 512)
    assert kimi.moe.n_shared_experts == 1 and kimi.moe.scoring == "sigmoid"
    ml = get_config("moonlight-16b-a3b")
    assert (ml.moe.n_experts, ml.moe.top_k, ml.moe.d_ff_expert,
            ml.moe.n_shared_experts) == (64, 6, 1408, 2)
    assert (ml.kv_lora_rank, ml.qk_nope_dim, ml.qk_rope_dim,
            ml.v_head_dim) == (512, 128, 64, 128)
    assert [s.ffn for s in ml.layer_specs()[:2]] == ["dense", "moe"]
    q3 = get_config("qwen3-moe-30b-a3b")
    assert q3.moe.n_experts == 128 and q3.moe.top_k == 8
    # patterns
    g2 = get_config("gemma2-27b")
    assert len(g2.pattern) == 2 and g2.attn_softcap == 50.0
    rg = get_config("recurrentgemma-9b")
    assert [s.mixer for s in rg.pattern] == ["rg_lru", "rg_lru",
                                             "local_attn"]
    xl = get_config("xlstm-1.3b")
    assert [s.mixer for s in xl.pattern].count("mlstm") == 7
