"""Attention paths: blocked==naive, windows, softcap, decode==full."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import attention as A


def qkv(seq=37, b=2, h=4, kv=2, d=16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (b, seq, h, d)),
            jax.random.normal(ks[1], (b, seq, kv, d)),
            jax.random.normal(ks[2], (b, seq, kv, d)))


@pytest.mark.parametrize("window", [0, 9])
@pytest.mark.parametrize("cap", [None, 25.0])
@pytest.mark.parametrize("causal_skip", [False, True])
def test_blocked_matches_naive(window, cap, causal_skip):
    q, k, v = qkv()
    o1 = A.naive_attention(q, k, v, causal=True, window=window, cap=cap)
    o2 = A.blocked_attention(q, k, v, causal=True, window=window, cap=cap,
                             q_chunk=8, kv_chunk=8,
                             causal_skip=causal_skip)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                               rtol=2e-4, atol=2e-5)


def test_noncausal_cross():
    q, k, v = qkv(seq=24)
    o1 = A.naive_attention(q, k, v, causal=False)
    o2 = A.blocked_attention(q, k, v, causal=False, q_chunk=8, kv_chunk=8)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                               rtol=2e-4, atol=2e-5)


def test_decode_attention_matches_naive_last_row():
    q, k, v = qkv(seq=21)
    full = A.naive_attention(q, k, v, causal=True)
    o = A.decode_attention(q[:, -1:], k, v, valid_len=21)
    np.testing.assert_allclose(np.asarray(full[:, -1:]), np.asarray(o),
                               rtol=2e-4, atol=2e-5)


def test_gqa_grouping():
    # kv == heads (MHA) must equal kv=1 (MQA) with broadcast kv
    q, k, v = qkv(h=4, kv=1)
    o = A.naive_attention(q, k, v, causal=True)
    k4 = jnp.broadcast_to(k, k.shape[:2] + (4, k.shape[-1]))
    v4 = jnp.broadcast_to(v, v.shape[:2] + (4, v.shape[-1]))
    o4 = A.naive_attention(q, k4, v4, causal=True)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o4),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("seq,kernel", [(128, False), (640, True)])
def test_chip_server_attention_takes_flash_past_one_tile(monkeypatch, seq,
                                                         kernel):
    """On the chip a differentiated self-attention runs the flash kernels
    once its sequence spans more than one tile, and stays on XLA within
    one (the traced gradient holds the Pallas calls or none)."""
    from repro.configs.gpt2 import gpt2_tiny
    from repro.distributed.sharding import AxisRules
    from repro.models import layers as L

    monkeypatch.setattr(A, "_fa_impl", lambda cfg: "pallas")
    cfg = gpt2_tiny().replace(forward_impl="kernel")
    params = A.init_attention(L.ParamBuilder(jax.random.PRNGKey(0)),
                              "attn", cfg)

    def loss(p, x):
        return jnp.sum(A.attention_layer(p, x, cfg, AxisRules(mesh=None))[0])

    x = jnp.ones((1, seq, cfg.d_model))
    text = str(jax.make_jaxpr(jax.grad(loss))(params, x))
    assert ("pallas_call" in text) == kernel
