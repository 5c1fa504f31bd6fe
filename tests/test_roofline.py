"""Roofline machinery: the scan-aware HLO analyzer is exact on FLOPs."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.hlo_costs import total_costs


def _flops(f, *args):
    c = jax.jit(f).lower(*args).compile()
    return total_costs(c.as_text()), c


def test_plain_matmul_flops():
    x = jnp.zeros((256, 512), jnp.float32)
    w = jnp.zeros((512, 128), jnp.float32)
    t, c = _flops(lambda a, b: a @ b, x, w)
    assert t["flops"] == pytest.approx(2 * 256 * 512 * 128, rel=0.01)


def test_scan_multiplies_trip_count():
    x = jnp.zeros((128, 128), jnp.bfloat16)
    ws = jnp.zeros((7, 128, 128), jnp.bfloat16)

    def f(x, ws):
        return jax.lax.scan(lambda c, w: (c @ w, None), x, ws)[0]

    t, _ = _flops(f, x, ws)
    assert t["flops"] == pytest.approx(7 * 2 * 128 ** 3, rel=0.02)


def test_nested_scan():
    x = jnp.zeros((64, 64), jnp.float32)
    ws = jnp.zeros((5, 64, 64), jnp.float32)

    def f(x, ws):
        def outer(c, w):
            c, _ = jax.lax.scan(lambda c2, _: (c2 @ w, None), c, None,
                                length=3)
            return c, None
        return jax.lax.scan(outer, x, ws)[0]

    t, _ = _flops(f, x, ws)
    assert t["flops"] == pytest.approx(15 * 2 * 64 ** 3, rel=0.02)


def test_matches_cost_analysis_when_scan_free():
    x = jnp.zeros((128, 256), jnp.float32)
    w1 = jnp.zeros((256, 512), jnp.float32)
    w2 = jnp.zeros((512, 64), jnp.float32)

    def f(x, w1, w2):
        return jax.nn.relu(x @ w1) @ w2

    t, c = _flops(f, x, w1, w2)
    ca = c.cost_analysis()
    ca = ca[0] if isinstance(ca, (list, tuple)) else ca
    assert t["flops"] == pytest.approx(float(ca["flops"]), rel=0.05)


def test_grad_flops_match_cost_analysis():
    w = jnp.zeros((128, 128), jnp.float32)
    x = jnp.zeros((64, 128), jnp.float32)

    def loss(w, x):
        return jnp.sum((x @ w) ** 2)

    t, c = _flops(jax.grad(loss), w, x)
    ca = c.cost_analysis()
    ca = ca[0] if isinstance(ca, (list, tuple)) else ca
    assert t["flops"] == pytest.approx(float(ca["flops"]), rel=0.05)


def test_roofline_terms_structure():
    from repro.launch.roofline import roofline_terms
    x = jnp.zeros((256, 256), jnp.float32)
    c = jax.jit(lambda a: a @ a).lower(x).compile()
    terms = roofline_terms(c, device_kind="TPU v5 lite")
    for k in ("compute_s", "memory_s", "collective_s", "bottleneck",
              "roofline_step_s", "flops", "bytes_accessed"):
        assert k in terms
    assert terms["collective_bytes"] == 0.0
    assert terms["bottleneck"] in ("compute", "memory", "collective")


def test_roofline_peaks_refuse_unknown_device_kind():
    from repro.launch.roofline import peaks_for, roofline_terms
    assert peaks_for("TPU v5 lite").flops == 197e12
    c = jax.jit(lambda a: a + 1).lower(jnp.zeros((8,))).compile()
    with pytest.raises(ValueError, match="no published peaks"):
        roofline_terms(c, device_kind="cpu")
