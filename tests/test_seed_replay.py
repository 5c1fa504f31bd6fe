"""Seed-replay lean uplink: the scan-vectorized reconstruction matches
the loop oracle, masked clients contribute nothing, and the mesh-sharded
/ chunked engine modes match the flat scan.  (The replayed ZO step is in
test_zo.py, the fed round's lean-vs-dense match in
test_zo_kernel_path.py.)"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import aggregate as AG
from repro.core import protocols as P
from repro.core import zo as Z
from repro.kernels import ops as O

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def make_params():
    return {"w": jnp.ones((6, 3)), "b": {"c": jnp.linspace(-1.0, 1.0, 5)}}


LOOP_CASES = {
    # (params, n, h, n_pairs, lr, mask, rtol)
    "tree-masked": (make_params, 3, 2, 2, 1e-2,
                    jnp.array([1.0, 0.0, 1.0]), 1e-5),
    "matrix": (lambda: {"w": jax.random.normal(jax.random.PRNGKey(0),
                                               (6, 6))}, 3, 2, 2, 0.05,
               None, 2e-5),
}


@pytest.mark.parametrize("case", list(LOOP_CASES))
def test_scan_aggregate_matches_loop_reference(case):
    """The flattened-scan replay equals the unvectorized (client, step,
    pair) loop over fold_seed and the materialized directions."""
    make, n, h, pairs, lr, mask, rtol = LOOP_CASES[case]
    params = make()
    seeds = O.fold_seed(jnp.int32(77), jnp.arange(n))
    coeffs = jax.random.normal(jax.random.PRNGKey(1), (n, h, pairs))
    fast = jax.jit(lambda c: AG.seed_replay_aggregate(
        params, seeds, c, lr, mask))(coeffs)
    ref = AG.seed_replay_aggregate_reference(params, seeds, coeffs, lr,
                                             mask)
    for a, b in zip(jax.tree.leaves(fast), jax.tree.leaves(ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=rtol, atol=1e-6)


def test_masked_clients_contribute_nothing():
    params = make_params()
    n, h, pairs = 3, 1, 2
    seeds = O.fold_seed(jnp.int32(0), jnp.arange(n))
    coeffs = jax.random.normal(jax.random.PRNGKey(1), (n, h, pairs))
    mask = jnp.array([1.0, 0.0, 1.0])
    out = AG.seed_replay_aggregate(params, seeds, coeffs, 1e-2, mask)
    # poisoning the masked-out client's coefficients changes nothing
    poisoned = coeffs.at[1].set(1e6)
    out_p = AG.seed_replay_aggregate(params, seeds, poisoned, 1e-2, mask)
    for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(out_p)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # ... but an unmasked client's coefficients do
    out_u = AG.seed_replay_aggregate(params, seeds,
                                     coeffs.at[0].set(1e6), 1e-2, mask)
    assert any(float(jnp.max(jnp.abs(a - b))) > 1e-3
               for a, b in zip(jax.tree.leaves(out),
                               jax.tree.leaves(out_u)))


def test_chunked_streaming_bit_exact_kernel():
    """Unsharded chunking continues the same scan carry: the donated
    chunk stream is bit-identical to the one-shot scan, for every chunk
    size including non-divisors of N*h*n_pairs."""
    params = make_params()
    n, h, pairs = 5, 2, 2
    seeds = O.fold_seed(jnp.int32(9), jnp.arange(n))
    coeffs = jax.random.normal(jax.random.PRNGKey(5), (n, h, pairs))
    one_shot = AG.seed_replay_aggregate(params, seeds, coeffs, 1e-2)
    for chunk in (1, 3, 7, 20, 64):
        chunked = AG.seed_replay_aggregate(params, seeds, coeffs, 1e-2,
                                           chunk=chunk)
        for a, b in zip(jax.tree.leaves(one_shot),
                        jax.tree.leaves(chunked)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_replay_mesh_validation():
    with pytest.raises(ValueError, match="not in mesh"):
        AG._resolve_replay_mesh(
            "clients", jax.make_mesh((1,), ("model",)))


_SHARDED_PROG = """
    import jax, jax.numpy as jnp, numpy as np
    from repro.core import aggregate as AG
    from repro.kernels import ops as O

    params = {"w": jax.random.normal(jax.random.PRNGKey(0), (12, 6)),
              "b": {"c": jnp.linspace(-1.0, 1.0, 7)}}
    n, h, pairs, lr = 7, 2, 2, 1e-2   # n not divisible by the mesh
    seeds = O.fold_seed(jnp.int32(42), jnp.arange(n))
    coeffs = jax.random.normal(jax.random.PRNGKey(1), (n, h, pairs))
    mask = jnp.array([1., 1., 0., 1., 1., 0., 1.])

    def leaves_close(a, b, tol=1e-6):
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
            np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                       rtol=1e-5, atol=tol)

    for m in (None, mask):
        flat = AG.seed_replay_aggregate(params, seeds, coeffs, lr, m)
        sh = AG.seed_replay_aggregate(params, seeds, coeffs, lr, m,
                                      shard="clients")
        leaves_close(flat, sh)
        shch = AG.seed_replay_aggregate(params, seeds, coeffs, lr, m,
                                        shard="clients", chunk=3)
        leaves_close(flat, shch)

    # masked clients contribute nothing under sharding: poisoning their
    # coefficients leaves the sharded result unchanged
    sh = AG.seed_replay_aggregate(params, seeds, coeffs, lr, mask,
                                  shard="clients")
    sh_p = AG.seed_replay_aggregate(params, seeds,
                                    coeffs.at[2].set(1e6), lr, mask,
                                    shard="clients")
    leaves_close(sh, sh_p, tol=0)
    print("SHARDED_OK devices=", jax.device_count())
"""


@pytest.mark.parametrize("devices", [1, 2, 4])
def test_sharded_matches_flat_scan(devices):
    """shard='clients' over a 1/2/4-device host mesh reproduces the flat
    scan (fp32 allclose), masked and unmasked, with and without
    chunking."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={devices}")
    env["PYTHONPATH"] = SRC
    env.setdefault("JAX_PLATFORMS", "cpu")
    r = subprocess.run([sys.executable, "-c",
                        textwrap.dedent(_SHARDED_PROG)], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "SHARDED_OK" in r.stdout


def _cnn_round_setup():
    from repro.data.pipeline import round_batches
    from repro.data.synthetic import GaussianMixtureImages
    from repro.models import cnn as CNN
    from repro.optim.optimizers import make_optimizer

    cfg = CNN.CNNConfig(widths=(8, 16), blocks_per_stage=1, classes=4,
                        client_blocks=1)
    ds = GaussianMixtureImages(classes=4, hw=8, noise=0.5)
    api = P.cnn_api(cfg)
    params = CNN.init_cnn(jax.random.PRNGKey(0), cfg)
    sopt = make_optimizer("adamw", 2e-3)
    state = {"client": params["client"], "server": params["server"],
             "opt_server": sopt.init(params["server"])}
    return api, state, sopt, round_batches, ds, make_optimizer


def test_fed_round_seed_replay_validation():
    api, state, sopt, _, _, make_optimizer = _cnn_round_setup()
    zo = Z.ZOConfig(mu=1e-3, n_pairs=1)
    fed = P.FedConfig(n_clients=2, h=1)
    copt = make_optimizer("adamw", 1e-3)
    with pytest.raises(ValueError, match="heron"):
        P.make_fed_round(api, "cse_fsl", zo, fed, copt, sopt,
                         uplink="seed_replay", client_lr=1e-2)
    with pytest.raises(ValueError, match="client_lr"):
        P.make_fed_round(api, "heron", zo, fed, copt, sopt,
                         uplink="seed_replay")
