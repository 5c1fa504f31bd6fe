"""Zeroth-order (ZO) optimization core — the paper's central mechanism.

Implements the two-point ZO gradient estimator of Eq. (2):

    g_hat = (d/mu) * [ l(theta + mu*u; xi) - l(theta; xi) ] * u,
    u ~ Unif(S^{d-1})

with

* seed-procedural perturbations (MeZO-style): ``u`` is a deterministic
  function of a PRNG key — never stored, always regenerated, so a client
  update can be *communicated* as ``(seed, coeff)`` pairs (seed-replay
  aggregation, see core/aggregate.py);
* n-pair variance reduction (paper Fig. 4: 2 perturbations/epoch suffice);
* a trainable-subtree filter so LoRA fine-tuning perturbs adapters only.

On TPU the perturbed forward is additionally served by the
``kernels/zo_matmul`` Pallas kernel which generates ``u`` tile-by-tile in
VMEM (zero HBM traffic for perturbations); this module is the
framework-level, backend-agnostic path.
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops as O


@dataclasses.dataclass(frozen=True)
class ZOConfig:
    mu: float = 1e-3
    n_pairs: int = 1            # number of two-point perturbation pairs
    scale: str = "sphere"       # sphere (Eq. 2, with d factor) | gaussian


# ---------------------------------------------------------------------------
# tree-level perturbation utilities
# ---------------------------------------------------------------------------

def tree_size(tree) -> int:
    return int(sum(np.prod(l.shape) for l in jax.tree.leaves(tree)))


def normal_like(key, tree, shardings=None):
    """Per-leaf standard normals, deterministic in (key, tree structure).

    ``shardings`` (optional matching pytree of NamedShardings/None) pins
    each perturbation leaf to its parameter's sharding so that on a big
    mesh the direction is *generated* sharded — never replicated in HBM.
    """
    leaves, treedef = jax.tree.flatten(tree)
    shard_leaves = (jax.tree.leaves(
        shardings, is_leaf=lambda x: x is None)
        if shardings is not None else [None] * len(leaves))
    if len(shard_leaves) != len(leaves):
        shard_leaves = [None] * len(leaves)
    keys = jax.random.split(key, max(len(leaves), 1))
    zs = []
    for k, l, sh in zip(keys, leaves, shard_leaves):
        z = jax.random.normal(k, l.shape, jnp.float32)
        if sh is not None:
            z = jax.lax.with_sharding_constraint(z, sh)
        zs.append(z)
    return jax.tree.unflatten(treedef, zs)


def global_norm(tree):
    return jnp.sqrt(sum(jnp.sum(jnp.square(l.astype(jnp.float32)))
                        for l in jax.tree.leaves(tree)) + 1e-30)


def unit_sphere_like(key, tree, shardings=None):
    """u ~ Unif(S^{d-1}) over the flattened tree (||u||_2 = 1)."""
    z = normal_like(key, tree, shardings)
    nrm = global_norm(z)
    return jax.tree.map(lambda l: l / nrm, z)


def add_scaled(params, direction, scale):
    return jax.tree.map(
        lambda p, u: (p.astype(jnp.float32)
                      + scale * u.astype(jnp.float32)).astype(p.dtype),
        params, direction)


def fold_in_range(key, n: int):
    """Batched key derivation: stacked ``fold_in(key, i)`` for i < n.

    One vmapped threefry dispatch instead of ``n`` sequential host-side
    folds — the building block for scanning over perturbation pairs and
    for the flattened (client, step, pair) seed-replay scan."""
    return jax.vmap(lambda i: jax.random.fold_in(key, i))(jnp.arange(n))


def direction_like(key, tree, zo: "ZOConfig", shardings=None):
    """The pair direction u for one folded key, per the configured scale."""
    if zo.scale == "sphere":
        return unit_sphere_like(key, tree, shardings)
    return normal_like(key, tree, shardings)


# ---------------------------------------------------------------------------
# the two-point estimator
# ---------------------------------------------------------------------------

def zo_gradient(loss_fn: Callable, params, key, zo: ZOConfig,
                shardings=None):
    """Two-point ZO gradient estimate of ``loss_fn`` at ``params``.

    ``loss_fn(params) -> (scalar loss, aux)``; the mini-batch is closed
    over (Eq. 2 uses one shared ``u`` across the batch).  Returns
    (grad_tree, info) where info carries the clean loss/aux and the
    projected-gradient coefficients (for seed-replay uplink).

    Cost: ``1 + n_pairs`` forward passes, zero backward passes — this is
    the client-side FLOP reduction of Table I (2(F_c+F_a) at n_pairs=1).
    """
    d = tree_size(params)
    l0, aux0 = loss_fn(params)
    dim_factor = float(d) if zo.scale == "sphere" else 1.0
    g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    if zo.n_pairs == 0:
        return g0, {"loss": l0, "aux": aux0, "coeffs": jnp.zeros((0,))}

    def pair_step(g, kp):
        u = direction_like(kp, params, zo, shardings)
        lp, _ = loss_fn(add_scaled(params, u, zo.mu))
        coeff = dim_factor * (lp - l0) / zo.mu / zo.n_pairs
        g = jax.tree.map(lambda gl, ul: gl + coeff * ul, g, u)
        return g, coeff

    # scan over folded keys: n_pairs stays ONE jitted program (the old
    # Python loop unrolled n_pairs copies of the forward pass into HLO).
    g, coeffs = jax.lax.scan(pair_step, g0, fold_in_range(key, zo.n_pairs))
    info = {"loss": l0, "aux": aux0, "coeffs": coeffs}
    return g, info


def zo_projected_coeffs(loss_fn: Callable, params, key, zo: ZOConfig):
    """Lean-uplink form: returns only the scalar coefficients (one per
    pair).  Combined with the shared ``key`` this *is* the client->server
    message — O(n_pairs) floats instead of O(d)."""
    _, info = zo_gradient(loss_fn, params, key, zo)
    return info["coeffs"], info["loss"]


def replay_gradient(params, key, coeffs, zo: ZOConfig, shardings=None):
    """Regenerate the materialized ZO gradient from its lean ``(key,
    coeffs)`` uplink form: g = sum_p coeff_p u_p(key).  The scan body is
    the same accumulation as :func:`zo_gradient` (minus the forward
    passes), so the reconstruction is bit-exact."""
    g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    n = coeffs.shape[0]
    if n == 0:
        return g0

    def pair_step(g, kc):
        kp, coeff = kc
        u = direction_like(kp, params, zo, shardings)
        g = jax.tree.map(lambda gl, ul: gl + coeff * ul, g, u)
        return g, None

    g, _ = jax.lax.scan(pair_step, g0, (fold_in_range(key, n), coeffs))
    return g


# ---------------------------------------------------------------------------
# kernel-stream estimator (fused dual probe + per-layer hash seeds)
# ---------------------------------------------------------------------------
#
# The jax.random path above materializes each direction leaf-by-leaf with
# threefry.  The kernel path instead derives one int32 seed per parameter
# leaf (base_seed + path hash, see kernels.ops.leaf_seed_tree) and lets
# the model's forward generate the perturbation *inside* the matmul
# kernels (kernels.zo_matmul).  Both loss evaluations of the two-point
# estimator come out of ONE fused dual-probe pass, so each pair costs a
# single read of the weights.  The noise is unit-variance uniform
# (iid per entry), i.e. the gaussian-type estimator contract:
# dim_factor == 1 and coeff = (l_pert - l_clean) / mu / n_pairs, exactly
# as the scale="gaussian" branch of zo_gradient.

def seed_from_key(key):
    """Stable int32 base seed from a PRNG key (typed or raw uint32)."""
    kd = key
    try:
        if jnp.issubdtype(key.dtype, jax.dtypes.prng_key):
            kd = jax.random.key_data(key)
    except TypeError:
        pass
    kd = jnp.reshape(kd, (-1,)).astype(jnp.uint32)
    return (kd[0] ^ kd[-1]).astype(jnp.int32)


def pair_seeds(base_seed, n_pairs: int):
    """The per-pair seed stream: fold_seed(base, p) for p < n_pairs."""
    return O.fold_seed(base_seed, jnp.arange(max(n_pairs, 1)))


def zo_gradient_kernel(dual_loss_fn, params, base_seed, zo: ZOConfig,
                       seed_pred=None):
    """Two-point ZO gradient with the fused kernel noise stream.

    ``dual_loss_fn(params, seeds_tree, mu) -> (l_clean, l_pert, aux,
    stats)`` must evaluate BOTH losses of the pair — the model's
    dual-probe forward does this in one pass per layer; ``stats`` is a
    dict of counters of that forward (possibly empty).  ``params`` may
    contain None placeholders (frozen leaves from ``partition``); their
    seeds are None and they are never perturbed.  Returns (grad_tree,
    info) with the same contract as :func:`zo_gradient` (coeffs are the
    lean-uplink scalars; see :func:`replay_gradient_kernel`), plus
    ``info["stats"]``, the counters summed over the pairs.
    """
    g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    if zo.n_pairs == 0:
        seeds = O.leaf_seed_tree(params, base_seed, seed_pred)
        l0, _, aux, stats = dual_loss_fn(params, seeds, zo.mu)
        return g0, {"loss": l0, "aux": aux, "coeffs": jnp.zeros((0,)),
                    "stats": stats}

    def pair_step(g, sp):
        seeds = O.leaf_seed_tree(params, sp, seed_pred)
        l0, lp, aux, stats = dual_loss_fn(params, seeds, zo.mu)
        coeff = (lp - l0) / zo.mu / zo.n_pairs
        u = O.kernel_direction_tree(params, seeds)
        g = jax.tree.map(lambda gl, ul: gl + coeff * ul, g, u)
        return g, (coeff, l0, aux, stats)

    g, (coeffs, l0s, auxs, stats) = jax.lax.scan(
        pair_step, g0, pair_seeds(base_seed, zo.n_pairs))
    info = {"loss": l0s[-1],
            "aux": jax.tree.map(lambda a: a[-1], auxs),
            "coeffs": coeffs,
            "stats": jax.tree.map(lambda a: jnp.sum(a, axis=0), stats)}
    return g, info


def replay_gradient_kernel(params, base_seed, coeffs, seed_pred=None):
    """Regenerate the kernel-stream ZO gradient from its lean
    ``(base_seed, coeffs)`` uplink form.  Same accumulation order as
    :func:`zo_gradient_kernel` minus the forward passes; the regenerated
    directions are bit-identical (hash noise is backend-invariant) and
    the accumulated gradient matches to f32 fusion rounding."""
    g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    n = coeffs.shape[0]
    if n == 0:
        return g0

    def pair_step(g, sc):
        sp, coeff = sc
        u = O.kernel_direction_tree(
            params, O.leaf_seed_tree(params, sp, seed_pred))
        g = jax.tree.map(lambda gl, ul: gl + coeff * ul, g, u)
        return g, None

    g, _ = jax.lax.scan(pair_step, g0, (pair_seeds(base_seed, n), coeffs))
    return g


def replay_update(params, key, coeffs, lr, zo: ZOConfig, shardings=None):
    """Server-side (or on-device, streaming) reconstruction of the ZO
    SGD step from (key, coeffs): theta <- theta - lr * sum_p coeff_p u_p.
    Regenerates each u from the seed inside a single jitted scan; the
    full direction never persists beyond one scan iteration."""
    g = replay_gradient(params, key, coeffs, zo, shardings)
    return add_scaled(params, g, -lr)
