"""Zeroth-order (ZO) optimization core — the paper's central mechanism.

Implements the two-point ZO gradient estimator of Eq. (2) with one
direction stream, the per-layer int32 hash noise of
``kernels/zo_matmul``:

    g_hat = sum_p [ l(theta + mu*u_p; xi) - l(theta; xi) ] / (mu*n_pairs) * u_p

* seed-procedural perturbations (MeZO-style): ``u_p`` is a pure function
  of an int32 seed — never stored, always regenerated, so a client
  update can be *communicated* as ``(seed, coeff)`` pairs (seed-replay
  aggregation, see core/aggregate.py);
* the model's dual-probe forward generates ``u_p`` inside the matmul
  kernels, tile by tile, and returns both losses of a pair from one read
  of the weights;
* the noise is unit-variance uniform, iid per entry, so the estimator
  needs no dimension factor (E[u u^T] = I);
* n-pair variance reduction (paper Fig. 4: 2 perturbations/epoch suffice);
* a trainable-subtree filter so LoRA fine-tuning perturbs adapters only.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.kernels import ops as O


@dataclasses.dataclass(frozen=True)
class ZOConfig:
    mu: float = 1e-3
    n_pairs: int = 1            # number of two-point perturbation pairs


def add_scaled(params, direction, scale):
    return jax.tree.map(
        lambda p, u: (p.astype(jnp.float32)
                      + scale * u.astype(jnp.float32)).astype(p.dtype),
        params, direction)


def seed_from_key(key):
    """Stable int32 base seed from a raw uint32 PRNG key."""
    kd = jnp.reshape(key, (-1,)).astype(jnp.uint32)
    return (kd[0] ^ kd[-1]).astype(jnp.int32)


def pair_seeds(base_seed, n_pairs: int):
    """The per-pair seed stream: fold_seed(base, p) for p < n_pairs."""
    return O.fold_seed(base_seed, jnp.arange(max(n_pairs, 1)))


def zo_gradient(dual_loss_fn, params, base_seed, zo: ZOConfig,
                seed_pred=None):
    """Two-point ZO gradient of the loss at ``params``.

    ``dual_loss_fn(params, seeds_tree, mu) -> (l_clean, l_pert, aux,
    stats)`` must evaluate BOTH losses of the pair — the model's
    dual-probe forward does this in one pass per layer; ``stats`` is a
    dict of counters of that forward (possibly empty).  ``params`` may
    contain None placeholders (frozen leaves from ``partition``); their
    seeds are None and they are never perturbed.  Returns (grad_tree,
    info): ``info`` carries the clean loss and aux of the last pair, the
    projected-gradient coefficients (the lean-uplink scalars; see
    :func:`replay_gradient`) and ``stats``, the counters summed over the
    pairs.

    Cost: ``n_pairs`` dual-probe forwards, zero backward passes — the
    client-side FLOP reduction of Table I (2(F_c+F_a) at n_pairs=1).
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


def replay_gradient(params, base_seed, coeffs, seed_pred=None):
    """Regenerate the ZO gradient from its lean ``(base_seed, coeffs)``
    uplink form.  Same accumulation order as :func:`zo_gradient` minus
    the forward passes; the regenerated directions are bit-identical
    (hash noise is backend-invariant) and the accumulated gradient
    matches to f32 fusion rounding."""
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

