"""Buffered-async Fed-Server over the lean seed-replay uplink.

The synchronous round (`core/protocols.make_fed_round`) gates every
global update on the slowest client.  Because a HERON client's whole
round update is a `(seed, coeffs)` token (DESIGN.md §6), the server can
instead apply updates *as they arrive*:

* arrivals are buffered and the global snapshots forward every ``K``
  arrivals (FedBuff-style; ``buffer_k=0`` = one barrier flush at round
  end, which reproduces the synchronous aggregation bit-exactly);
* each entry is scaled by a staleness weight ``w(τ) = (1+τ)^(-α)``
  (polynomial decay per the gradient-aggregation analysis of Liang et
  al., arXiv:2501.01078), where ``τ`` is the number of global snapshots
  taken since the client pulled its base model;
* the weight is **pre-folded into the per-entry scales** of the
  flattened (client, step, pair) stream, so the donated-accumulator /
  chunked / mesh-sharded paths of
  :func:`repro.core.aggregate._replay_engine` all work unchanged.

Bit-exactness contract: a single flush holding the full cohort in
client-id order with every weight exactly 1.0 produces byte-identical
tokens and scales to :func:`repro.core.aggregate.seed_replay_aggregate`
(both call :func:`repro.core.aggregate.replay_token_stream`), hence a
bit-identical new global.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import aggregate as AG


@dataclasses.dataclass(frozen=True)
class StalenessConfig:
    """``w(τ) = (1+τ)^(-alpha)``; ``alpha=0`` keeps every weight at
    exactly 1.0 (the bit-exact synchronous limit)."""
    alpha: float = 0.0

    def weight(self, tau) -> float:
        return staleness_weight(tau, self.alpha)


def staleness_weight(tau, alpha: float) -> float:
    """Polynomial staleness decay.  Exact 1.0 at ``tau == 0`` or
    ``alpha == 0`` so the pre-folded scales are bit-identical to the
    unweighted stream in the synchronous limit."""
    if alpha == 0.0 or tau == 0:
        return 1.0
    return float((1.0 + float(tau)) ** (-float(alpha)))


@dataclasses.dataclass
class AsyncTelemetry:
    arrivals: int = 0
    flushes: int = 0
    dropped: int = 0            # zero-weight (masked-out) arrivals
    staleness_sum: float = 0.0
    flush_times: list = dataclasses.field(default_factory=list)
    flush_sizes: list = dataclasses.field(default_factory=list)

    @property
    def mean_staleness(self) -> float:
        return self.staleness_sum / max(self.arrivals, 1)


# One jitted flush body per engine configuration, shared across server
# instances (a fresh AsyncReplayServer per round must not recompile).
_APPLY_CACHE: dict = {}


def _cached_apply(client_lr, shard, mesh, seed_pred):
    key = (client_lr, shard, mesh, seed_pred)
    fn = _APPLY_CACHE.get(key)
    if fn is None:
        md = AG.kernel_direction_builder(seed_pred)

        def _apply(params, tokens, coeffs, weights, tot):
            toks, scales = AG.replay_token_stream(
                tokens, coeffs, client_lr, weights, tot)
            return AG._replay_engine(params, toks, scales, md,
                                     shard=shard, mesh=mesh, chunk=None)

        fn = _APPLY_CACHE[key] = jax.jit(_apply)
    return fn


@dataclasses.dataclass
class _Arrival:
    cid: int
    token: Any              # int32 scalar seed
    coeffs: Any             # (h, n_pairs)
    mask: float
    base_version: int
    t_done: float


class AsyncReplayServer:
    """Applies seed-replay arrivals to the global client params.

    Parameters
    ----------
    global_params: the Fed-Server's client-side global tree.
    client_lr: the replayed plain-SGD local learning rate.
    buffer_k: snapshot a new global every ``buffer_k`` buffered
        arrivals; ``0`` means no auto-flush — callers flush explicitly
        (the synchronous barrier limit).
    shard / mesh / chunk: forwarded to ``_replay_engine`` — the
        staleness weights live in the scales, so every execution mode
        composes unchanged.
    seed_pred: selects the seeded leaves, as on the clients.
    on_flush: optional callback ``on_flush(cids, t)`` fired after each
        snapshot with the flushed client ids (in client-id order) and
        the flush's simulated completion time.
    """

    def __init__(self, global_params, client_lr: float, *,
                 staleness: StalenessConfig = StalenessConfig(),
                 buffer_k: int = 0, shard: str = "none", mesh=None,
                 chunk=None, seed_pred=None,
                 on_flush: Callable | None = None):
        self.params = global_params
        self.client_lr = client_lr
        self.staleness = staleness
        self.buffer_k = int(buffer_k)
        self._engine_kw = dict(shard=shard, mesh=mesh, chunk=chunk)
        self._make_direction = AG.kernel_direction_builder(seed_pred)
        if chunk is None:
            # jitted flush body, cached across server instances (one
            # compile per configuration and flush size)
            self._apply = _cached_apply(float(client_lr), shard, mesh,
                                        seed_pred)
        else:
            # the donated-chunk stream manages its own buffers eagerly
            def _apply(params, tokens, coeffs, weights, tot):
                toks, scales = AG.replay_token_stream(
                    tokens, coeffs, self.client_lr, weights, tot)
                return AG._replay_engine(params, toks, scales,
                                         self._make_direction,
                                         **self._engine_kw)

            self._apply = _apply
        self.on_flush = on_flush
        self.version = 0
        self._buf: list[_Arrival] = []
        self.telemetry = AsyncTelemetry()

    @property
    def pending(self) -> int:
        return len(self._buf)

    def submit(self, cid: int, token, coeffs, base_version: int | None = None,
               mask: float = 1.0, t_done: float = 0.0) -> int:
        """Buffer one client's round token.

        ``token`` is the client's replay token, an int32 scalar seed;
        ``coeffs`` the (h, n_pairs) projected-gradient scalars;
        ``base_version`` the global version the client trained from
        (defaults to the current one, i.e. zero staleness); ``mask`` the
        participation weight (0.0 = dropped/straggler: buffered but an
        exact no-op).
        Returns the current global version.
        """
        if base_version is None:
            base_version = self.version
        self._buf.append(_Arrival(int(cid), token, coeffs, float(mask),
                                  int(base_version), float(t_done)))
        self.telemetry.arrivals += 1
        if float(mask) == 0.0:
            self.telemetry.dropped += 1
        if self.buffer_k and len(self._buf) >= self.buffer_k:
            self.flush()
        return self.version

    def flush(self) -> list[int]:
        """Snapshot a new global from the buffered arrivals.

        Entries are ordered by client id (deterministic regardless of
        arrival order; the full-cohort single-flush case thereby
        reproduces the synchronous scan order exactly).  Staleness is
        evaluated at flush time: ``τ_i = version - base_version_i``.
        Returns the flushed client ids.
        """
        if not self._buf:
            return []
        entries = sorted(self._buf, key=lambda e: e.cid)
        self._buf = []
        taus = [self.version - e.base_version for e in entries]
        ws = [self.staleness.weight(t) for t in taus]
        tokens = jnp.asarray(np.stack(
            [np.asarray(e.token) for e in entries]))
        coeffs = jnp.stack([jnp.asarray(e.coeffs) for e in entries])
        masks = jnp.asarray([e.mask for e in entries], jnp.float32)
        weights = jnp.asarray(ws, jnp.float32) * masks
        tot = jnp.maximum(jnp.sum(masks), 1.0)
        self.params = self._apply(self.params, tokens, coeffs, weights,
                                  tot)
        self.version += 1
        t = max(e.t_done for e in entries)
        tel = self.telemetry
        tel.flushes += 1
        tel.staleness_sum += float(sum(taus))
        tel.flush_times.append(t)
        tel.flush_sizes.append(len(entries))
        cids = [e.cid for e in entries]
        if self.on_flush is not None:
            self.on_flush(cids, t)
        return cids
