"""SFL training protocols: HERON-SFL (ours) and the paper's baselines
(SFLV1/V2, CSE-FSL, FSL-SAGE, SplitLoRA), in two execution modes:

* **datacenter step** (`make_train_step`) — one jitted hybrid ZO/FO step
  on the production mesh; the data-parallel shards act as virtual client
  cohorts (see DESIGN.md §3).  This is what the multi-pod dry-run lowers.
* **federated simulation** (`make_fed_round`) — the paper-faithful
  N-client round: broadcast, h decoupled local steps (vmap over clients),
  smashed-data uploads every k steps, sequential SFLV2-style server
  updates, Fed-Server aggregation with partial participation/stragglers.

Both modes are model-agnostic through :class:`ModelAPI` (LM and CNN
adapters provided).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import aggregate as AG
from repro.core import zo as Z
from repro.core.split import combine, param_bytes, partition
from repro.kernels import ops as O
from repro.distributed.sharding import AxisRules
from repro.models import cnn as CNN
from repro.models import transformer as T
from repro.models.config import ModelConfig
from repro.optim.optimizers import Optimizer

METHODS = ("heron", "cse_fsl", "fsl_sage", "sflv1", "sflv2", "splitlora")


@dataclasses.dataclass(frozen=True)
class ModelAPI:
    """Adapter between a concrete model family and the SFL protocols."""
    client_loss: Callable   # (client_params, batch) -> (loss, smashed)
    aux_loss: Callable      # (client_params, smashed, batch) -> loss
    server_loss: Callable   # (server_params, client_const, smashed, batch) -> loss
    joint_loss: Callable    # (client_params, server_params, batch) -> loss
    # the ZO client's fused dual probe:
    # (client_params, batch, seeds_tree, mu) -> (l_clean, l_pert, smashed,
    # stats) — both ZO losses of one pair from a single dual-batch
    # forward; ``stats`` counts its work ({"moe_rows": ...} with a
    # dropless MoE, else {}).
    client_dual_loss: Callable
    # leaf-seed predicate the kernel estimator AND the server replay must
    # share (attn_probe="scores" excludes attention wk/wv — the probe
    # moves to the score field, which is never replayed; see
    # ops.attn_kv_seed_pred).  Must be a module-level function: the jit
    # caches keyed on it rely on a stable identity/hash.
    seed_pred: Callable | None = None


def forward_impl_of(cfg) -> str:
    """Resolve a model config's forward_impl to the dual-probe matmul
    backend: ``"kernel"`` picks it from the platform, ``"kernel_interpret"``
    runs the Pallas kernels in interpret mode (tests)."""
    fi = cfg.forward_impl
    if fi == "kernel":
        return O.default_forward_impl()
    if fi == "kernel_interpret":
        return "interpret"
    raise ValueError(f"forward_impl must be 'kernel' or "
                     f"'kernel_interpret', got {fi!r}")


def lm_api(cfg: ModelConfig, rules: AxisRules) -> ModelAPI:
    def client_loss(cp, batch):
        s, _ = T.client_forward(cp, cfg, rules, batch["inputs"],
                                batch.get("positions"))
        return aux_loss(cp, s, batch), s

    def aux_loss(cp, smashed, batch):
        with jax.named_scope("heron_aux_head"):
            logits, _ = T.aux_forward(cp, cfg, rules, smashed,
                                      batch.get("positions"))
            lbl = batch.get("aux_labels", batch["labels"])
            return T.lm_loss(logits, lbl, cfg.vocab)

    def server_loss(sp, cp_const, smashed, batch):
        logits, _ = T.server_forward(
            {"client": cp_const, "server": sp}, cfg, rules, smashed,
            positions=batch.get("positions"),
            dec_tokens=batch.get("dec_tokens"),
            dec_positions=batch.get("dec_positions"))
        return T.lm_loss(logits, batch["labels"], cfg.vocab)

    def joint_loss(cp, sp, batch):
        s, _ = T.client_forward(cp, cfg, rules, batch["inputs"],
                                batch.get("positions"))
        logits, _ = T.server_forward(
            {"client": cp, "server": sp}, cfg, rules, s,
            positions=batch.get("positions"),
            dec_tokens=batch.get("dec_tokens"),
            dec_positions=batch.get("dec_positions"))
        return T.lm_loss(logits, batch["labels"], cfg.vocab)

    impl = forward_impl_of(cfg)
    if impl == "pallas" and rules.partitioned:
        impl = "xla"    # the same stream, emulated: GSPMD can split it

    def client_dual_loss(cp, batch, seeds, mu):
        pz = O.Perturb(seeds=seeds, mu=mu, dual=True, impl=impl)
        pos = batch.get("positions")
        s2, ncs = T.client_forward(cp, cfg, rules, batch["inputs"],
                                   pos, perturb=pz)
        pos2 = None if pos is None else T.dual_positions(pos)
        B = batch["inputs"].shape[0]
        with jax.named_scope("heron_aux_head"):
            logits2, aux_ncs = T.aux_forward(cp, cfg, rules, s2, pos2,
                                             perturb=pz)
            lbl = batch.get("aux_labels", batch["labels"])
            l0 = T.lm_loss(logits2[:B], lbl, cfg.vocab)
            lp = T.lm_loss(logits2[B:], lbl, cfg.vocab)
        stats = {}
        if cfg.moe is not None and cfg.moe.capacity_factor is None:
            stats["moe_rows"] = T.moe_rows((ncs, aux_ncs))
        return l0, lp, s2[:B], stats

    seed_pred = None
    if cfg.attn_probe == "scores":
        seed_pred = O.attn_kv_seed_pred
    return ModelAPI(client_loss, aux_loss, server_loss, joint_loss,
                    client_dual_loss, seed_pred)


def cnn_api(cfg: CNN.CNNConfig) -> ModelAPI:
    def client_loss(cp, batch):
        s = CNN.client_forward(cp, batch["inputs"], cfg)
        return aux_loss(cp, s, batch), s

    def aux_loss(cp, smashed, batch):
        with jax.named_scope("heron_aux_head"):
            return CNN.xent(CNN.aux_logits(cp, smashed, cfg),
                            batch["labels"])

    def server_loss(sp, cp_const, smashed, batch):
        return CNN.xent(CNN.server_logits(sp, smashed, cfg),
                        batch["labels"])

    def joint_loss(cp, sp, batch):
        s = CNN.client_forward(cp, batch["inputs"], cfg)
        return CNN.xent(CNN.server_logits(sp, s, cfg), batch["labels"])

    impl = forward_impl_of(cfg)

    def client_dual_loss(cp, batch, seeds, mu):
        pz = O.Perturb(seeds=seeds, mu=mu, dual=True, impl=impl)
        s2 = CNN.client_forward(cp, batch["inputs"], cfg, pz)
        B = batch["inputs"].shape[0]
        with jax.named_scope("heron_aux_head"):
            logits2 = CNN.aux_logits(cp, s2, cfg, pz)
            l0 = CNN.xent(logits2[:B], batch["labels"])
            lp = CNN.xent(logits2[B:], batch["labels"])
        return l0, lp, s2[:B], {}

    return ModelAPI(client_loss, aux_loss, server_loss, joint_loss,
                    client_dual_loss)


# ===========================================================================
# datacenter hybrid step (what the dry-run lowers)
# ===========================================================================

def init_train_state(rng, params, client_opt: Optimizer,
                     server_opt: Optimizer, tc_pred=None, ts_pred=None):
    tc_pred = tc_pred or (lambda p: True)
    ts_pred = ts_pred or (lambda p: True)
    tc, _ = partition(params["client"], tc_pred)
    ts, _ = partition(params["server"], ts_pred)
    return {"params": params,
            "opt_client": client_opt.init(tc),
            "opt_server": server_opt.init(ts),
            "step": jnp.zeros((), jnp.int32),
            "rng": rng}


def make_train_step(api: ModelAPI, method: str, zo_cfg: Z.ZOConfig,
                    client_opt: Optimizer, server_opt: Optimizer,
                    tc_pred=None, ts_pred=None, align_weight: float = 1.0):
    """Returns step(state, batch) -> (state, metrics)."""
    assert method in METHODS, method
    tc_pred = tc_pred or (lambda p: True)
    ts_pred = ts_pred or (lambda p: True)

    def step_fn(state, batch):
        params = state["params"]
        key = jax.random.fold_in(state["rng"], state["step"])
        tc, fc = partition(params["client"], tc_pred)
        ts, fs = partition(params["server"], ts_pred)
        metrics: dict[str, Any] = {}

        if method in ("sflv1", "sflv2", "splitlora"):
            # end-to-end FO: the server's cut-layer gradient reaches the
            # client (training lock; 2pq communication per batch).
            def jloss(args):
                tcx, tsx = args
                return api.joint_loss(combine(tcx, fc),
                                      combine(tsx, fs), batch)

            loss, (g_c, g_s) = jax.value_and_grad(jloss)((tc, ts))
            metrics["loss"] = metrics["client_loss"] = loss
        else:
            def closs(tcx):
                return api.client_loss(combine(tcx, fc), batch)

            if method == "heron":
                # --- the paper's technique: forward-only ZO client ---
                # per-layer hash seeds, fused dual-probe forward (both
                # losses per weight read)
                def dloss(tcx, seeds, mu):
                    return api.client_dual_loss(combine(tcx, fc), batch,
                                                seeds, mu)

                g_c, info = Z.zo_gradient(dloss, tc, Z.seed_from_key(key),
                                          zo_cfg, seed_pred=api.seed_pred)
                c_loss, smashed = info["loss"], info["aux"]
                metrics["zo_coeff_abs"] = jnp.mean(
                    jnp.abs(info["coeffs"]))
            else:  # cse_fsl / fsl_sage: FO client via the aux head
                (c_loss, smashed), g_c = jax.value_and_grad(
                    closs, has_aux=True)(tc)
            smashed_sg = jax.lax.stop_gradient(smashed)
            cp_const = jax.lax.stop_gradient(params["client"])

            def sloss(tsx):
                return api.server_loss(combine(tsx, fs), cp_const,
                                       smashed_sg, batch)

            s_loss, g_s = jax.value_and_grad(sloss)(ts)
            if method == "fsl_sage":
                # align the aux head's cut-layer gradient with the
                # server's true cut-layer gradient (SAGE estimator).
                g_cut_srv = jax.lax.stop_gradient(jax.grad(
                    lambda s: api.server_loss(combine(ts, fs), cp_const,
                                              s, batch))(smashed_sg))

                def align(tcx):
                    g_cut_aux = jax.grad(
                        lambda s: api.aux_loss(combine(tcx, fc), s,
                                               batch))(smashed_sg)
                    return jnp.mean(jnp.square(
                        g_cut_aux.astype(jnp.float32)
                        - g_cut_srv.astype(jnp.float32)))

                g_align = jax.grad(align)(tc)
                g_c = jax.tree.map(
                    lambda a, b: a + align_weight * b, g_c, g_align)
            metrics["loss"] = s_loss
            metrics["client_loss"] = c_loss

        new_tc, oc = client_opt.update(g_c, state["opt_client"], tc)
        new_ts, os_ = server_opt.update(g_s, state["opt_server"], ts)
        new_state = {
            "params": {"client": combine(new_tc, fc),
                       "server": combine(new_ts, fs)},
            "opt_client": oc,
            "opt_server": os_,
            "step": state["step"] + 1,
            "rng": state["rng"],
        }
        return new_state, metrics

    return step_fn


# ===========================================================================
# inference steps (prefill / decode) — serving the assembled global model
# ===========================================================================

def make_prefill_step(cfg: ModelConfig, rules: AxisRules):
    def prefill(params, batch):
        logits = T.full_forward(params, cfg, rules, batch["inputs"],
                                batch.get("positions"),
                                batch.get("dec_tokens"))
        return logits

    return prefill


def make_cached_prefill_step(cfg: ModelConfig, rules: AxisRules):
    """Block prefill for serving: one forward over the whole prompt
    (``decode=False``) that *writes* the KV / recurrent caches, so decode
    continues at ``pos = prompt_len``.  Returns
    ``prefill(params, caches, tokens) -> (logits, caches)``; caches must
    be fresh (``init_serve_caches``, pos 0).  Decoder-only archs — the
    enc-dec decoder needs its cross-attended token loop."""
    from repro.models import layers as L

    if cfg.enc_dec:
        raise ValueError("cached block prefill is decoder-only; enc-dec "
                         "serving prefills token-by-token")

    def prefill(params, caches, tokens):
        x = T.embed_inputs(params["client"], cfg, tokens)
        x, cc = T.apply_stack(params["client"]["layers"], x, cfg, rules,
                              T.client_specs(cfg), caches=caches["client"],
                              decode=False)
        x, sc = T.apply_stack(params["server"]["layers"], x, cfg, rules,
                              T.server_specs(cfg), caches=caches["server"],
                              decode=False)
        x = T._norm(cfg, params["server"]["final_norm"], x)
        if cfg.tie_embeddings:
            logits = L.unembed(params["client"]["embed"], x, jnp.float32)
        else:
            logits = x.astype(jnp.float32) @ params["server"][
                "unembed"].astype(jnp.float32)
        return (L.softcap(logits, cfg.final_softcap),
                {"client": cc, "server": sc})

    return prefill


def init_serve_caches(cfg: ModelConfig, batch: int, seq: int,
                      per_slot: bool = False):
    """``per_slot=True`` lays the caches out for the fused decode engine
    (:mod:`repro.core.decode`): every KV cache carries a per-request
    ``pos`` vector instead of one scalar, so slots at different sequence
    positions coexist in one batch and finished slots can be recycled."""
    if cfg.enc_dec:
        return {
            "dec": T.init_stack_cache(cfg, T.decoder_specs(cfg), batch,
                                      seq),
            "enc_out": jnp.zeros((batch, seq, cfg.d_model),
                                 cfg.jnp_compute_dtype()),
        }
    return {
        "client": T.init_stack_cache(cfg, T.client_specs(cfg), batch, seq,
                                     per_slot),
        "server": T.init_stack_cache(cfg, T.server_specs(cfg), batch, seq,
                                     per_slot),
    }


def make_serve_step(cfg: ModelConfig, rules: AxisRules):
    """One decode step: (params, caches, token) -> (logits, caches)."""
    from repro.models import layers as L

    def serve(params, caches, token):
        if cfg.enc_dec:
            y = L.embed(params["server"]["dec_embed"], token,
                        cfg.jnp_compute_dtype())
            y, dec_c = T.apply_stack(
                params["server"]["decoder"], y, cfg, rules,
                T.decoder_specs(cfg), caches=caches["dec"], decode=True,
                enc_out=caches["enc_out"])
            y = T._norm(cfg, params["server"]["final_norm"], y)
            logits = L.unembed(params["client"]["embed"], y, jnp.float32)
            return (L.softcap(logits, cfg.final_softcap),
                    {"dec": dec_c, "enc_out": caches["enc_out"]})
        x = T.embed_inputs(params["client"], cfg, token)
        x, cc = T.apply_stack(params["client"]["layers"], x, cfg, rules,
                              T.client_specs(cfg), caches=caches["client"],
                              decode=True)
        x, sc = T.apply_stack(params["server"]["layers"], x, cfg, rules,
                              T.server_specs(cfg), caches=caches["server"],
                              decode=True)
        x = T._norm(cfg, params["server"]["final_norm"], x)
        if cfg.tie_embeddings:
            logits = L.unembed(params["client"]["embed"], x, jnp.float32)
        else:
            logits = x.astype(jnp.float32) @ params["server"][
                "unembed"].astype(jnp.float32)
        return (L.softcap(logits, cfg.final_softcap),
                {"client": cc, "server": sc})

    return serve


# ===========================================================================
# federated simulation (paper-faithful N-client rounds)
# ===========================================================================

@dataclasses.dataclass(frozen=True)
class FedConfig:
    n_clients: int = 5
    h: int = 4                    # local steps per round
    upload_every: int = 1         # k: smashed upload period
    participation: float = 1.0
    straggler_prob: float = 0.0
    sequential_server: bool = True
    quantize_uplink: bool = False  # int8 smashed-data upload (pq/2)


UPLINKS = ("dense", "seed_replay")


def seed_replay_uplink_bytes(n_clients: int, h: int, n_pairs: int) -> int:
    """Bytes on the wire for the lean uplink: per client one 64-bit PRNG
    key plus h·n_pairs fp32 projected-gradient coefficients."""
    return n_clients * (h * n_pairs * 4 + 8)


def _make_local_update(api: ModelAPI, method: str, zo_cfg: Z.ZOConfig,
                       client_opt: Optimizer, uplink: str, client_lr):
    """One client's local step — shared by the sync and async rounds."""
    def local_update(cp, oc, batch, seed):
        def closs(cpx):
            return api.client_loss(cpx, batch)

        stats = {}

        if method == "heron":
            def dloss(cpx, seeds, mu):
                return api.client_dual_loss(cpx, batch, seeds, mu)

            g, info = Z.zo_gradient(dloss, cp, seed, zo_cfg,
                                    seed_pred=api.seed_pred)
            stats = info["stats"]
            loss, smashed = info["loss"], info["aux"]
            coeffs = info["coeffs"]
            if uplink == "seed_replay":
                cp = Z.add_scaled(cp, g, -client_lr)
            else:
                cp, oc = client_opt.update(g, oc, cp)
        else:
            (loss, smashed), g = jax.value_and_grad(closs, has_aux=True)(cp)
            coeffs = jnp.zeros((zo_cfg.n_pairs,))
            cp, oc = client_opt.update(g, oc, cp)
        return cp, oc, smashed, loss, coeffs, stats

    return local_update


def _make_cohort_trajectory(api: ModelAPI, method: str, zo_cfg: Z.ZOConfig,
                            fed: FedConfig, client_opt: Optimizer,
                            uplink: str, client_lr):
    """The client side of a round: h decoupled local steps vmapped over
    the N-client cohort.  Factored out of :func:`make_fed_round` so the
    async engine (:func:`make_async_round`) reuses the *identical* jitted
    trajectory — same seed stream, same scan order — which is what makes
    the async path bit-exact against the sync one at zero staleness.

    Returns ``(run, method == "heron")`` where
    ``run(state_client, round_batch, key) ->
    (client_seeds, cps, smashed_all, losses, coeffs_all, stats)``, the
    clients' dual-probe counters summed over the cohort and its steps.
    The flag is kept only because the benchmark's fed-round module
    unpacks a pair.
    """
    local_update = _make_local_update(api, method, zo_cfg, client_opt,
                                      uplink, client_lr)

    def run(state_client, round_batch, key):
        with jax.named_scope("heron_cohort"):
            N, h = fed.n_clients, fed.h
            cp0 = jax.tree.map(
                lambda p: jnp.broadcast_to(p[None], (N,) + p.shape),
                state_client)
            oc0 = jax.vmap(client_opt.init)(cp0)
            # one base seed per client; local step m folds m on top and
            # zo_gradient folds the pair index on top of that — the same
            # (client, step, pair) stream seed_replay_aggregate re-derives.
            client_seeds = O.fold_seed(Z.seed_from_key(key), jnp.arange(N))

            def step_m(carry, m):
                cps, ocs = carry
                batch_m = jax.tree.map(lambda x: jnp.take(x, m, axis=1),
                                       round_batch)
                seeds = O.fold_seed(client_seeds, m)
                cps, ocs, smashed, losses, coeffs, stats = jax.vmap(
                    local_update, in_axes=(0, 0, 0, 0))(cps, ocs, batch_m,
                                                        seeds)
                return (cps, ocs), (smashed, losses, coeffs, stats)

            (cps, _), (smashed_all, losses, coeffs_all, stats) = \
                jax.lax.scan(step_m, (cp0, oc0), jnp.arange(h))
            stats = jax.tree.map(jnp.sum, stats)
            return client_seeds, cps, smashed_all, losses, coeffs_all, stats

    return run, method == "heron"


def _make_server_updates(api: ModelAPI, fed: FedConfig,
                         server_opt: Optimizer):
    """Sequential SFLV2-style server FO updates over a set of clients.

    ``apply(sp, os_, cp_const, round_batch, smashed_all, cids)`` runs,
    for every upload step m, one scan over the client ids in ``cids``
    (an int array — ``jnp.arange(N)`` reproduces the historical sync
    behavior; the async engine passes each flush's arrivals instead).
    """
    upload_ms = [m for m in range(fed.h) if m % fed.upload_every == 0]

    def apply(sp, os_, cp_const, round_batch, smashed_all, cids):
        with jax.named_scope("heron_server_fo"):
            s_losses = []
            for m in upload_ms:
                batch_m = jax.tree.map(lambda x: x[:, m], round_batch)
                smashed_m = jax.tree.map(lambda s: s[m], smashed_all)
                if fed.quantize_uplink:
                    from repro.core.split import (dequantize_smashed,
                                                  quantize_smashed)
                    qm, sc = quantize_smashed(smashed_m)
                    smashed_m = dequantize_smashed(qm, sc, smashed_m.dtype)

                def server_client_step(carry, i):
                    spx, osx = carry
                    sm = jax.tree.map(lambda s: jnp.take(s, i, axis=0),
                                      smashed_m)
                    bt = jax.tree.map(lambda x: jnp.take(x, i, axis=0),
                                      batch_m)
                    sl, g = jax.value_and_grad(
                        lambda p: api.server_loss(p, cp_const,
                                                  jax.lax.stop_gradient(sm),
                                                  bt))(spx)
                    spx, osx = server_opt.update(g, osx, spx)
                    return (spx, osx), sl

                (sp, os_), sls = jax.lax.scan(server_client_step, (sp, os_),
                                              cids)
                s_losses.append(sls)
            return sp, os_, s_losses

    return apply


def make_fed_round(api: ModelAPI, method: str, zo_cfg: Z.ZOConfig,
                   fed: FedConfig, client_opt: Optimizer,
                   server_opt: Optimizer, uplink: str = "dense",
                   client_lr: float | None = None,
                   replay_shard: str = "none", replay_mesh=None,
                   replay_chunk: int | None = None):
    """Returns round(state, round_batch, key) -> (state, metrics).

    state = {"client": global client params, "server": server params,
             "opt_server": ...}
    round_batch: pytree with leading (N, h, ...) dims; for enc-dec /
    aux-label tasks include the extra fields per ModelAPI.

    ``uplink`` selects the client->Fed-Server weight channel:

    * ``"dense"`` — clients upload their full local client params
      (O(d) floats each) and the Fed-Server runs masked FedAvg.
    * ``"seed_replay"`` — the paper's lean uplink (HERON only): client i
      uploads its round seed plus the (h, n_pairs) projected-gradient
      coefficients — O(h·n_pairs) floats — and the Fed-Server
      reconstructs the aggregate with the scan-vectorized
      :func:`repro.core.aggregate.seed_replay_aggregate`.  Clients step
      with plain SGD at ``client_lr`` (replay needs a linear, stateless
      optimizer); the result matches the dense path to first order in
      ``client_lr`` and exactly at ``h == 1``.

    Both modes report ``uplink_bytes`` / ``uplink_bytes_dense`` metrics
    so the O(d) -> O(h·n_pairs) reduction is observable per round.

    ``replay_shard``/``replay_mesh``/``replay_chunk`` configure the
    seed-replay reconstruction's execution (see
    :func:`repro.core.aggregate._replay_engine`): ``replay_shard``
    partitions the client axis over that mesh axis (e.g. ``"clients"``
    on a cohort mesh), ``replay_chunk`` streams the flattened
    (client, step, pair) stream in donated-buffer chunks.  Defaults
    reproduce the flat single-scan behavior bit-for-bit.
    """
    assert method in METHODS
    assert uplink in UPLINKS, uplink
    if uplink == "seed_replay":
        if method != "heron":
            raise ValueError("seed_replay uplink requires the forward-only"
                             f" ZO client (method='heron'), got {method!r}")
        if client_lr is None:
            raise ValueError("seed_replay uplink needs client_lr: the "
                             "Fed-Server replays plain-SGD local steps")
    run_cohort, _ = _make_cohort_trajectory(
        api, method, zo_cfg, fed, client_opt, uplink, client_lr)
    server_updates = _make_server_updates(api, fed, server_opt)

    def round_fn(state, round_batch, key):
        N, h = fed.n_clients, fed.h
        if method in ("sflv1", "sflv2", "splitlora"):
            return _fo_locked_round(api, method, fed, client_opt,
                                    server_opt, state, round_batch, key)

        client_seeds, cps, smashed_all, losses, coeffs_all, stats = \
            run_cohort(state["client"], round_batch, key)
        cp_const = jax.lax.stop_gradient(state["client"])
        sp, os_, s_losses = server_updates(
            state["server"], state["opt_server"], cp_const, round_batch,
            smashed_all, jnp.arange(N))
        # Fed-Server aggregation with participation / stragglers
        mask = AG.straggler_mask(jax.random.fold_in(key, 777), N,
                                 fed.participation, fed.straggler_prob)
        dense_bytes = N * param_bytes(state["client"])
        if uplink == "seed_replay":
            # (h, N, n_pairs) -> (N, h, n_pairs): the per-client message
            coeffs_nhp = jnp.transpose(coeffs_all, (1, 0, 2))
            new_client = AG.seed_replay_aggregate(
                state["client"], client_seeds, coeffs_nhp, client_lr,
                mask, seed_pred=api.seed_pred, shard=replay_shard,
                mesh=replay_mesh, chunk=replay_chunk)
            lean_bytes = seed_replay_uplink_bytes(N, h, zo_cfg.n_pairs)
        else:
            new_client = AG.fedavg_masked(cps, mask, state["client"])
            lean_bytes = dense_bytes
        metrics = {"client_loss": jnp.mean(losses),
                   "server_loss": jnp.mean(jnp.stack(s_losses)),
                   "participants": jnp.sum(mask),
                   "uplink_bytes": jnp.asarray(lean_bytes, jnp.float32),
                   "uplink_bytes_dense": jnp.asarray(dense_bytes,
                                                     jnp.float32),
                   **stats}
        return ({"client": new_client, "server": sp, "opt_server": os_},
                metrics)

    return round_fn


def make_async_round(api: ModelAPI, method: str, zo_cfg: Z.ZOConfig,
                     fed: FedConfig, client_opt: Optimizer,
                     server_opt: Optimizer, client_lr: float,
                     staleness_alpha: float = 0.0, buffer_k: int = 0,
                     replay_shard: str = "none", replay_mesh=None,
                     replay_chunk: int | None = None):
    """Buffered-async federated round (FedBuff-style) over the lean
    seed-replay uplink.

    The client side is *literally* the synchronous trajectory — the same
    :func:`_make_cohort_trajectory` scan ``make_fed_round`` uses, so
    coefficients and smashed data are bit-identical — but the Fed-Server
    incorporates arrivals through
    :class:`repro.fed.async_engine.AsyncReplayServer`: completion order
    is the stable sort of per-client ``durations``, the buffer snapshots
    a new global every ``buffer_k`` arrivals, and every entry is
    staleness-weighted ``w(τ) = (1+τ)^(-α)`` with ``τ`` counted in
    snapshots taken since the client pulled its base model.

    ``buffer_k=0`` is the barrier limit — one flush holding the whole
    cohort — and is **bit-exact** against ``make_fed_round(uplink=
    "seed_replay")``: the flush re-derives the identical token/scale
    stream (shared :func:`repro.core.aggregate.replay_token_stream`) and
    the per-flush server FO updates run over the flushed clients in
    client-id order, matching the sync (upload-step, client) scan order.

    Returns ``round(state, round_batch, key, durations=None) ->
    (state, metrics)``.  ``durations`` is an optional (N,) array of
    per-client round times — e.g. :func:`repro.fed.cutplan.round_time_s`
    estimates for a heterogeneous fleet — driving arrival order and the
    simulated-time metrics (``sim_makespan_s``,
    ``time_to_first_update_s``, ``updates_per_sim_s``).  Heterogeneous
    *cuts* enter this simulation through those durations; the cohort
    math executes at the config's shared cut (per-client parameter
    shapes cannot share one vmapped trajectory).
    """
    from repro.fed.async_engine import AsyncReplayServer, StalenessConfig

    if method != "heron":
        raise ValueError("the async round rides the seed-replay uplink, "
                         "which needs the forward-only ZO client "
                         f"(method='heron'); got {method!r}")
    if client_lr is None:
        raise ValueError("async round needs client_lr: the Fed-Server "
                         "replays plain-SGD local steps")
    run_cohort, _ = _make_cohort_trajectory(
        api, method, zo_cfg, fed, client_opt, "seed_replay", client_lr)
    server_updates = _make_server_updates(api, fed, server_opt)

    def round_fn(state, round_batch, key, durations=None):
        N, h = fed.n_clients, fed.h
        client_seeds, cps, smashed_all, losses, coeffs_all, _ = run_cohort(
            state["client"], round_batch, key)
        coeffs_nhp = jnp.transpose(coeffs_all, (1, 0, 2))
        mask = AG.straggler_mask(jax.random.fold_in(key, 777), N,
                                 fed.participation, fed.straggler_prob)
        if durations is None:
            durations = np.ones((N,))
        durations = np.asarray(durations, np.float64)
        order = np.argsort(durations, kind="stable")

        sp, os_ = state["server"], state["opt_server"]
        s_losses = []
        cp_const = jax.lax.stop_gradient(state["client"])

        def on_flush(cids, t):
            nonlocal sp, os_
            sp, os_, sls = server_updates(
                sp, os_, cp_const, round_batch, smashed_all,
                jnp.asarray(cids, jnp.int32))
            s_losses.extend(sls)

        srv = AsyncReplayServer(
            state["client"], client_lr,
            staleness=StalenessConfig(alpha=staleness_alpha),
            buffer_k=buffer_k, shard=replay_shard, mesh=replay_mesh,
            chunk=replay_chunk, seed_pred=api.seed_pred,
            on_flush=on_flush)

        seeds_host = np.asarray(client_seeds)
        mask_host = np.asarray(mask)
        for cid in order:
            cid = int(cid)
            srv.submit(cid, seeds_host[cid], coeffs_nhp[cid],
                       base_version=0, mask=float(mask_host[cid]),
                       t_done=float(durations[cid]))
        srv.flush()

        tel = srv.telemetry
        makespan = float(durations.max()) if N else 0.0
        last_t = tel.flush_times[-1] if tel.flush_times else makespan
        metrics = {
            "client_loss": jnp.mean(losses),
            "server_loss": jnp.mean(jnp.concatenate(
                [jnp.reshape(s, (-1,)) for s in s_losses])),
            "participants": jnp.sum(mask),
            "uplink_bytes": jnp.asarray(
                seed_replay_uplink_bytes(N, h, zo_cfg.n_pairs),
                jnp.float32),
            "uplink_bytes_dense": jnp.asarray(
                N * param_bytes(state["client"]), jnp.float32),
            "flushes": float(tel.flushes),
            "mean_staleness": float(tel.mean_staleness),
            "sim_makespan_s": makespan,
            "time_to_first_update_s": float(
                tel.flush_times[0]) if tel.flush_times else makespan,
            "updates_per_sim_s": tel.flushes / max(last_t, 1e-9),
        }
        return ({"client": srv.params, "server": sp, "opt_server": os_},
                metrics)

    return round_fn


def _fo_locked_round(api, method, fed, client_opt, server_opt, state,
                     round_batch, key):
    """SFLV1/V2 (and SplitLoRA): no aux net — the client waits for the
    server's cut-layer gradient (training lock).  Clients are processed
    sequentially against the shared server model (SFLV2) or per-client
    server replicas aggregated at round end (SFLV1)."""
    N, h = fed.n_clients, fed.h
    v1 = method == "sflv1"

    def client_loop(carry, i):
        sp, os_ = carry
        cp = state["client"]
        oc = client_opt.init(cp)

        def step_m(c2, m):
            cpx, ocx, spx, osx = c2
            bt = jax.tree.map(lambda x: jnp.take(jnp.take(x, i, axis=0),
                                                 m, axis=0), round_batch)
            (loss, (g_c, g_s)) = jax.value_and_grad(
                lambda args: api.joint_loss(args[0], args[1], bt))(
                    (cpx, spx))
            cpx, ocx = client_opt.update(g_c, ocx, cpx)
            spx, osx = server_opt.update(g_s, osx, spx)
            return (cpx, ocx, spx, osx), loss

        (cp, oc, sp, os_), losses = jax.lax.scan(
            step_m, (cp, oc, sp, os_), jnp.arange(h))
        return (sp, os_), (cp, losses)

    if v1:
        # independent server replicas per client, averaged afterwards
        def one_client(i):
            (sp_i, _), (cp_i, losses) = client_loop(
                (state["server"], state["opt_server"]), i)
            return cp_i, sp_i, losses

        cps, sps, losses = jax.vmap(one_client)(jnp.arange(N))
        sp = AG.fedavg(sps)
        os_ = state["opt_server"]
    else:
        (sp, os_), (cps, losses) = jax.lax.scan(
            client_loop, (state["server"], state["opt_server"]),
            jnp.arange(N))
    mask = AG.straggler_mask(jax.random.fold_in(key, 777), N,
                             fed.participation, fed.straggler_prob)
    new_client = AG.fedavg_masked(cps, mask, state["client"])
    dense_bytes = jnp.asarray(N * param_bytes(state["client"]),
                              jnp.float32)
    metrics = {"client_loss": jnp.mean(losses),
               "server_loss": jnp.mean(losses),
               "participants": jnp.sum(mask),
               "uplink_bytes": dense_bytes,
               "uplink_bytes_dense": dense_bytes}
    return ({"client": new_client, "server": sp, "opt_server": os_},
            metrics)
