"""Elastic heterogeneous fleet on the buffered-async round engine.

Phones, laptops and an edge TPU join a federated CNN run: the cut
planner (repro.fed.cutplan) picks each device's cut layer from its
compute/memory profile, the event-driven controller (repro.fed.
controller) dispatches local ZO rounds and feeds completions into the
buffered-async Fed-Server (repro.fed.async_engine), which snapshots a
new global every K arrivals with staleness-weighted seed replay.
Mid-run a phone drops out (its in-flight result is discarded), a new
laptop is admitted (the mesh re-forms), and an injected fault drill
exercises the bounded-backoff retry path.

PYTHONPATH=src python examples/fleet_async.py
PYTHONPATH=src python examples/fleet_async.py --buffer-k 3 \
    --staleness 0.5 --completions 40
"""
import argparse

import jax
import jax.numpy as jnp

from repro.core import protocols as P
from repro.core import zo as Z
from repro.data.synthetic import GaussianMixtureImages
from repro.distributed import fault as F
from repro.fed import (AsyncReplayServer, FleetController, StalenessConfig,
                       candidate_costs, plan_cut)
from repro.fed.cutplan import PROFILES
from repro.kernels import ops as O
from repro.models import cnn as CNN


def make_local_fn(api, ds, zo, h, client_lr, batch):
    """One client's local round as a pure function of
    (global_params, cid, round_idx, base_version) -> (token, coeffs,
    mask) — pure so a fault-triggered retry replays exactly."""

    @jax.jit
    def local_round(cp, seed, batches):
        def step_m(cp, xs):
            m, bm = xs
            g, info = Z.zo_gradient(
                lambda p, seeds, mu: api.client_dual_loss(p, bm, seeds, mu),
                cp, O.fold_seed(seed, m), zo, seed_pred=api.seed_pred)
            return Z.add_scaled(cp, g, -client_lr), \
                (info["coeffs"], info["loss"])

        _, (coeffs, losses) = jax.lax.scan(
            step_m, cp, (jnp.arange(h), batches))
        return coeffs, losses

    def local_fn(global_params, cid, round_idx, base_version):
        ck = jax.random.fold_in(
            jax.random.fold_in(jax.random.PRNGKey(11), round_idx), cid)
        bk = jax.random.fold_in(ck, 999)
        batches = jax.tree.map(
            lambda *xs: jnp.stack(xs),
            *[ds.batch(jax.random.fold_in(bk, m), batch)
              for m in range(h)])
        seed = Z.seed_from_key(ck)       # the lean uplink: (seed, coeffs)
        coeffs, losses = local_round(global_params, seed, batches)
        return seed, coeffs, 1.0

    return local_fn


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--completions", type=int, default=24,
                    help="client-round completions to process")
    ap.add_argument("--local-steps", type=int, default=2)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--pairs", type=int, default=2)
    ap.add_argument("--mu", type=float, default=1e-3)
    ap.add_argument("--lr-client", type=float, default=2e-2)
    ap.add_argument("--buffer-k", type=int, default=2)
    ap.add_argument("--staleness", type=float, default=0.5)
    args = ap.parse_args()

    cfg = CNN.CNNConfig(widths=(16, 32), blocks_per_stage=1, classes=10,
                        client_blocks=1)
    ds = GaussianMixtureImages(classes=10, hw=16, noise=0.8)
    api = P.cnn_api(cfg)
    zo = Z.ZOConfig(mu=args.mu, n_pairs=args.pairs)
    h = args.local_steps

    # --- profile-driven cut planning (admission-time, per device) ----
    costs = candidate_costs(cfg, ds.batch(jax.random.PRNGKey(2),
                                          args.batch))
    fleet0 = [PROFILES["phone"], PROFILES["phone"], PROFILES["laptop"],
              PROFILES["edge_tpu"]]
    plans = [plan_cut(costs, p, h, args.pairs) for p in fleet0]
    for p, pl in zip(fleet0, plans):
        print(f"[plan] {p.name:8s} cut={pl.cut} "
              f"est_round={pl.round_s:.3g}s feasible={pl.feasible}")
    # NOTE: the executed split stays at cfg.client_blocks — planned cuts
    # shape the *durations* (who arrives when), the honest simulation
    # contract documented in core/protocols.make_async_round.

    # --- buffered-async Fed-Server over the global client tree -------
    params = CNN.init_cnn(jax.random.PRNGKey(0), cfg)
    server = AsyncReplayServer(
        params["client"], args.lr_client,
        staleness=StalenessConfig(alpha=args.staleness),
        buffer_k=args.buffer_k, seed_pred=api.seed_pred)

    local_fn = make_local_fn(api, ds, zo, h, args.lr_client, args.batch)
    ctl = FleetController(
        server, local_fn,
        injector=F.FaultInjector(fail_at=(3,)),     # drill: one fault
        sleep=lambda s: None,
        remesh_fn=lambda n: F.remesh(1))

    held = ds.batch(jax.random.PRNGKey(12345), 256)
    loss0 = float(api.client_loss(server.params, held)[0])

    for p, pl in zip(fleet0, plans):
        ctl.admit(p, pl)
    half = args.completions // 2
    ctl.run(half)
    print(f"[fleet] t={ctl.now:.3g}s version={server.version} "
          f"after {half} completions")

    ctl.drop(0)                              # a phone leaves mid-round
    ctl.admit(PROFILES["laptop"], plan_cut(costs, PROFILES["laptop"], h,
                                           args.pairs))
    ctl.run(args.completions - half)
    server.flush()

    loss1 = float(api.client_loss(server.params, held)[0])
    t, s = ctl.telemetry, server.telemetry
    print(f"[fleet] admitted={t.admitted} dropped={t.dropped} "
          f"completed={t.completed} discarded={t.discarded} "
          f"restarts={t.restarts} remeshes={t.remeshes}")
    print(f"[async] flushes={s.flushes} arrivals={s.arrivals} "
          f"mean_staleness={s.mean_staleness:.2f} "
          f"version={server.version}")
    print(f"[loss ] held-out client loss {loss0:.4f} -> {loss1:.4f}")


if __name__ == "__main__":
    main()
