"""Datacenter training driver: elastic mesh, checkpoint/restart, the
hybrid HERON step (or any baseline method) on real devices.

Example (CPU, reduced config):
  PYTHONPATH=src python -m repro.launch.train --arch qwen2-1.5b --smoke \
      --steps 20 --batch 8 --seq 64 --ckpt-dir /tmp/ckpt

Federated simulation with the lean seed-replay uplink (clients upload
(seed, coeff) pairs — O(h*n_pairs) floats — instead of O(d) params):
  PYTHONPATH=src python -m repro.launch.train --arch qwen2-1.5b --smoke \
      --fed --clients 4 --local-steps 2 --uplink seed_replay --steps 5
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.checkpoint import checkpoint as CKPT
from repro.configs.registry import ARCH_IDS, get_config
from repro.core import protocols as P
from repro.core import zo as Z
from repro.data.pipeline import place_batch
from repro.data.synthetic import BigramLM
from repro.distributed.sharding import AxisRules, DATA_AXES
from repro.launch import compile_cache
from repro.launch.mesh import make_local_mesh, make_replay_mesh
from repro.models import transformer as T
from repro.optim.optimizers import make_optimizer
from repro.optim.schedules import warmup_cosine


def build_batch(cfg, ds, key, batch, seq):
    b = ds.batch(key, batch)
    if cfg.enc_dec:
        emb = jax.random.normal(key, (batch, seq - 1, cfg.d_model),
                                jnp.float32).astype(cfg.jnp_compute_dtype())
        return {"inputs": emb, "aux_labels": b["labels"],
                "dec_tokens": b["inputs"], "labels": b["labels"]}
    if cfg.frontend == "vision":
        emb = jax.random.normal(key, (batch, seq - 1, cfg.d_model),
                                jnp.float32).astype(cfg.jnp_compute_dtype())
        pos = jnp.broadcast_to(jnp.arange(seq - 1)[None, None],
                               (3, batch, seq - 1)).astype(jnp.int32)
        return {"inputs": emb, "positions": pos, "labels": b["labels"]}
    if cfg.frontend == "audio":
        emb = jax.random.normal(key, (batch, seq - 1, cfg.d_model),
                                jnp.float32).astype(cfg.jnp_compute_dtype())
        return {"inputs": emb, "labels": b["labels"]}
    return b


def run_fed(args, cfg, api):
    """N-client federated simulation rounds (make_fed_round) with the
    dense or lean seed-replay uplink; reports per-round uplink bytes."""
    from repro.data.pipeline import round_batches

    if cfg.enc_dec or cfg.frontend is not None:
        raise SystemExit("--fed supports decoder-only text archs")
    copt = make_optimizer("zo_sgd" if args.method == "heron" else "adamw",
                          args.lr_client)
    sopt = make_optimizer("adamw", args.lr_server)
    fed = P.FedConfig(n_clients=args.clients, h=args.local_steps,
                      participation=args.participation)
    replay_mesh = (make_replay_mesh() if args.replay_shard != "none"
                   else None)
    zo_cfg = Z.ZOConfig(mu=args.zo_mu, n_pairs=args.zo_pairs)
    ds = BigramLM(vocab=cfg.vocab, seq_len=args.seq, seed=0)
    durations = None
    if args.fed_async:
        if args.method != "heron":
            raise SystemExit("--fed-async rides the seed-replay uplink "
                             "and requires --method heron")
        round_fn = P.make_async_round(
            api, args.method, zo_cfg, fed, copt, sopt,
            client_lr=args.lr_client, staleness_alpha=args.staleness,
            buffer_k=args.buffer_k, replay_shard=args.replay_shard,
            replay_mesh=replay_mesh, replay_chunk=args.replay_chunk)
        if args.cutplan:
            from repro.fed import cutplan as CP
            costs = CP.candidate_costs(cfg,
                                       ds.batch(jax.random.PRNGKey(2),
                                                args.batch),
                                       rules=AxisRules(mesh=None))
            tiers = list(CP.PROFILES.values())
            profiles = [tiers[i % len(tiers)] for i in
                        range(args.clients)]
            plans = CP.plan_fleet(costs, profiles, fed.h, zo_cfg.n_pairs)
            durations = [p.round_s for p in plans]
            for i, (prof, plan) in enumerate(zip(profiles, plans)):
                print(f"[cutplan] client {i}: {prof.name:8s} "
                      f"cut={plan.cut} est_round={plan.round_s:.3g}s "
                      f"feasible={plan.feasible}")
    else:
        round_fn = jax.jit(P.make_fed_round(
            api, args.method, zo_cfg, fed, copt, sopt,
            uplink=args.uplink, client_lr=args.lr_client,
            replay_shard=args.replay_shard, replay_mesh=replay_mesh,
            replay_chunk=args.replay_chunk))
    params = T.init_lm(jax.random.PRNGKey(0), cfg)
    state = {"client": params["client"], "server": params["server"],
             "opt_server": sopt.init(params["server"])}
    t0 = time.time()
    for r in range(args.steps):
        rb = round_batches(ds, jax.random.fold_in(jax.random.PRNGKey(5),
                                                  r),
                           args.clients, args.local_steps, args.batch)
        key_r = jax.random.fold_in(jax.random.PRNGKey(9), r)
        if args.fed_async:
            state, m = round_fn(state, rb, key_r, durations=durations)
            extra = (f"flushes={int(m['flushes'])} "
                     f"staleness={m['mean_staleness']:.2f} "
                     f"upd/s={m['updates_per_sim_s']:.3g} ")
        else:
            state, m = round_fn(state, rb, key_r)
            extra = ""
        print(f"[fed] round {r:3d} "
              f"client_loss={float(m['client_loss']):.4f} "
              f"server_loss={float(m['server_loss']):.4f} "
              f"uplink={'seed_replay' if args.fed_async else args.uplink} "
              f"bytes/round={float(m['uplink_bytes']):.3g} "
              f"(dense={float(m['uplink_bytes_dense']):.3g}) {extra}"
              f"({time.time()-t0:.1f}s)")
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-1.5b", choices=list(ARCH_IDS))
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--method", default="heron", choices=list(P.METHODS))
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr-client", type=float, default=1e-3)
    ap.add_argument("--lr-server", type=float, default=1e-3)
    ap.add_argument("--zo-mu", type=float, default=1e-3)
    ap.add_argument("--zo-pairs", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--fed", action="store_true",
                    help="paper-faithful N-client federated simulation "
                         "(--steps counts rounds)")
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--local-steps", type=int, default=2)
    ap.add_argument("--participation", type=float, default=1.0)
    ap.add_argument("--uplink", default="dense", choices=list(P.UPLINKS),
                    help="client->Fed-Server weight channel "
                         "(seed_replay = lean (seed, coeff) uplink)")
    ap.add_argument("--replay-shard", default="none",
                    choices=["none", "clients"],
                    help="partition seed-replay reconstruction over a "
                         "1-D cohort mesh of all local devices")
    ap.add_argument("--replay-chunk", type=int, default=None,
                    help="stream the replay in donated-buffer chunks of "
                         "this many (client, step, pair) entries per "
                         "device — O(d) server memory for huge cohorts")
    ap.add_argument("--fed-async", action="store_true",
                    help="buffered-async round engine: seed-replay "
                         "arrivals are applied as they land, weighted by "
                         "staleness (implies --fed, requires heron)")
    ap.add_argument("--staleness", type=float, default=0.0,
                    help="staleness-decay exponent alpha in "
                         "w(tau) = (1+tau)^-alpha (0 = no decay)")
    ap.add_argument("--buffer-k", type=int, default=0,
                    help="snapshot a new global every K async arrivals "
                         "(0 = one flush per full cohort)")
    ap.add_argument("--cutplan", action="store_true",
                    help="pick per-client cut layers from device "
                         "profiles (HLO costs + roofline) and use the "
                         "estimated round times as async arrival order")
    args = ap.parse_args(argv)

    compile_cache.enable()
    cfg = get_config(args.arch, smoke=args.smoke)
    mesh = make_local_mesh(args.model_parallel) if jax.device_count() > 1 \
        else None
    rules = AxisRules(mesh=mesh, enable_fsdp=False)
    api = P.lm_api(cfg, rules)
    if args.fed or args.fed_async:
        return run_fed(args, cfg, api)
    if args.uplink != "dense":
        raise SystemExit("--uplink seed_replay requires --fed (the lean "
                         "uplink is a federated-round mechanism)")
    c_name = "zo_sgd" if args.method == "heron" else "adamw"
    copt = make_optimizer(
        c_name, warmup_cosine(args.lr_client, 5, args.steps))
    sopt = make_optimizer(
        cfg.optimizer if cfg.optimizer != "adafactor" or not args.smoke
        else "adamw",
        warmup_cosine(args.lr_server, 5, args.steps))

    params = T.init_lm(jax.random.PRNGKey(0), cfg)
    state = P.init_train_state(jax.random.PRNGKey(1), params, copt, sopt)
    start = 0
    if args.ckpt_dir and CKPT.latest_step(args.ckpt_dir) is not None:
        state, start = CKPT.restore(args.ckpt_dir, state)
        print(f"[train] restored checkpoint at step {start}")
    step_fn = jax.jit(P.make_train_step(
        api, args.method, Z.ZOConfig(mu=args.zo_mu, n_pairs=args.zo_pairs),
        copt, sopt), donate_argnums=0)

    ds = BigramLM(vocab=cfg.vocab, seq_len=args.seq, seed=0)
    key = jax.random.PRNGKey(7)
    t0 = time.time()
    for step in range(start, args.steps):
        batch = build_batch(cfg, ds, jax.random.fold_in(key, step),
                            args.batch, args.seq)
        batch = place_batch(batch, rules)
        state, metrics = step_fn(state, batch)
        if step % 5 == 0 or step == args.steps - 1:
            m = {k: float(v) for k, v in metrics.items()}
            print(f"[train] step {step:4d} loss={m.get('loss', 0):.4f} "
                  f"client_loss={m.get('client_loss', 0):.4f} "
                  f"({time.time()-t0:.1f}s)")
        if args.ckpt_dir and (step + 1) % args.ckpt_every == 0:
            CKPT.save(args.ckpt_dir, step + 1, state)
    if args.ckpt_dir:
        CKPT.save(args.ckpt_dir, args.steps, state)
        print(f"[train] final checkpoint at {args.ckpt_dir}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
