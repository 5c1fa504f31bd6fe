"""Benchmark harness — one function per paper table/figure.
Prints ``name,us_per_call,derived`` CSV rows.

  table1   — client-side resource formulas (Table I), analytic, at the
             paper's ResNet-18 and GPT2-Medium splits.
  table2   — measured client-update costs for the vision task (Table II
             in miniature): wall time, FLOPs (scan-aware HLO count) and
             peak temp memory per method.
  table3   — measured client-update costs for LM+LoRA (Table III).
  fig2     — convergence: accuracy after fixed federated rounds,
             HERON vs CSE-FSL vs SFLV2 (IID and non-IID).
  fig4     — ZO hyperparameter ablation: mu sweep + n_pairs sweep.
  fig6     — aux-model complexity ablation: HERON flat, FO needs capacity.
  seed_replay — the lean uplink: dense vs (seed, coeff) bytes on the
             wire, scan vs loop reconstruction wall-clock, and the
             end-to-end federated round in both uplink modes.
  serve    — sustained decode tok/s: fused single-jit engine (paged KV
             slots, continuous batching) vs the eager per-token serve
             loop, mixed-length queue on a GPT-2-class config.
  kernels  — wall-clock of the XLA hot paths + Pallas interpret sanity.

Each bench also writes a machine-readable ``benchmarks/BENCH_<name>.json``
(rows + git rev + backend) for CI artifacts and cross-revision diffs.

Run all:          PYTHONPATH=src python benchmarks/run.py
Run a subset:     PYTHONPATH=src python benchmarks/run.py seed_replay
"""
from __future__ import annotations

import json
import os
import time
import traceback

import jax
import jax.numpy as jnp

ROWS = []


def row(name, us, derived):
    ROWS.append((name, us, derived))
    print(f"{name},{us:.1f},{derived}", flush=True)


def timeit(fn, *args, n=5, warmup=2):
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(n):
        out = jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / n * 1e6, out


# ---------------------------------------------------------------------------
def bench_table1():
    from repro.core.split import client_costs
    # paper splits: ResNet-18 (client = stem + 1 block, aux = FC) and
    # GPT2-Medium (client = 6 blocks, aux = 3 blocks + unembed)
    settings = {
        "resnet18": dict(p_batch_bytes=256 * 32 * 32 * 3 * 4,
                         q_smashed_bytes=256 * 16 * 16 * 64 * 4,
                         client_params=160_000, aux_params=5_130,
                         f_c=2 * 0.9e9, f_a=2 * 1.3e4),
        "gpt2-medium": dict(p_batch_bytes=8 * 512 * 4,
                            q_smashed_bytes=8 * 512 * 1024 * 4,
                            client_params=85e6, aux_params=55e6,
                            f_c=2 * 0.9e12, f_a=2 * 0.6e12),
    }
    for scale, kw in settings.items():
        base = client_costs("cse_fsl", **kw)
        for m in ("sflv2", "cse_fsl", "fsl_sage", "heron"):
            c = client_costs(m, **kw)
            mem_save = 1 - c["peak_mem_bytes"] / base["peak_mem_bytes"]
            flop_save = 1 - c["flops"] / base["flops"]
            row(f"table1/{scale}/{m}", 0.0,
                f"comm={c['comm_bytes']:.3g}B "
                f"mem_save_vs_cse={mem_save:.2f} "
                f"flop_save_vs_cse={flop_save:.2f}")


# ---------------------------------------------------------------------------
def _client_update_costs(method):
    """Measured per-client-update costs on the vision task."""
    from repro.core import protocols as P
    from repro.core import zo as Z
    from repro.launch.hlo_costs import total_costs
    from repro.models import cnn as CNN
    from repro.optim.optimizers import make_optimizer

    zo_method = method in ("heron", "heron_kernel")
    cfg = CNN.CNNConfig(widths=(16, 32), blocks_per_stage=1, classes=10,
                        client_blocks=1,
                        forward_impl=("kernel" if method == "heron_kernel"
                                      else "xla"))
    params = CNN.init_cnn(jax.random.PRNGKey(0), cfg)
    api = P.cnn_api(cfg)
    opt = make_optimizer("zo_sgd" if zo_method else "adamw", 1e-3)
    x = jax.random.normal(jax.random.PRNGKey(1), (32, 16, 16, 3))
    y = jax.random.randint(jax.random.PRNGKey(2), (32,), 0, 10)
    batch = {"inputs": x, "labels": y}
    oc = opt.init(params["client"])

    if method == "heron_kernel":
        def update(cp, oc):
            g, info = Z.zo_gradient_kernel(
                lambda p, seeds, mu: api.client_dual_loss(p, batch, seeds,
                                                          mu),
                cp, jnp.int32(3), Z.ZOConfig(mu=1e-3, n_pairs=1))
            cp, oc = opt.update(g, oc, cp)
            return cp, oc
    elif method == "heron":
        def update(cp, oc):
            g, info = Z.zo_gradient(
                lambda p: api.client_loss(p, batch), cp,
                jax.random.PRNGKey(3), Z.ZOConfig(mu=1e-3, n_pairs=1))
            cp, oc = opt.update(g, oc, cp)
            return cp, oc
    else:
        def update(cp, oc):
            (_, _), g = jax.value_and_grad(
                lambda p: api.client_loss(p, batch), has_aux=True)(cp)
            cp, oc = opt.update(g, oc, cp)
            return cp, oc

    jitted = jax.jit(update)
    us, _ = timeit(jitted, params["client"], oc, n=3)
    comp = jitted.lower(params["client"], oc).compile()
    costs = total_costs(comp.as_text())
    mem = comp.memory_analysis()
    return us, costs["flops"], int(mem.temp_size_in_bytes)


def bench_table2():
    base = None
    stats = {}
    for m in ("sflv2", "cse_fsl", "heron", "heron_kernel"):
        us, fl, mem = _client_update_costs(m)
        stats[m] = (us, fl, mem)
        row(f"table2/resnet_client_update/{m}", us,
            f"flops={fl:.3g} temp_mem={mem}")
    row("table2/heron_vs_cse_flops_ratio", 0.0,
        f"{stats['heron'][1] / stats['cse_fsl'][1]:.3f} (paper: ~0.67)")
    row("table2/heron_vs_cse_mem_ratio", 0.0,
        f"{stats['heron'][2] / stats['cse_fsl'][2]:.3f} (paper: ~0.36)")
    # flops/mem of the kernel path are interpret-mode artifacts off-TPU
    # (the grid loop unrolls into HLO), so compare wall clock only
    row("table2/heron_kernel_vs_heron_time_ratio", 0.0,
        f"{stats['heron_kernel'][0] / stats['heron'][0]:.3f} "
        "(interpret-mode CPU proxy; fused dual probe halves W reads on "
        "TPU)")


# ---------------------------------------------------------------------------
def bench_table3():
    from repro.configs.gpt2 import gpt2_tiny
    from repro.core import protocols as P
    from repro.core import zo as Z
    from repro.core.split import combine, partition
    from repro.data.synthetic import BigramLM
    from repro.distributed.sharding import AxisRules
    from repro.launch.hlo_costs import total_costs
    from repro.models import lora as LoRA
    from repro.models import transformer as T

    cfg = gpt2_tiny()
    rules = AxisRules(mesh=None)
    params = LoRA.add_lora(jax.random.PRNGKey(2),
                           T.init_lm(jax.random.PRNGKey(0), cfg), rank=8)
    api = P.lm_api(cfg, rules)
    ds = BigramLM(vocab=cfg.vocab, seq_len=33, seed=0)
    batch = ds.batch(jax.random.PRNGKey(5), 8)
    tc, fc = partition(params["client"], LoRA.lora_pred)

    def heron_update(tc):
        g, _ = Z.zo_gradient(
            lambda t: api.client_loss(combine(t, fc), batch), tc,
            jax.random.PRNGKey(3), Z.ZOConfig(mu=1e-3, n_pairs=1))
        return g

    import dataclasses
    api_k = P.lm_api(dataclasses.replace(cfg, forward_impl="kernel"),
                     rules)

    def heron_kernel_update(tc):
        g, _ = Z.zo_gradient_kernel(
            lambda t, seeds, mu: api_k.client_dual_loss(
                combine(t, fc), batch, seeds, mu),
            tc, jnp.int32(3), Z.ZOConfig(mu=1e-3, n_pairs=1))
        return g

    def fo_update(tc):
        (_, _), g = jax.value_and_grad(
            lambda t: api.client_loss(combine(t, fc), batch),
            has_aux=True)(tc)
        return g

    stats = {}
    for name, fn in (("heron", heron_update),
                     ("heron_kernel", heron_kernel_update),
                     ("splitlora_fo", fo_update)):
        jitted = jax.jit(fn)
        us, _ = timeit(jitted, tc, n=3)
        comp = jitted.lower(tc).compile()
        costs = total_costs(comp.as_text())
        mem = comp.memory_analysis()
        stats[name] = (costs["flops"], int(mem.temp_size_in_bytes))
        row(f"table3/gpt2_lora_client_update/{name}", us,
            f"flops={costs['flops']:.3g} "
            f"temp_mem={mem.temp_size_in_bytes}")
    row("table3/heron_vs_fo_flops_ratio", 0.0,
        f"{stats['heron'][0] / stats['splitlora_fo'][0]:.3f} "
        "(paper: ~0.56-0.67)")
    row("table3/heron_vs_fo_mem_ratio", 0.0,
        f"{stats['heron'][1] / stats['splitlora_fo'][1]:.3f}")
    row("table3/heron_kernel_vs_heron_flops_ratio", 0.0,
        f"{stats['heron_kernel'][0] / stats['heron'][0]:.3f} "
        "(fused dual probe: 2 losses per weight read)")


# ---------------------------------------------------------------------------
def _fed_accuracy(method, alpha=0.0, rounds=10):
    from repro.core import protocols as P
    from repro.core import zo as Z
    from repro.data.partition import dirichlet_client_probs
    from repro.data.pipeline import round_batches
    from repro.data.synthetic import GaussianMixtureImages
    from repro.models import cnn as CNN
    from repro.optim.optimizers import make_optimizer

    cfg = CNN.CNNConfig(widths=(8, 16), blocks_per_stage=1, classes=4,
                        client_blocks=1)
    ds = GaussianMixtureImages(classes=4, hw=8, noise=0.5)
    probs = dirichlet_client_probs(3, 4, alpha) if alpha > 0 else None
    api = P.cnn_api(cfg)
    fed = P.FedConfig(n_clients=3, h=2)
    copt = make_optimizer("zo_sgd" if method == "heron" else "adamw",
                          2e-2 if method == "heron" else 2e-3)
    sopt = make_optimizer("adamw", 2e-3)
    rnd = jax.jit(P.make_fed_round(api, method,
                                   Z.ZOConfig(mu=1e-3, n_pairs=2), fed,
                                   copt, sopt))
    params = CNN.init_cnn(jax.random.PRNGKey(0), cfg)
    state = {"client": params["client"], "server": params["server"],
             "opt_server": sopt.init(params["server"])}
    t0 = time.perf_counter()
    for r in range(rounds):
        rb = round_batches(ds, jax.random.PRNGKey(r), 3, 2, 16,
                           client_probs=probs)
        state, _ = rnd(state, rb, jax.random.PRNGKey(1000 + r))
    dt = (time.perf_counter() - t0) / rounds * 1e6
    eb = ds.batch(jax.random.PRNGKey(9999), 256)
    s = CNN.client_forward(state["client"], eb["inputs"], cfg)
    logits = CNN.server_logits(state["server"], s, cfg)
    return dt, float(CNN.accuracy(logits, eb["labels"]))


def bench_fig2():
    for alpha, tag in ((0.0, "iid"), (0.3, "noniid_a0.3")):
        for m in ("heron", "cse_fsl", "sflv2"):
            us, acc = _fed_accuracy(m, alpha)
            row(f"fig2/{tag}/{m}", us, f"acc_after_10_rounds={acc:.3f}")


def bench_fig4():
    from repro.core import protocols as P
    from repro.core import zo as Z
    from repro.data.synthetic import BigramLM
    from repro.distributed.sharding import AxisRules
    from repro.models import transformer as T
    from repro.models.config import ModelConfig
    from repro.optim.optimizers import make_optimizer
    cfg = ModelConfig(name="t", n_layers=2, d_model=32, n_heads=4,
                      n_kv_heads=2, d_ff=64, vocab=31, cut_layers=1,
                      param_dtype="float32", compute_dtype="float32")
    rules = AxisRules(mesh=None)
    api = P.lm_api(cfg, rules)
    ds = BigramLM(vocab=31, seq_len=17, seed=0)

    def run(mu, pairs):
        params = T.init_lm(jax.random.PRNGKey(0), cfg)
        copt = make_optimizer("zo_sgd", 5e-3)
        sopt = make_optimizer("adamw", 2e-3)
        st = P.init_train_state(jax.random.PRNGKey(1), params, copt,
                                sopt)
        step = jax.jit(P.make_train_step(
            api, "heron", Z.ZOConfig(mu=mu, n_pairs=pairs), copt, sopt))
        t0 = time.perf_counter()
        m = {}
        for i in range(25):
            st, m = step(st, ds.batch(jax.random.PRNGKey(100 + i), 16))
        return (time.perf_counter() - t0) / 25 * 1e6, float(m["loss"])

    for mu in (1e-2, 1e-3, 1e-4):
        us, loss = run(mu, 2)
        row(f"fig4/mu_{mu:g}", us, f"loss_after_25_steps={loss:.4f}")
    for pairs in (1, 2, 4):
        us, loss = run(1e-3, pairs)
        row(f"fig4/n_pairs_{pairs}", us,
            f"loss_after_25_steps={loss:.4f}")


def bench_fig6():
    from repro.core import protocols as P
    from repro.core import zo as Z
    from repro.data.synthetic import BigramLM
    from repro.distributed.sharding import AxisRules
    from repro.models import transformer as T
    from repro.models.config import ModelConfig
    from repro.optim.optimizers import make_optimizer
    rules = AxisRules(mesh=None)
    ds = BigramLM(vocab=31, seq_len=17, seed=0)

    def run(method, aux_layers):
        cfg = ModelConfig(name="t", n_layers=4, d_model=32, n_heads=4,
                          n_kv_heads=2, d_ff=64, vocab=31, cut_layers=1,
                          aux_layers=aux_layers, param_dtype="float32",
                          compute_dtype="float32")
        api = P.lm_api(cfg, rules)
        params = T.init_lm(jax.random.PRNGKey(0), cfg)
        copt = make_optimizer(
            "zo_sgd" if method == "heron" else "adamw",
            5e-3 if method == "heron" else 1e-3)
        sopt = make_optimizer("adamw", 2e-3)
        st = P.init_train_state(jax.random.PRNGKey(1), params, copt,
                                sopt)
        step = jax.jit(P.make_train_step(
            api, method, Z.ZOConfig(mu=1e-3, n_pairs=2), copt, sopt))
        m = {}
        for i in range(25):
            st, m = step(st, ds.batch(jax.random.PRNGKey(100 + i), 16))
        return float(m["loss"])

    for method in ("heron", "cse_fsl"):
        for aux in (0, 1, 2):
            loss = run(method, aux)
            row(f"fig6/{method}/aux_layers_{aux}", 0.0,
                f"loss_after_25_steps={loss:.4f}")


# ---------------------------------------------------------------------------
def bench_seed_replay():
    """The lean uplink: bytes on the wire (dense vs (seed, coeff)) and
    Fed-Server reconstruction wall-clock (flattened scan vs the
    triple-loop reference it replaced)."""
    from repro.core import aggregate as AG
    from repro.core import protocols as P
    from repro.core import zo as Z
    from repro.core.split import param_bytes
    from repro.data.pipeline import round_batches
    from repro.data.synthetic import GaussianMixtureImages
    from repro.models import cnn as CNN
    from repro.optim.optimizers import make_optimizer

    cfg = CNN.CNNConfig(widths=(16, 32), blocks_per_stage=1, classes=10,
                        client_blocks=1)
    params = CNN.init_cnn(jax.random.PRNGKey(0), cfg)
    N, h, pairs, lr = 4, 2, 2, 2e-2
    zo = Z.ZOConfig(mu=1e-3, n_pairs=pairs)

    dense_b = N * param_bytes(params["client"])
    lean_b = P.seed_replay_uplink_bytes(N, h, pairs)
    row("seed_replay/uplink_bytes_dense", 0.0, f"{dense_b}B (N={N})")
    row("seed_replay/uplink_bytes_lean", 0.0,
        f"{lean_b}B reduction={dense_b / lean_b:.0f}x")

    keys = Z.fold_in_range(jax.random.PRNGKey(7), N)
    coeffs = jax.random.normal(jax.random.PRNGKey(8), (N, h, pairs))
    scan_fn = jax.jit(lambda c: AG.seed_replay_aggregate(
        params["client"], keys, c, lr, zo))
    us_scan, out_scan = timeit(scan_fn, coeffs, n=3)
    ref_fn = jax.jit(lambda c: AG.seed_replay_aggregate_reference(
        params["client"], keys, c, lr, zo))
    us_ref, out_ref = timeit(ref_fn, coeffs, n=3)
    err = max(float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                    - b.astype(jnp.float32))))
              for a, b in zip(jax.tree.leaves(out_scan),
                              jax.tree.leaves(out_ref)))
    row("seed_replay/reconstruct_scan", us_scan,
        f"N*h*pairs={N * h * pairs}")
    row("seed_replay/reconstruct_loop_ref", us_ref,
        f"loop_over_scan={us_ref / us_scan:.2f} max_err={err:.2g}")

    # end-to-end federated round, dense vs lean uplink
    ds = GaussianMixtureImages(classes=10, hw=16, noise=0.8)
    api = P.cnn_api(cfg)
    fed = P.FedConfig(n_clients=N, h=h)
    sopt = make_optimizer("adamw", 2e-3)
    rb = round_batches(ds, jax.random.PRNGKey(3), N, h, 16)
    state = {"client": params["client"], "server": params["server"],
             "opt_server": sopt.init(params["server"])}
    for uplink in ("dense", "seed_replay"):
        rnd = jax.jit(P.make_fed_round(
            api, "heron", zo, fed, make_optimizer("zo_sgd", lr), sopt,
            uplink=uplink, client_lr=lr))
        us, (_, m) = timeit(rnd, state, rb, jax.random.PRNGKey(9), n=3)
        row(f"seed_replay/fed_round_{uplink}", us,
            f"uplink_bytes={float(m['uplink_bytes']):.3g}")


# ---------------------------------------------------------------------------
def bench_seed_replay_scaling():
    """N-scaling of the mesh-sharded seed-replay reconstruction.

    For each cohort size N the Fed-Server replays N·h·n_pairs directions
    flat (one scan) and sharded over a ``("clients",)`` device mesh; the
    row records both wall-clocks, the speedup, and the sharded-vs-flat
    max error (fp32 summation-order noise only).  On a single-device CPU
    host the bench re-execs itself with a forced 4-device host platform
    so the sharded path has a real mesh to scale over, and re-emits the
    child's rows.  REPRO_SCALING_NMAX caps the sweep (CI).
    """
    import subprocess
    import sys

    from repro.core import aggregate as AG
    from repro.core import zo as Z

    n_max = int(os.environ.get("REPRO_SCALING_NMAX", "100000"))
    if (jax.default_backend() == "cpu" and jax.device_count() == 1
            and os.environ.get("REPRO_SCALING_SUBPROC") != "1"):
        env = dict(os.environ, REPRO_SCALING_SUBPROC="1",
                   XLA_FLAGS=(os.environ.get("XLA_FLAGS", "")
                              + " --xla_force_host_platform_device_count=4"))
        r = subprocess.run([sys.executable, os.path.abspath(__file__),
                            "seed_replay_scaling"], env=env,
                           capture_output=True, text=True, timeout=3000)
        if r.returncode != 0:
            raise RuntimeError("scaling subprocess failed: "
                               + r.stderr[-300:])
        for line in r.stdout.splitlines():
            if line.startswith("seed_replay_scaling/"):
                name, us, derived = line.split(",", 2)
                row(name, float(us), derived)
        return

    devs = jax.device_count()
    params = {"w": jax.random.normal(jax.random.PRNGKey(0), (128, 64)),
              "b": jnp.zeros((64,), jnp.float32)}
    zo = Z.ZOConfig(mu=1e-3, n_pairs=1)
    h, lr = 1, 1e-2

    def err_vs(a, b):
        return max(float(jnp.max(jnp.abs(x - y)))
                   for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))

    n_sweep = [n for n in (100, 1000, 10000, 100000) if n <= n_max]
    for N in n_sweep:
        keys = Z.fold_in_range(jax.random.PRNGKey(7), N)
        coeffs = jax.random.normal(jax.random.PRNGKey(8), (N, h, 1))
        flat_fn = jax.jit(lambda c, k: AG.seed_replay_aggregate(
            params, k, c, lr, zo))
        us_flat, out_flat = timeit(flat_fn, coeffs, keys, n=2, warmup=1)
        sh_fn = jax.jit(lambda c, k: AG.seed_replay_aggregate(
            params, k, c, lr, zo, shard="clients"))
        us_sh, out_sh = timeit(sh_fn, coeffs, keys, n=2, warmup=1)
        row(f"seed_replay_scaling/N{N}", us_sh,
            f"devices={devs} flat_us={us_flat:.1f} "
            f"speedup={us_flat / us_sh:.2f} "
            f"max_err={err_vs(out_flat, out_sh):.2g}")

    # donated-buffer chunked streaming at the largest N (eager outer
    # loop: this is the O(d)-memory serving shape, not a jit candidate)
    N = n_sweep[-1]
    keys = Z.fold_in_range(jax.random.PRNGKey(7), N)
    coeffs = jax.random.normal(jax.random.PRNGKey(8), (N, h, 1))
    chunk = 4096
    us_ch, out_ch = timeit(
        lambda: AG.seed_replay_aggregate(params, keys, coeffs, lr, zo,
                                         shard="clients", chunk=chunk),
        n=2, warmup=1)
    out_flat = jax.jit(lambda c, k: AG.seed_replay_aggregate(
        params, k, c, lr, zo))(coeffs, keys)
    row(f"seed_replay_scaling/N{N}_chunk{chunk}", us_ch,
        f"devices={devs} max_err={err_vs(out_flat, out_ch):.2g}")


# ---------------------------------------------------------------------------
def bench_async_round():
    """Buffered-async vs synchronous federated round under injected
    stragglers (20% of the cohort, 10x slower) on the ResNet-18 smoke
    config: global-update throughput per simulated second, time to the
    first global update, and simulated time-to-loss for the event-driven
    fleet (fast clients keep completing rounds while the straggler's
    first round is still in flight)."""
    import numpy as np

    from repro.configs.resnet18_cifar import smoke_config
    from repro.core import aggregate as AG
    from repro.core import protocols as P
    from repro.core import zo as Z
    from repro.data.pipeline import round_batches
    from repro.data.synthetic import GaussianMixtureImages
    from repro.fed import (AsyncReplayServer, FleetController,
                           StalenessConfig)
    from repro.fed.cutplan import CutPlan, DeviceProfile
    from repro.models import cnn as CNN
    from repro.optim.optimizers import make_optimizer

    cfg = smoke_config()
    ds = GaussianMixtureImages(classes=10, hw=8, noise=0.8)
    api = P.cnn_api(cfg)
    N, h, pairs, lr, rounds = 10, 2, 2, 2e-2, 6
    zo = Z.ZOConfig(mu=1e-3, n_pairs=pairs)
    fed = P.FedConfig(n_clients=N, h=h)
    copt = make_optimizer("zo_sgd", lr)
    sopt = make_optimizer("adamw", 2e-3)
    durations = np.ones(N)
    durations[-max(N // 5, 1):] = 10.0      # 20% stragglers, 10x slower
    makespan = float(durations.max())
    params = CNN.init_cnn(jax.random.PRNGKey(0), cfg)
    state0 = {"client": params["client"], "server": params["server"],
              "opt_server": sopt.init(params["server"])}
    held = ds.batch(jax.random.PRNGKey(12345), 256)
    held_loss = jax.jit(lambda cp: api.client_loss(cp, held)[0])

    # --- synchronous barrier baseline (same lean uplink) -------------
    sync_rnd = jax.jit(P.make_fed_round(
        api, "heron", zo, fed, copt, sopt, uplink="seed_replay",
        client_lr=lr))
    state = state0
    sync_curve = []
    t0 = time.perf_counter()
    for r in range(rounds):
        rb = round_batches(ds, jax.random.PRNGKey(r), N, h, 16)
        state, m = sync_rnd(state, rb, jax.random.PRNGKey(1000 + r))
        sync_curve.append(((r + 1) * makespan,
                           float(held_loss(state["client"]))))
    us_sync = (time.perf_counter() - t0) / rounds * 1e6
    sync_tput = 1.0 / makespan              # one global update per round
    row("async_round/sync", us_sync,
        f"updates_per_sim_s={sync_tput:.3g} "
        f"time_to_first_update_s={makespan:.3g} "
        f"loss_after_{rounds}_rounds={sync_curve[-1][1]:.4f}")

    # --- buffered-async engine (eager orchestration: not a jit
    #     candidate — it drives jitted cohort/replay pieces) ----------
    async_rnd = P.make_async_round(api, "heron", zo, fed, copt, sopt,
                                   client_lr=lr, staleness_alpha=0.5,
                                   buffer_k=4)
    state = state0
    t0 = time.perf_counter()
    m = {}
    for r in range(rounds):
        rb = round_batches(ds, jax.random.PRNGKey(r), N, h, 16)
        state, m = async_rnd(state, rb, jax.random.PRNGKey(1000 + r),
                             durations=durations)
    us_async = (time.perf_counter() - t0) / rounds * 1e6
    speedup = m["updates_per_sim_s"] / sync_tput
    row("async_round/async_buffer4", us_async,
        f"updates_per_sim_s={m['updates_per_sim_s']:.3g} "
        f"speedup_vs_sync={speedup:.2f} (gate: >=1.5) "
        f"flushes={m['flushes']:.0f} "
        f"mean_staleness={m['mean_staleness']:.2f} "
        f"time_to_first_update_s={m['time_to_first_update_s']:.3g} "
        f"loss_after_{rounds}_rounds="
        f"{float(held_loss(state['client'])):.4f}")

    # --- event-driven fleet: simulated time-to-loss ------------------
    # target = what the sync barrier reaches after `rounds` rounds; the
    # async fleet keeps fast clients busy while stragglers are in
    # flight, so it should cross the target in far less simulated time.
    target = sync_curve[-1][1]
    t_sync = next(t for t, l in sync_curve if l <= target)

    @jax.jit
    def local_round(cp, ck, batches):
        def step_m(cp, xs):
            m_, bm = xs
            g, info = Z.zo_gradient(lambda p: api.client_loss(p, bm),
                                    cp, jax.random.fold_in(ck, m_), zo)
            return Z.add_scaled(cp, g, -lr), info["coeffs"]

        _, coeffs = jax.lax.scan(step_m, cp, (jnp.arange(h), batches))
        return coeffs

    def local_fn(global_params, cid, round_idx, base_version):
        ck = jax.random.fold_in(
            jax.random.fold_in(jax.random.PRNGKey(11), round_idx), cid)
        batches = jax.tree.map(
            lambda *xs: jnp.stack(xs),
            *[ds.batch(jax.random.fold_in(ck, 900 + m_), 16)
              for m_ in range(h)])
        coeffs = local_round(global_params, ck, batches)
        return AG._raw_key_data(ck), coeffs, 1.0

    server = AsyncReplayServer(params["client"], lr, zo,
                               staleness=StalenessConfig(alpha=0.5),
                               buffer_k=4)
    reached = []

    def on_flush(cids, t):
        if not reached and float(held_loss(server.params)) <= target:
            reached.append(t)

    server.on_flush = on_flush
    ctl = FleetController(server, local_fn, sleep=lambda s: None)
    prof = DeviceProfile("bench", 1e9, 1e9, 1e12)
    for d in durations:
        ctl.admit(prof, CutPlan(cut=cfg.client_blocks, round_s=float(d),
                                feasible=True))
    budget = 6 * rounds * N                  # completion cap, not time
    while not reached and ctl.telemetry.completed < budget:
        ctl.run(N)
    t_async = reached[0] if reached else float("inf")
    row("async_round/fleet_time_to_loss", 0.0,
        f"target_loss={target:.4f} sync_s={t_sync:.3g} "
        f"async_s={t_async:.3g} "
        f"speedup={t_sync / t_async:.2f} "
        f"completions={ctl.telemetry.completed} "
        f"flushes={server.telemetry.flushes}")


# ---------------------------------------------------------------------------
def bench_serve():
    """Sustained decode throughput: the fused single-jit engine (paged KV
    slots, K-step segments, continuous batching) vs the eager
    ``make_serve_step`` Python loop it replaced, on a GPT-2-class config
    with a mixed-length request queue.  Greedy decode, so the two paths
    must also produce identical tokens; the speedup gate (>=3x) is
    enforced — a miss surfaces as an ERROR row that fails ``--check``."""
    import numpy as np

    from repro.configs.gpt2 import gpt2_tiny
    from repro.core import decode as D
    from repro.core import protocols as P
    from repro.distributed.sharding import AxisRules
    from repro.models import transformer as T

    cfg = gpt2_tiny()
    rules = AxisRules(mesh=None)
    params = T.init_lm(jax.random.PRNGKey(0), cfg)
    slots, max_new, seg = 8, 24, 12
    n_req = int(os.environ.get("REPRO_SERVE_REQUESTS", "16"))
    lengths = (4, 8, 12, 16)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab, size=lengths[i % len(lengths)])
               for i in range(n_req)]
    capacity = max(lengths) + max_new

    # --- eager baseline: the old driver's per-token Python loop over
    # make_serve_step.  A scalar-pos cache cannot batch mixed-length
    # requests, so the faithful baseline serves them one at a time
    # (batch=1); the idealized equal-length grouping below is also
    # reported as the strongest schedule that layout allows.
    serve = jax.jit(P.make_serve_step(cfg, rules))

    def eager_batched(members):
        plen = len(members[0][1])
        batch = jnp.asarray(np.stack([p for _, p in members]), jnp.int32)
        caches = P.init_serve_caches(cfg, len(members), capacity)
        for t in range(plen):
            logits, caches = serve(params, caches, batch[:, t:t + 1])
        toks = []
        for _ in range(max_new):
            tok = jnp.argmax(logits[:, -1, :cfg.vocab], -1)[:, None]
            toks.append(tok)
            logits, caches = serve(params, caches, tok)
        gen = jax.block_until_ready(jnp.concatenate(toks, axis=1))
        return {rid: row_toks.tolist() for (rid, _), row_toks
                in zip(members, np.asarray(gen))}

    def eager_run():
        out = {}
        for i, p in enumerate(prompts):
            out.update(eager_batched([(i, p)]))
        return out

    def eager_grouped_run():
        groups: dict[int, list] = {}
        for i, p in enumerate(prompts):
            groups.setdefault(len(p), []).append((i, p))
        out = {}
        for members in groups.values():
            out.update(eager_batched(members))
        return out

    # --- fused engine: block prefill into paged slots + K-step segments
    def fused_run():
        eng = D.DecodeEngine(params, cfg, rules, slots=slots,
                             capacity=capacity, segment_len=seg)
        rids = [eng.submit(p, max_new) for p in prompts]
        out = eng.run()
        return {i: out[rid] for i, rid in enumerate(rids)}, eng.segments

    us_eager, out_eager = timeit(lambda: eager_run(), n=2, warmup=1)
    us_grouped, out_grouped = timeit(lambda: eager_grouped_run(), n=2,
                                     warmup=1)
    us_fused, (out_fused, segments) = timeit(lambda: fused_run(), n=2,
                                             warmup=1)
    total = sum(len(t) for t in out_eager.values())
    eager_tps = total / (us_eager / 1e6)
    grouped_tps = total / (us_grouped / 1e6)
    fused_tps = total / (us_fused / 1e6)
    match = out_eager == out_fused and out_grouped == out_fused
    speedup = fused_tps / eager_tps
    row("serve/eager_loop", us_eager,
        f"sustained_tok_s={eager_tps:.1f} requests={n_req} "
        f"mixed_prompt_lens={list(lengths)} (per-request batch=1: "
        "scalar-pos caches cannot batch mixed lengths)")
    row("serve/eager_grouped", us_grouped,
        f"sustained_tok_s={grouped_tps:.1f} (idealized equal-length "
        "batching, still per-token dispatch)")
    row("serve/fused_engine", us_fused,
        f"sustained_tok_s={fused_tps:.1f} batch={slots} "
        f"segments={segments} segment_len={seg} "
        f"vs_grouped={fused_tps / grouped_tps:.2f}x")
    row("serve/fused_vs_eager", 0.0,
        f"speedup={speedup:.2f}x (gate: >=3) greedy_match={match}")
    assert match, "fused greedy tokens diverge from eager loop"
    assert speedup >= 3.0, f"fused speedup {speedup:.2f}x below 3x gate"


# ---------------------------------------------------------------------------
def bench_kernels():
    from repro.kernels import ops
    from repro.models import attention as A
    q = jax.random.normal(jax.random.PRNGKey(0), (2, 512, 8, 64))
    k = jax.random.normal(jax.random.PRNGKey(1), (2, 512, 2, 64))
    v = jax.random.normal(jax.random.PRNGKey(2), (2, 512, 2, 64))
    naive = jax.jit(lambda q, k, v: A.naive_attention(q, k, v))
    blocked = jax.jit(lambda q, k, v: A.blocked_attention(
        q, k, v, q_chunk=128, kv_chunk=128))
    us_n, _ = timeit(naive, q, k, v, n=3)
    us_b, _ = timeit(blocked, q, k, v, n=3)
    row("kernels/naive_attention_512", us_n, "xla")
    row("kernels/blocked_attention_512", us_b,
        f"naive_over_blocked={us_n / us_b:.2f}")
    x = jax.random.normal(jax.random.PRNGKey(3), (128, 256))
    w = jax.random.normal(jax.random.PRNGKey(4), (256, 128))
    t0 = time.perf_counter()
    jax.block_until_ready(ops.zo_matmul(x, w, 7, 1e-3, bm=128))
    row("kernels/zo_matmul_interpret", (time.perf_counter() - t0) * 1e6,
        "pallas_interpret_smoke")
    # fused dual probe (clean + perturbed in one pass over W) vs two
    # separate zo_matmul passes.  Interpret wall clock is the CPU proxy;
    # on TPU the fused kernel additionally halves the HBM reads of W.
    fused = jax.jit(lambda x, w: ops.zo_dual_forward(x, w, 7, 1e-3,
                                                     impl="interpret"))
    split = jax.jit(lambda x, w: ops.zo_dual_forward_split(
        x, w, 7, 1e-3, interpret=True))
    us_f, _ = timeit(fused, x, w, n=3)
    us_s, _ = timeit(split, x, w, n=3)
    row("kernels/zo_dual_fused_interpret", us_f, "one pass over W")
    row("kernels/zo_dual_split_interpret", us_s,
        f"split_over_fused={us_s / us_f:.2f}")
    emul = jax.jit(lambda x, w: ops.zo_dual_forward(x, w, 7, 1e-3,
                                                    impl="xla"))
    us_e, _ = timeit(emul, x, w, n=3)
    row("kernels/zo_dual_xla_emulation", us_e, "bit-exact jnp fallback")
    a = jax.random.uniform(jax.random.PRNGKey(5), (2, 256, 64),
                           minval=0.5, maxval=0.99)
    b = jax.random.normal(jax.random.PRNGKey(6), (2, 256, 64))
    t0 = time.perf_counter()
    jax.block_until_ready(ops.rg_lru_scan(a, b, bt=64, bw=64))
    row("kernels/rg_lru_interpret", (time.perf_counter() - t0) * 1e6,
        "pallas_interpret_smoke")

    # fused dual-probe flash attention (clean + score-perturbed streams
    # through one sequential pass over K/V) vs two separate flash
    # passes.  Interpret wall clock is the CPU proxy; the HBM-bytes
    # column counts the K/V block loads the shared pass eliminates
    # (exact on TPU, where each grid step streams its K/V tile from
    # HBM into VMEM).  REPRO_ATTN_SEQ caps the sequence for CI smoke.
    from repro.kernels import flash_attention as FA
    S = int(os.environ.get("REPRO_ATTN_SEQ", "256"))
    B, H, D = 2, 12, 64
    bq, bk = min(128, S), min(128, S)
    qa = jax.random.normal(jax.random.PRNGKey(7), (B, S, H, D))
    qb = jax.random.normal(jax.random.PRNGKey(8), (B, S, H, D))
    ka = jax.random.normal(jax.random.PRNGKey(9), (B, S, H, D))
    va = jax.random.normal(jax.random.PRNGKey(10), (B, S, H, D))
    fused_fa = jax.jit(lambda qa, qb, k, v: ops.zo_dual_flash_attention(
        qa, qb, k, v, seed=7, mu_b=1e-3, perturb_b=True,
        impl="interpret", bq=bq, bk=bk))
    # one head per grid step, as the fused kernel takes them
    one_fa = jax.jit(lambda q, k, v: FA.flash_attention(
        q, k, v, bq=bq, bk=bk, hb=1, interpret=True))

    def two_fa(qa, qb, k, v):
        return one_fa(qa, ka, va), one_fa(qb, ka, va)

    us_fa_f, _ = timeit(fused_fa, qa, qb, ka, va, n=3)
    us_fa_2, _ = timeit(two_fa, qa, qb, ka, va, n=3)
    nq, nk = S // bq, -(-S // bk)
    kv_gb = B * H * nq * nk * 2 * bk * D * 4 / 1e9  # one pass's K/V loads
    ratio = us_fa_2 / us_fa_f
    row("kernels/zo_dual_flash_attn_fused", us_fa_f,
        f"B{B}xS{S}xH{H}xD{D} kv_hbm_gb={kv_gb:.3g} (shared K/V pass)")
    gated = S >= 256      # short sequences don't amortize per-step cost
    row("kernels/zo_dual_flash_attn_two_pass", us_fa_2,
        f"kv_hbm_gb={2 * kv_gb:.3g} two_pass_over_fused={ratio:.2f} "
        + ("(gate: >=1.2)" if gated else "(smoke size: gate waived)"))
    assert not gated or ratio >= 1.2, (
        f"fused flash speedup {ratio:.2f}x below 1.2x gate")


BENCHES = {
    "table1": bench_table1, "table2": bench_table2,
    "table3": bench_table3, "fig2": bench_fig2, "fig4": bench_fig4,
    "fig6": bench_fig6, "seed_replay": bench_seed_replay,
    "seed_replay_scaling": bench_seed_replay_scaling,
    "async_round": bench_async_round,
    "serve": bench_serve,
    "kernels": bench_kernels,
}


def _git_rev() -> str:
    import subprocess
    try:
        return subprocess.check_output(
            ["git", "rev-parse", "--short", "HEAD"],
            cwd=os.path.dirname(os.path.abspath(__file__)),
            stderr=subprocess.DEVNULL).decode().strip()
    except Exception:  # pragma: no cover
        return "unknown"


def _write_json(name: str, rows) -> None:
    """Machine-readable mirror of the CSV rows: BENCH_<name>.json next to
    this script, so CI can diff runs across revisions."""
    out = {"name": name, "git_rev": _git_rev(),
           "backend": jax.default_backend(),
           "rows": [{"name": n, "us": u, "derived": d}
                    for n, u, d in rows]}
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        f"BENCH_{name}.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
        f.write("\n")
    print(f"# wrote {path}", flush=True)


def check_json(names) -> int:
    """Validate BENCH_<name>.json files (CI gate): each must exist,
    parse, carry non-empty rows with numeric ``us``, and contain no
    */ERROR rows.  Returns a nonzero exit code on any violation."""
    bad = 0
    here = os.path.dirname(os.path.abspath(__file__))
    for name in names:
        path = os.path.join(here, f"BENCH_{name}.json")
        try:
            with open(path) as f:
                data = json.load(f)
        except (OSError, json.JSONDecodeError) as e:
            print(f"CHECK FAIL {name}: {e}")
            bad += 1
            continue
        rows = data.get("rows", [])
        errs = [r for r in rows if str(r.get("name", "")).endswith("/ERROR")]
        if not rows:
            print(f"CHECK FAIL {name}: no rows")
            bad += 1
        elif errs:
            print(f"CHECK FAIL {name}: ERROR rows {errs}")
            bad += 1
        elif not all(isinstance(r.get("us"), (int, float)) for r in rows):
            print(f"CHECK FAIL {name}: non-numeric us field")
            bad += 1
        else:
            print(f"CHECK OK {name}: {len(rows)} rows")
    return bad


def main(argv=None) -> None:
    import sys
    names = list(argv if argv is not None else sys.argv[1:]) or \
        list(BENCHES)
    if names and names[0] == "--check":
        raise SystemExit(check_json(names[1:] or list(BENCHES)))
    unknown = [n for n in names if n not in BENCHES]
    if unknown:
        raise SystemExit(f"unknown benchmark(s) {unknown}; "
                         f"choose from {list(BENCHES)}")
    from repro.launch import compile_cache
    compile_cache.enable()
    print("name,us_per_call,derived")
    failed = []
    for name in names:
        fn = BENCHES[name]
        t0 = time.time()
        start = len(ROWS)
        try:
            fn()
        except Exception as e:  # pragma: no cover
            traceback.print_exc()
            row(f"{fn.__name__}/ERROR", 0.0, repr(e)[:120])
            failed.append(name)
        _write_json(name, ROWS[start:])
        print(f"# {fn.__name__} done in {time.time()-t0:.1f}s",
              flush=True)
    if failed:
        raise SystemExit(f"benchmark(s) failed: {failed}")


if __name__ == "__main__":
    main()
