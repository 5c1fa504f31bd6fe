"""Bring-up smoke of the HERON main path on a TPU at GPT-2 Medium width.

    python chip_smoke.py            # one chip: federated rounds + serving
    python chip_smoke.py --chips 4  # four chips: the cross-chip paths only

One chip (24 layers, d_model 1024, 16 heads, d_ff 4096, vocab 50257,
split after block 6 with a 3-block aux head, bf16, random weights from a
seed):

* training — federated rounds through ``make_fed_round`` with
  ``forward_impl="kernel"``, so the clients' dual probe runs through the
  compiled Pallas kernels; the lean ``seed_replay`` uplink is checked
  against the ``dense`` one at h=1, and the in-kernel noise generator
  against the XLA replay stream, bit for bit;
* serving — ``DecodeEngine`` answers requests of two prompt lengths, each
  generated token checked against a teacher-forced full forward.

Four chips: the ``"clients"``-sharded Fed-Server replay against the flat
replay on one chip, and the datacenter HERON step on a ("data", "model")
mesh against the same step with no mesh.

Times and memory printed here are bring-up observations, not benchmark
numbers.  The last line of stdout is one JSON object naming the device;
it is printed only when every check passed.  Without a TPU the script
exits non-zero before any phase runs.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs.gpt2 import gpt2_medium  # noqa: E402
from repro.core import aggregate as AG  # noqa: E402
from repro.core import decode as D  # noqa: E402
from repro.core import protocols as P  # noqa: E402
from repro.core import zo as Z  # noqa: E402
from repro.data.pipeline import place_batch  # noqa: E402
from repro.distributed.sharding import AxisRules  # noqa: E402
from repro.kernels import ops as O  # noqa: E402
from repro.launch import compile_cache  # noqa: E402
from repro.launch.mesh import make_local_mesh, make_replay_mesh  # noqa: E402
from repro.models import transformer as T  # noqa: E402
from repro.optim.optimizers import make_optimizer  # noqa: E402

SEQ = 1024
CLIENTS = 4
# per-client micro-batch: the compiled GPT-2 Medium round for a v5e chip
# needs 2.4 GiB of arguments and 7.6 GiB of temporaries at 4 (dense
# uplink, memory_analysis), which leaves room for the dense-vs-lean
# comparison's second state on a 16 GiB chip
MICRO_BATCH = 4
LEAN_ROUNDS = 3
CLIENT_LR, SERVER_LR, MU = 1e-4, 1e-4, 1e-3
MAX_ULPS = 2          # lean vs dense at h=1, per leaf (see _ulps)
SERVE_PROMPTS = (64, 256)
SERVE_PER_LEN = 3
SERVE_NEW = 32
SERVE_SLOTS = 4
REF_TOP_K = 5
MESH_BATCH = 8
MESH_MU = 1e-2        # see mesh_step_phase
MESH_LOSS_RTOL = 1e-3
MESH_MIN_COS = 0.9    # client updates, mesh vs no mesh


def log(tag, **fields):
    print(f"[{tag}] " + json.dumps(fields, default=float), flush=True)


def require_tpu(n_chips: int):
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"chip_smoke needs a TPU; JAX found "
                         f"{devs[0].platform!r}")
    if len(devs) < n_chips:
        raise SystemExit(f"chip_smoke --chips {n_chips} found "
                         f"{len(devs)} device(s)")
    return devs


def _tree_max_abs_diff(a, b):
    return max(float(jnp.max(jnp.abs(x.astype(jnp.float32)
                                     - y.astype(jnp.float32))))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def _ulps(a, b):
    """Worst per-leaf max|a - b| in ulps of the leaf dtype at the leaf's
    largest magnitude.  Per-client rounding before an average is bounded
    by the clients' magnitudes, not by the average's, so a per-element
    ulp would count cancellation as error."""
    worst = 0.0
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        ulp = 2.0 ** -jnp.finfo(x.dtype).nmant
        xf, yf = x.astype(jnp.float32), y.astype(jnp.float32)
        scale = float(jnp.maximum(jnp.max(jnp.abs(xf)),
                                  jnp.max(jnp.abs(yf))))
        if scale > 0:
            worst = max(worst, float(jnp.max(jnp.abs(xf - yf)))
                        / (ulp * scale))
    return worst


def _update_cosine(old, a, b):
    """Cosine between the updates ``a - old`` and ``b - old`` of two
    trees, each flattened to one f32 vector."""
    dot = na = nb = 0.0
    for o, x, y in zip(*(jax.tree.leaves(t) for t in (old, a, b))):
        o = np.asarray(o, np.float32)
        dx, dy = np.asarray(x, np.float32) - o, np.asarray(y, np.float32) - o
        dot += float(np.vdot(dx, dy))
        na += float(np.vdot(dx, dx))
        nb += float(np.vdot(dy, dy))
    return dot / (na * nb) ** 0.5 if na and nb else 0.0


def _entries(path):
    return len(os.listdir(path)) if os.path.isdir(path) else 0


def _finite(*xs):
    return all(np.isfinite(float(x)) for x in xs)


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------

def noise_phase(shape=(1024, 4096), seed=1234):
    """The kernel's noise generator (``zo_noise``) against the XLA stream
    the Fed-Server replays (``uniform_noise``): must be bit-identical."""
    uk = O.zo_noise(jnp.zeros(shape, jnp.bfloat16), seed)
    ux = jax.jit(O.uniform_noise, static_argnums=1)(seed, shape)
    equal = bool(np.array_equal(np.asarray(uk), np.asarray(ux)))
    log("noise", shape=list(shape), kernel_equals_replay=equal)
    if not equal:
        raise RuntimeError("in-kernel noise differs from the replay stream")


def train_phase(cfg, *, impl: str, clients=CLIENTS, batch=MICRO_BATCH,
                seq=SEQ, lean_rounds=LEAN_ROUNDS, seed=0):
    """Federated HERON rounds (h=1, one pair).  Returns the trained
    params and a report; raises if a check fails."""
    resolved = P.forward_impl_of(cfg)
    if resolved != impl:
        raise RuntimeError(f"client forward resolved to {resolved!r}, "
                           f"expected {impl!r}")
    rules = AxisRules(mesh=None)
    api = P.lm_api(cfg, rules)
    copt = make_optimizer("zo_sgd", CLIENT_LR)
    sopt = make_optimizer("adamw", SERVER_LR)
    fed = P.FedConfig(n_clients=clients, h=1)
    zo = Z.ZOConfig(mu=MU, n_pairs=1)
    key = jax.random.PRNGKey(seed)
    params = T.init_lm(jax.random.fold_in(key, 0), cfg)
    state = {"client": params["client"], "server": params["server"],
             "opt_server": sopt.init(params["server"])}
    del params

    def round_inputs(r):
        toks = jax.random.randint(jax.random.fold_in(key, 100 + r),
                                  (clients, 1, batch, seq + 1), 0, cfg.vocab)
        rb = {"inputs": toks[..., :-1], "labels": toks[..., 1:]}
        return jax.block_until_ready(rb), jax.random.fold_in(key, 200 + r)

    def compiled(uplink, donate):
        fn = P.make_fed_round(api, "heron", zo, fed, copt, sopt,
                              uplink=uplink, client_lr=CLIENT_LR)
        t0 = time.perf_counter()
        c = jax.jit(fn, donate_argnums=(0,) if donate else ()).lower(
            state, *round_inputs(0)).compile()
        ma = c.memory_analysis()
        log("train", uplink=uplink, compile_s=time.perf_counter() - t0,
            kernel_in_hlo="tpu_custom_call" in c.as_text(),
            argument_bytes=ma.argument_size_in_bytes,
            temp_bytes=ma.temp_size_in_bytes)
        return c

    lean = compiled("seed_replay", donate=True)
    dense = compiled("dense", donate=False)
    kernel_in_hlo = all("tpu_custom_call" in c.as_text()
                        for c in (lean, dense))

    rb, k = round_inputs(0)
    out, m_dense = dense(state, rb, k)
    dense_client = out["client"]
    del out
    client0 = jax.tree.map(jnp.copy, state["client"])
    state, m = lean(state, rb, k)
    ulps = _ulps(state["client"], dense_client)
    max_abs = _tree_max_abs_diff(state["client"], dense_client)
    changed = sum(int(jnp.sum(x != y)) for x, y in zip(
        jax.tree.leaves(state["client"]), jax.tree.leaves(client0)))
    n_client = sum(x.size for x in jax.tree.leaves(client0))
    del dense_client, client0
    losses = [(float(m["client_loss"]), float(m["server_loss"]))]
    log("train", round=0, uplink="dense",
        client_loss=float(m_dense["client_loss"]),
        server_loss=float(m_dense["server_loss"]))
    log("train", round=0, uplink="seed_replay", client_loss=losses[0][0],
        server_loss=losses[0][1],
        lean_vs_dense_max_abs=max_abs, lean_vs_dense_ulps=ulps,
        client_elements_changed=changed / n_client)
    step_s = []
    for r in range(1, lean_rounds):
        rb, k = round_inputs(r)
        t0 = time.perf_counter()
        state, m = lean(state, rb, k)
        jax.block_until_ready(m)
        step_s.append(time.perf_counter() - t0)
        losses.append((float(m["client_loss"]), float(m["server_loss"])))
        log("train", round=r, uplink="seed_replay", client_loss=losses[-1][0],
            server_loss=losses[-1][1], round_s=step_s[-1],
            uplink_bytes=float(m["uplink_bytes"]),
            uplink_bytes_dense=float(m["uplink_bytes_dense"]))
    report = {"impl": resolved, "kernel_in_hlo": kernel_in_hlo,
              "lean_vs_dense_ulps": ulps,
              "lean_vs_dense_max_abs": max_abs, "losses": losses,
              "client_elements_changed": changed / n_client,
              "round_s": step_s}
    if not all(_finite(*l) for l in losses) or not _finite(
            m_dense["client_loss"], m_dense["server_loss"]):
        raise RuntimeError(f"non-finite loss: {losses}")
    if ulps > MAX_ULPS:
        raise RuntimeError(f"lean != dense at h=1: {ulps} ulps")
    if changed == 0:
        raise RuntimeError("the round left every client parameter as it "
                           "was; lean == dense would be vacuous")
    params = {"client": state["client"], "server": state["server"]}
    return params, report


def serve_phase(params, cfg, *, prompt_lens=SERVE_PROMPTS,
                per_len=SERVE_PER_LEN, max_new=SERVE_NEW,
                slots=SERVE_SLOTS, seed=0):
    """DecodeEngine over requests of two prompt lengths (more requests
    than slots, so slots are refilled).  Every request must come back
    with ``max_new`` tokens, each among the top ``REF_TOP_K`` of a
    teacher-forced full forward over the prompt and the tokens before
    it (greedy decode up to bf16 near-ties)."""
    rules = AxisRules(mesh=None)
    engine = D.DecodeEngine(params, cfg, rules, slots=slots,
                            capacity=max(prompt_lens) + max_new,
                            segment_len=16, seed=seed)
    rng = np.random.default_rng(seed)
    prompts = {}
    for plen in prompt_lens:
        for _ in range(per_len):
            p = rng.integers(0, cfg.vocab, size=plen)
            prompts[engine.submit(p, max_new)] = p
    t0 = time.perf_counter()
    out = engine.run()
    wall = time.perf_counter() - t0
    fwd = jax.jit(lambda p, x: T.full_forward(p, cfg, rules, x))
    worst_rank, exact, total = 0, 0, 0
    for plen in prompt_lens:
        rids = [r for r in sorted(prompts) if len(prompts[r]) == plen]
        if any(len(out.get(r, ())) != max_new for r in rids):
            raise RuntimeError(f"unanswered or short requests: "
                               f"{ {r: len(out.get(r, ())) for r in rids} }")
        seqs = np.stack([np.concatenate([prompts[r], out[r][:-1]])
                         for r in rids])
        logits = fwd(params, jnp.asarray(seqs, jnp.int32))
        logits = logits[:, plen - 1:, :cfg.vocab]
        gen = jnp.asarray([out[r] for r in rids], jnp.int32)
        picked = jnp.take_along_axis(logits, gen[..., None], axis=-1)
        rank = np.asarray(jnp.sum(logits > picked, axis=-1))
        worst_rank = max(worst_rank, int(rank.max()))
        exact += int((rank == 0).sum())
        total += rank.size
        for r in rids:
            log("serve", request=r, prompt_len=plen, tokens=out[r])
    log("serve", requests=len(out), segments=engine.segments,
        wall_s=wall, worst_reference_rank=worst_rank,
        exact_greedy_fraction=exact / total)
    if worst_rank >= REF_TOP_K:
        raise RuntimeError(f"a decoded token ranks {worst_rank} in the "
                           f"reference logits (limit {REF_TOP_K})")
    return {"requests": len(out), "worst_reference_rank": worst_rank,
            "exact_greedy_fraction": exact / total}


def one_chip(cfg):
    noise_phase()
    params, rep = train_phase(cfg, impl="pallas")
    if not rep["kernel_in_hlo"]:
        raise RuntimeError("no Pallas kernel (tpu_custom_call) in the "
                           "compiled round")
    serve_phase(params, cfg)
    stats = jax.devices()[0].memory_stats() or {}
    log("device", peak_bytes_in_use=stats.get("peak_bytes_in_use"))


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def replay_phase(cfg, *, clients=8, lr=CLIENT_LR, seed=0):
    """Seed replay of the client tree: ``"clients"``-sharded over every
    local chip vs the flat scan on one.  Both sum the same directions in
    f32; only the summation order differs."""
    key = jax.random.PRNGKey(seed)
    client = T.init_lm(key, cfg)["client"]
    seeds = O.fold_seed(jnp.int32(seed + 7), jnp.arange(clients))
    coeffs = jax.random.normal(jax.random.fold_in(key, 1), (clients, 1, 1))
    flat = jax.jit(functools.partial(AG.seed_replay_aggregate,
                                     lr=lr))(client, seeds, coeffs)
    mesh = make_replay_mesh()
    sharded = jax.jit(functools.partial(
        AG.seed_replay_aggregate, lr=lr, shard="clients",
        mesh=mesh))(client, seeds, coeffs)
    ulps = _ulps(sharded, flat)
    max_abs = _tree_max_abs_diff(sharded, flat)
    tree_bytes = sum(x.nbytes for x in jax.tree.leaves(client))
    spans = min(len(x.sharding.device_set) for x in jax.tree.leaves(sharded))
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in mesh.devices.flat]
    log("replay", clients=clients, devices=int(mesh.size),
        sharded_vs_flat_max_abs=max_abs, sharded_vs_flat_ulps=ulps,
        client_tree_bytes=tree_bytes, result_devices=spans,
        peak_bytes_per_device=peaks)
    if ulps > 1:
        raise RuntimeError(f"sharded replay differs from flat: {ulps} ulps")
    if spans < mesh.size:
        raise RuntimeError("the sharded replay result is not on every chip")
    return {"peak_bytes_per_device": peaks, "tree_bytes": tree_bytes}


def mesh_step_phase(cfg, *, batch=MESH_BATCH, seq=SEQ, seed=0):
    """The datacenter HERON step (``make_train_step``, the path of
    ``launch/train.py`` without ``--fed``) on a ("data", "model") mesh of
    every local chip vs the same step with no mesh.

    The probe here is at ``MESH_MU``, ten times the rounds' mu, so the
    loss difference it measures stands far above the bf16 rounding by
    which the two programs' reductions differ.  The client updates are
    compared by their cosine (same direction, same sign), since the bf16
    rounding of lr-sized updates leaves their element-wise difference
    near an ulp either way."""
    key = jax.random.PRNGKey(seed)
    params = T.init_lm(key, cfg)
    copt = make_optimizer("zo_sgd", CLIENT_LR)
    sopt = make_optimizer("adamw", SERVER_LR)
    toks = jax.random.randint(jax.random.fold_in(key, 1), (batch, seq + 1),
                              0, cfg.vocab)
    batch_in = {"inputs": toks[:, :-1], "labels": toks[:, 1:]}

    def run(mesh):
        rules = AxisRules(mesh=mesh, enable_fsdp=False)
        step = jax.jit(P.make_train_step(
            P.lm_api(cfg, rules), "heron",
            Z.ZOConfig(mu=MESH_MU), copt, sopt))
        st = P.init_train_state(jax.random.fold_in(key, 2), params, copt,
                                sopt)
        return step(st, place_batch(batch_in, rules))

    one, m_one = run(None)
    mesh = make_local_mesh(2)
    many, m_many = run(mesh)
    leaves = jax.tree.leaves(many["params"])
    spans = min(len(x.sharding.device_set) for x in leaves)
    split = sum(not x.sharding.is_fully_replicated for x in leaves)
    d_loss = abs(float(m_one["loss"]) - float(m_many["loss"]))
    d_closs = abs(float(m_one["client_loss"]) - float(m_many["client_loss"]))
    client_ulps = _ulps(one["params"]["client"], many["params"]["client"])
    cos = _update_cosine(params["client"], one["params"]["client"],
                         many["params"]["client"])
    log("mesh_step", mesh=dict(mesh.shape), loss=float(m_many["loss"]),
        loss_no_mesh=float(m_one["loss"]), loss_abs_diff=d_loss,
        client_loss_abs_diff=d_closs,
        zo_coeff=float(m_many["zo_coeff_abs"]),
        zo_coeff_no_mesh=float(m_one["zo_coeff_abs"]),
        server_params_max_abs_diff=_tree_max_abs_diff(
            one["params"]["server"], many["params"]["server"]),
        client_params_max_abs_diff=_tree_max_abs_diff(
            one["params"]["client"], many["params"]["client"]),
        client_params_ulps=client_ulps, client_update_cosine=cos,
        min_devices_per_leaf=spans, partitioned_leaves=split,
        leaves=len(leaves))
    if not _finite(m_many["loss"], m_many["client_loss"]):
        raise RuntimeError("non-finite loss on the mesh")
    if d_loss > MESH_LOSS_RTOL * abs(float(m_one["loss"])):
        raise RuntimeError(f"mesh loss differs by {d_loss}")
    if cos < MESH_MIN_COS:
        raise RuntimeError(f"mesh client update points elsewhere: cos {cos}")
    if spans < mesh.size or split == 0:
        raise RuntimeError("the mesh step did not spread over the mesh")


def four_chips(cfg):
    rep = replay_phase(cfg)
    # a chip that took part held at least its copy of the client tree
    if min(rep["peak_bytes_per_device"]) < rep["tree_bytes"]:
        raise RuntimeError(f"a chip took no part in the replay: {rep}")
    mesh_step_phase(cfg)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the cross-chip paths")
    args = ap.parse_args(argv)
    cache = compile_cache.enable()
    devs = require_tpu(args.chips)
    log("setup", devices=len(devs), kind=devs[0].device_kind,
        compile_cache=cache, cache_entries=_entries(cache))
    cfg = gpt2_medium()
    (four_chips if args.chips == 4 else one_chip)(cfg)
    log("teardown", cache_entries=_entries(cache))
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)


if __name__ == "__main__":
    main()
