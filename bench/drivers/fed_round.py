"""Driver ``fed_round``: synchronous HERON rounds of the program's
``make_fed_round(..., "heron", uplink="seed_replay")``.

Set-up builds one object, the compiled round with its state, from the
seed: weights made on the device in one jitted call, token ids drawn on
the device per round.  It drives that object through the traffic's
``check_steps`` first rounds, reading each round's losses, the first
round's gradients (the server's AdamW first moment; the client's update
over its learning rate) and the parameters' change after the last of
them.  The window then runs whole rounds, each ending in
``block_until_ready``, until ``--seconds`` have passed.  After the
window, the plain reference replays those first rounds from the same
seed and the readings are compared.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from lib import harness as H


def _norms(tree):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree.leaves(tree)])


@jax.jit
def _diff_norms(a, b):
    return _norms(jax.tree.map(lambda x, y: x.astype(jnp.float32)
                               - y.astype(jnp.float32), a, b))


@jax.jit
def _tree_norms(a):
    return _norms(a)


class FedCell:
    """The program's round, its state and its feed, for one cell; what
    depends on the model comes from its family's module (``model``)."""

    def __init__(self, model, cfg_json: dict, traffic: dict):
        from repro.core import protocols as P
        from repro.core import zo as Z
        from repro.optim.optimizers import make_optimizer

        self.model, self.cfg_json, self.traffic = model, cfg_json, traffic
        tr = traffic
        self.cfg = model.program_config(cfg_json)
        self.api = model.round_api(self.cfg)
        self.lr = tr["client_lr"]
        self.sopt = make_optimizer(tr["server_opt"], tr["server_lr"])
        self.round_fn = P.make_fed_round(
            self.api, "heron", Z.ZOConfig(mu=tr["mu"], n_pairs=tr["n_pairs"]),
            P.FedConfig(n_clients=tr["clients"], h=tr["h"],
                        participation=tr["participation"]),
            make_optimizer("zo_sgd", self.lr), self.sopt,
            uplink="seed_replay", client_lr=self.lr)
        self.shapes = model.param_shapes(self.cfg)
        self.names = {side: H.leaf_paths(self.shapes[side])
                      for side in ("client", "server")}
        self._rounds = {}
        self.init_params = jax.jit(self._init_params)
        self.init_state = jax.jit(self._init_state)
        self.make_inputs = jax.jit(self._make_inputs)

    # -- weights and feed, from the seed ----------------------------------

    def _init_params(self, root):
        return self.model.init_params(self.shapes, root)

    def _init_state(self, root):
        p = self._init_params(root)
        return {"client": p["client"], "server": p["server"],
                "opt_server": self.sopt.init(p["server"])}

    def _make_inputs(self, root, r, half_batch=False):
        batch = self.model.make_batch(self.cfg_json, self.traffic,
                                      jax.random.fold_in(root, 1000 + r))
        if half_batch:
            batch = jax.tree.map(
                lambda x: x[:, :, : self.traffic["micro_batch"] // 2], batch)
        return batch, jax.random.fold_in(root, 2000 + r)

    def compile(self, root):
        st = jax.eval_shape(self.init_state, root)
        b, k = jax.eval_shape(self.make_inputs, root, np.int32(0))
        return jax.jit(self.round_fn, donate_argnums=0).lower(
            st, b, k).compile()

    # -- readings ----------------------------------------------------------

    def named(self, side, values) -> dict[str, float]:
        return {f"{side}/{n}": float(v)
                for n, v in zip(self.names[side], np.asarray(values))}

    def readings(self, step, state, root, steps: int, feed=None):
        """Drive ``step(state, batch, key)`` through ``steps`` rounds from
        ``state`` on the feed (the cell's own by default); returns
        (state, readings)."""
        feed = feed or self.make_inputs
        p0 = self.init_params(root)
        loss, grad = [], {}
        for r in range(steps):
            batch, key = feed(root, np.int32(r))
            state, m = step(state, batch, key)
            loss.append([float(m["client_loss"]), float(m["server_loss"])])
            if r == 0:
                grad = self.named("client", np.asarray(_diff_norms(
                    state["client"], p0["client"])) / self.lr)
                grad |= self.named("server", _tree_norms(
                    state["opt_server"]["m"]))
        change = self.named("client", _diff_norms(state["client"],
                                                  p0["client"]))
        change |= self.named("server", _diff_norms(state["server"],
                                                   p0["server"]))
        return state, {"loss": loss, "grad": grad, "change": change}

    def reference(self, registry, root, precision="f32", half_batch=False):
        """The plain reference's readings over the same first rounds, in
        ``precision``; ``half_batch`` leaves out half of every client's
        micro-batch (a planted fault)."""
        t = self.traffic
        if precision not in self._rounds:
            ref = registry.reference(self.cfg_json["reference"])
            self._rounds[precision] = ref.Round(self.cfg_json, t, precision)
        rnd = self._rounds[precision]
        p = self.init_params(root)
        zeros = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32),
                             p["server"])
        state = {"client": p["client"], "server": p["server"],
                 "opt_server": {"step": jnp.zeros((), jnp.int32),
                                "m": zeros, "v": zeros}}
        del p
        feed = jax.jit(lambda root, r: self._make_inputs(root, r, True)) \
            if half_batch else None
        _, rd = self.readings(rnd, state, root, t["check_steps"], feed)
        return rd


def run(ctx) -> H.Result:
    cell = FedCell(ctx.model, ctx.cfg_json, ctx.traffic)
    root = H.root_key(ctx.seed)
    with H.span("bench.compile"):
        compiled = ctx.wrap_step(ctx.drives(cell.compile(root)), cell)
    with H.span("bench.init"):
        state = cell.init_state(root)
    with H.span("bench.first_steps"):
        state, prog = cell.readings(compiled, state, root,
                                    ctx.traffic["check_steps"])
    # the window's own feed, compiled and warm: one more input draw
    jax.block_until_ready(cell.make_inputs(root, np.int32(0)))
    jax.block_until_ready(state)
    setup_s = time.perf_counter() - ctx.t0

    tracer = H.Tracer(ctx.trace, ctx.traffic.get("trace_s"))
    r, rounds, bad = ctx.traffic["check_steps"], 0, 0
    traced = None                   # (rounds, seconds) of the traced part
    tracer.start()
    with ctx.counter.window():
        t_start = time.perf_counter()
        while True:
            with H.span("bench.make_inputs"):
                batch, key = cell.make_inputs(root, np.int32(r))
            with H.span("bench.round"):
                state, m = compiled(state, batch, key)
            with H.span("bench.block"):
                jax.block_until_ready((state, m))
            rounds, r = rounds + 1, r + 1
            bad += not np.isfinite(float(m["client_loss"])) or \
                not np.isfinite(float(m["server_loss"]))
            if tracer.tick(time.perf_counter() - t_start):
                traced = (rounds, tracer.closed_at)
            if time.perf_counter() - t_start >= ctx.seconds:
                break
        elapsed = time.perf_counter() - t_start
    if tracer.span is not None:
        tracer.stop(elapsed)
        traced = (rounds, elapsed)
    record = {"kind": "fed_round"}
    if tracer.dir:
        # the per-layer metrics read the traced part of the window only:
        # stopping the profiler takes window time
        record |= {"steps": traced[0], "window_s": traced[1],
                   "trace": tracer.load()}
    fl = cell.model.fed_round_flops(ctx.cfg_json, ctx.traffic)
    record["model_flops"] = fl["total"] * record.get("steps", rounds)
    H.log("window", rounds=rounds, seconds=elapsed,
          **{k: float(v) for k, v in m.items()},
          round_tflop=fl["total"] / 1e12,
          client_share=fl["client"] / fl["total"])
    peak_bytes = ctx.memory_peak()
    del state, m, compiled, batch, key
    if ctx.trace:
        # compiled only, never run: it adds nothing to the device's peak
        record["client_peak_bytes"] = client_program_bytes(cell,
                                                           root)["total"]

    t_ref = time.perf_counter()
    with H.span("bench.reference"):
        ref = cell.reference(ctx.registry, root)
    numbers, notes = H.training_numbers(prog, ref)
    H.log("readings", program=prog["loss"], reference=ref["loss"],
          reference_s=time.perf_counter() - t_ref)
    return H.Result(
        attempted=rounds, failed=int(bad),
        metrics={"setup_s": setup_s, "round_s": elapsed / rounds},
        checks=H.checks_from(numbers, ctx.cell["limits"], notes),
        record=record, memory_peak_bytes=peak_bytes)


def client_program_bytes(cell: FedCell, root) -> dict:
    """``memory_analysis()`` of the client side of the round for a cohort
    of one (what one edge client holds), compiled through the function
    the round itself uses."""
    from repro.core import protocols as P
    from repro.core import zo as Z
    from repro.optim.optimizers import make_optimizer

    t = cell.traffic
    run_one, _ = P._make_cohort_trajectory(
        cell.api, "heron", Z.ZOConfig(mu=t["mu"], n_pairs=t["n_pairs"]),
        P.FedConfig(n_clients=1, h=t["h"]), make_optimizer("zo_sgd", cell.lr),
        "seed_replay", cell.lr)
    st = jax.eval_shape(cell.init_state, root)["client"]
    batch, key = jax.eval_shape(cell.make_inputs, root, np.int32(0))
    one = jax.tree.map(lambda s: jax.ShapeDtypeStruct((1,) + s.shape[1:],
                                                      s.dtype), batch)
    ma = jax.jit(run_one).lower(st, one, key).compile().memory_analysis()
    return {"argument": ma.argument_size_in_bytes,
            "output": ma.output_size_in_bytes,
            "temp": ma.temp_size_in_bytes,
            "alias": ma.alias_size_in_bytes,
            "total": ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes}


def calibrate(ctx, seeds, control_seeds):
    """Readings for the limits: the program against the reference on
    ``seeds``; on ``control_seeds`` the control (the reference in float8
    in the program's place) and the planted fault of half of every
    micro-batch left out.  A state left unchanged reads 1 by construction
    (every kept leaf's change is 0) and needs no run."""
    cell = FedCell(ctx.model, ctx.cfg_json, ctx.traffic)
    compiled = cell.compile(H.root_key(seeds[0]))
    yield {"client_program_bytes": client_program_bytes(
        cell, H.root_key(seeds[0]))}
    for seed in seeds:
        root = H.root_key(seed)
        state = cell.init_state(root)
        _, prog = cell.readings(compiled, state, root,
                                ctx.traffic["check_steps"])
        ref = cell.reference(ctx.registry, root)
        numbers, notes = H.training_numbers(prog, ref)
        yield {"seed": seed, "what": "program", **numbers, "notes": notes,
               "loss": prog["loss"], "ref_loss": ref["loss"]}
    for seed in control_seeds:
        root = H.root_key(seed)
        ref = cell.reference(ctx.registry, root)
        for what, rd in (
                ("control_fp8", cell.reference(ctx.registry, root, "fp8")),
                ("fault_half_batch", cell.reference(ctx.registry, root,
                                                    half_batch=True))):
            numbers, notes = H.training_numbers(rd, ref)
            yield {"seed": seed, "what": what, **numbers, "notes": notes}
