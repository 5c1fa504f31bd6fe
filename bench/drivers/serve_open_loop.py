"""Driver ``serve_open_loop``: the program's ``DecodeEngine`` (continuous
batching, greedy) under open-loop arrivals.

The traffic mix fixes the arrival rate, the prompt-length buckets and
their weights, and the lognormal output lengths.  Every seed serves the
same schedule of prompt lengths, output lengths and Poisson arrival
times (drawn once from the mix's own ``size_seed``) with prompt tokens
drawn from ``--seed``: near capacity the order of the work moves the
latency tail by a third, so seeds change the tokens and not the work.
Arrivals are open loop: a request is submitted once
its scheduled time has passed, whatever the engine is doing, and its
latency counts from the scheduled time.  Requests due in the window are
attempted; after the window no request arrives, in-flight ones drain,
and one not finished ``drain_s`` after the window has failed.

A request's first token is handed out by the ``engine.step()`` that
admitted it, its last by the step that finished it:

* ``ttft_p95_ms``: 95th percentile over the attempted requests of
  (return of the admitting step - scheduled arrival);
* ``tpot_p95_ms``: 95th percentile of (last - first hand-out) /
  (tokens - 1).

After the window, the plain reference runs once over a sample of the
finished requests drawn from the seed (the longest among them), each
over its prompt and served tokens, and the widest gap by which a served
token's logit lies below the reference's best is compared.
"""
from __future__ import annotations

import collections
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from lib import harness as H


@dataclasses.dataclass
class Req:
    arrival: float          # seconds after the window opens
    prompt: np.ndarray
    max_new: int
    rid: int = -1
    first: float = -1.0     # seconds after the window opens
    last: float = -1.0
    emitted: int = 0
    tokens: list | None = None


def schedule(traffic: dict, seconds: float, seed: int, vocab: int,
             rate: float | None = None) -> list[Req]:
    """The requests due in a window of ``seconds``: their prompt lengths,
    output lengths and arrival times come from the mix's ``size_seed``
    alone, the same for every run; ``seed`` draws the prompts' tokens."""
    rate = traffic["rate_per_s"] if rate is None else rate
    n = max(1, int(round(rate * seconds)))
    fixed = np.random.default_rng(traffic["size_seed"])
    w = np.asarray(traffic["prompt_weights"], float)
    counts = np.floor(w / w.sum() * n).astype(int)
    for i in np.argsort(-(w / w.sum() * n - counts))[: n - counts.sum()]:
        counts[i] += 1
    plens = fixed.permutation(np.repeat(traffic["prompt_buckets"], counts))
    outs = np.clip(np.round(traffic["out_median"] * np.exp(
        traffic["out_sigma"] * fixed.standard_normal(n))),
        traffic["out_min"], traffic["out_max"]).astype(int)
    gaps = fixed.exponential(1.0 / rate, n)
    gaps *= seconds / gaps.sum()
    arrivals = np.cumsum(gaps) - gaps[0]
    rng = np.random.default_rng(seed)
    return [Req(float(a), rng.integers(0, vocab, size=int(p), dtype=np.int32),
                int(o)) for a, p, o in zip(arrivals, plens, outs)]


class Server:
    """The program's engine over the cell's weights, made from the seed;
    what depends on the model comes from its family's module
    (``model``)."""

    def __init__(self, model, cfg_json: dict, traffic: dict, root):
        from repro.core import decode as D

        self.model, self.cfg_json, self.traffic = model, cfg_json, traffic
        self.cfg = model.program_config(cfg_json)
        shapes = model.param_shapes(self.cfg)
        self.init_params = jax.jit(lambda r: model.init_params(shapes, r))
        self.params = self.init_params(root)
        self.engine = D.DecodeEngine(
            self.params, self.cfg, slots=traffic["slots"],
            capacity=traffic["capacity"],
            segment_len=traffic["segment_len"],
            sampler=D.SamplerConfig(greedy=True), eos_id=-1, seed=0)

    def warm_up(self):
        """Compile every admission (one program per prompt length) and the
        segment, through the engine's own calls."""
        t = self.traffic
        for p in t["prompt_buckets"]:
            self.engine.submit(np.zeros(p, np.int32), t["segment_len"] + 2)
        while self.engine.pending:
            self.engine.step()

    def close(self):
        del self.engine, self.params


def open_loop(engine, reqs: list[Req], seconds: float, drain_s: float,
              cfg_json: dict, model, on_tick=None) -> dict:
    """Serve ``reqs`` on their schedule; returns the loop's readings,
    with ``steps``: (end, seconds, model FLOPs by ``model``) of every
    engine step.  ``on_tick(t)`` is called after every turn of the
    loop."""
    seg = engine.segment_len
    by_rid: dict[int, Req] = {}
    queued: collections.deque[Req] = collections.deque()  # not admitted
    live: list[Req] = []                                   # admitted
    todo = collections.deque(sorted(reqs, key=lambda r: r.arrival))
    steps, late = [], []
    backlog = {}              # requests waiting for a slot, by window share
    prefill0 = engine.prefill_tokens
    t0 = time.perf_counter()
    while True:
        t = time.perf_counter() - t0
        if on_tick is not None:
            on_tick(t)
        while todo and todo[0].arrival <= t:
            r = todo.popleft()
            with H.span("bench.submit"):
                r.rid = engine.submit(r.prompt, r.max_new)
            late.append(t - r.arrival)
            by_rid[r.rid] = r
            queued.append(r)
        for share in (0.5, 1.0):
            if share not in backlog and t >= share * seconds:
                backlog[share] = len(queued)
        if not engine.pending:
            if not todo:
                break
            with H.span("bench.idle"):
                time.sleep(max(0.0, todo[0].arrival
                               - (time.perf_counter() - t0)))
            continue
        if t > seconds + drain_s:
            break
        s0 = time.perf_counter()
        with H.span("bench.engine_step"):
            done = engine.step()
        s1 = time.perf_counter()
        now, flops = s1 - t0, 0
        # admissions: a FIFO prefix of the queue, told by the prompt tokens
        # the engine prefilled in this step
        admitted = engine.prefill_tokens - prefill0
        prefill0 = engine.prefill_tokens
        while admitted > 0:
            r = queued.popleft()
            admitted -= r.prompt.size
            r.first, r.emitted = now, 1
            flops += model.prefill_flops(cfg_json, r.prompt.size)
            live.append(r)
        if admitted != 0:
            raise RuntimeError("admissions do not match the queue's prefix")
        for r in live:
            k = min(seg, r.max_new - r.emitted)
            flops += sum(model.decode_token_flops(cfg_json, r.prompt.size + g)
                         for g in range(r.emitted, r.emitted + k))
            r.emitted += k
        for d in done:
            r = by_rid.get(d.rid)     # None: left over from an earlier loop
            if r is not None:
                r.last, r.tokens = now, list(d.tokens)
        live = [r for r in live if r.tokens is None]
        steps.append((now, s1 - s0, flops))
    return {"steps": steps, "step_s": sum(d for _, d, _ in steps),
            "late_s": late,
            "elapsed": time.perf_counter() - t0,
            "backlog_half": backlog.get(0.5, 0),
            "backlog_end": backlog.get(1.0, 0)}


def latencies(reqs: list[Req], worst: float) -> tuple[np.ndarray, ...]:
    """TTFT and TPOT per attempted request, in seconds; a request that
    never finished counts as ``worst``."""
    ttft = np.asarray([r.first - r.arrival if r.first >= 0 else worst
                       for r in reqs])
    tpot = np.asarray([(r.last - r.first) / max(len(r.tokens) - 1, 1)
                       if r.tokens is not None else worst for r in reqs])
    return ttft, tpot


def sample(reqs: list[Req], seed: int, tokens: int) -> list[Req]:
    """Finished requests drawn from the seed, the longest first, until
    ``tokens`` served tokens are in the sample."""
    done = [r for r in reqs if r.tokens is not None]
    if not done:
        return []
    longest = max(done, key=lambda r: r.prompt.size + len(r.tokens))
    rest = [r for r in done if r is not longest]
    order = np.random.default_rng(seed + 1).permutation(len(rest))
    out, n = [longest], len(longest.tokens)
    for i in order:
        if n >= tokens:
            break
        out.append(rest[i])
        n += len(rest[i].tokens)
    return out


class Reference:
    """The plain reference's logits over whole served sequences, each
    padded at its end to ``length`` (the slot capacity): the model is
    causal, so the padding changes no position before it, and one
    program serves every request."""

    def __init__(self, registry, cfg_json: dict, length: int):
        self.ref = registry.reference(cfg_json["reference"])
        self.n_heads, self.vocab = cfg_json["n_head"], cfg_json["vocab_size"]
        self.length = length
        self._fns = {}

    def logits(self, params, ids, precision="f32"):
        if precision not in self._fns:
            self._fns[precision] = jax.jit(
                lambda p, x, prec=precision: self.ref.full_logits(
                    p, x, self.n_heads, prec)[..., : self.vocab])
        return self._fns[precision](params, ids)

    def gaps(self, params, picked: list[Req], precision="f32",
             alter=False) -> dict:
        """Per sampled request, the largest (reference best - logit of the
        served token); with a lower ``precision`` in the program's place,
        of the token that precision puts first at each position instead;
        ``alter`` changes the last served token (a planted fault)."""
        worst, n = 0.0, 0
        for r in picked:
            toks = np.asarray(r.tokens, np.int32)
            if alter:
                toks = toks.copy()
                toks[-1] = (toks[-1] + 1) % self.vocab
            seq = np.concatenate([r.prompt, toks[:-1]])
            at = slice(r.prompt.size - 1, seq.size)
            seq = np.pad(seq, (0, self.length - seq.size))[None]
            ref = self.logits(params, seq)[0, at]
            if precision != "f32":
                toks = np.asarray(jnp.argmax(self.logits(
                    params, seq, precision)[0, at], -1))
            best = jnp.max(ref, -1)
            got = jnp.take_along_axis(ref, jnp.asarray(toks)[:, None],
                                      -1)[:, 0]
            worst = max(worst, float(jnp.max(best - got)))
            n += toks.size
        return {"logit_gap": worst, "tokens": n}


def run(ctx) -> H.Result:
    t = ctx.traffic
    root = H.root_key(ctx.seed)
    with H.span("bench.init"):
        srv = Server(ctx.model, ctx.cfg_json, t, root)
    with H.span("bench.warm_up"):
        srv.warm_up()
    reqs = schedule(t, ctx.seconds, ctx.seed, ctx.cfg_json["vocab_size"])
    engine = ctx.wrap_step(srv.engine, srv)
    setup_s = time.perf_counter() - ctx.t0

    tracer = H.Tracer(ctx.trace, t.get("trace_s"))
    tracer.start()
    with ctx.counter.window():
        loop = open_loop(engine, reqs, ctx.seconds, t["drain_s"],
                         ctx.cfg_json, srv.model, on_tick=tracer.tick)
    tracer.stop(loop["elapsed"])
    record = {"kind": "serve"}
    if tracer.dir:
        traced = [(d, f) for end, d, f in loop["steps"]
                  if end <= tracer.closed_at]
        record |= {"trace": tracer.load(),
                   "step_s": sum(d for d, _ in traced),
                   "model_flops": sum(f for _, f in traced)}
    worst = ctx.seconds + t["drain_s"]
    ttft, tpot = latencies(reqs, worst)
    failed = sum(r.tokens is None for r in reqs)
    late = np.asarray(loop["late_s"])
    # where the longest stall fell in the window: a first run that reads
    # slower than the later ones is told apart from a slow tail by it
    longest = max(loop["steps"], key=lambda s: s[1])
    H.log("window", attempted=len(reqs), failed=failed,
          elapsed=loop["elapsed"], engine_step_s=loop["step_s"],
          ttft_p50_ms=1e3 * float(np.median(ttft)),
          tpot_p50_ms=1e3 * float(np.median(tpot)),
          submit_late_p95_ms=1e3 * float(np.percentile(late, 95)),
          submit_late_max_ms=1e3 * float(late.max()),
          submit_late_max_at_s=reqs[int(late.argmax())].arrival,
          longest_step_ms=1e3 * longest[1],
          longest_step_end_s=longest[0],
          segments=srv.engine.segments,
          decoded_tokens=srv.engine.decoded_tokens)
    peak_bytes = ctx.memory_peak()
    srv.close()
    del engine

    picked = sample(reqs, ctx.seed, t["check_tokens"])
    t_ref = time.perf_counter()
    with H.span("bench.reference"):
        ref = Reference(ctx.registry, ctx.cfg_json, t["capacity"])
        got = ref.gaps(srv.init_params(root), picked)
    H.log("reference", requests=len(picked), **got,
          reference_s=time.perf_counter() - t_ref)
    numbers = {"logit_gap": got["logit_gap"] if picked else float("inf")}
    return H.Result(
        attempted=len(reqs), failed=int(failed),
        metrics={"setup_s": setup_s,
                 "ttft_p95_ms": 1e3 * float(np.percentile(ttft, 95)),
                 "tpot_p95_ms": 1e3 * float(np.percentile(tpot, 95))},
        checks=H.checks_from(numbers, ctx.cell["limits"],
                             {"logit_gap": f"{got['tokens']} tokens of "
                              f"{len(picked)} requests"}),
        record=record, memory_peak_bytes=peak_bytes)


def calibrate(ctx, seeds, control_seeds):
    """Readings for the limits: for each seed, a short window at the
    cell's own load with the program, its sample compared with the
    reference; on ``control_seeds`` also the control (the reference in
    float8 in the program's place, at the same positions) and a served
    token altered where it is produced."""
    t = ctx.traffic
    ref = Reference(ctx.registry, ctx.cfg_json, t["capacity"])
    warm = False
    for seed in list(seeds) + [s for s in control_seeds if s not in seeds]:
        root = H.root_key(seed)
        srv = Server(ctx.model, ctx.cfg_json, t, root)
        if not warm:
            srv.warm_up()
            warm = True
        reqs = schedule(t, t["calibrate_s"], seed,
                        ctx.cfg_json["vocab_size"])
        open_loop(srv.engine, reqs, t["calibrate_s"], t["drain_s"],
                  ctx.cfg_json, srv.model)
        srv.close()
        params = srv.init_params(root)
        picked = sample(reqs, seed, t["check_tokens"])
        rows = [("program", {})] if seed in seeds else []
        if seed in control_seeds:
            rows += [("control_fp8", {"precision": "fp8"}),
                     ("fault_token_altered", {"alter": True})]
        for what, kw in rows:
            yield {"seed": seed, "what": what,
                   "finished": sum(r.tokens is not None for r in reqs),
                   "attempted": len(reqs), **ref.gaps(params, picked, **kw)}
        del params
