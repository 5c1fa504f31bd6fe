"""Serving driver: fused single-jit decode with continuous batching.

Decoder-only archs run through :class:`repro.core.decode.DecodeEngine`:
block prefill into slot-paged KV/recurrent caches, then fused K-step
decode segments under one jit (early EOS exit, threefry-keyed greedy /
temperature / top-k / top-p sampling), with finished slots drained and
refilled from the request queue between segments.  Enc-dec archs keep
their cross-attended token loop but consume the prompt in one jitted
``lax.scan`` and route through the same sampler.

Example (CPU, reduced config):
  PYTHONPATH=src python -m repro.launch.serve --arch xlstm-1.3b --smoke \
      --batch 4 --prompt-len 16 --max-new 16 --requests 12 \
      --sample --temperature 0.8 --top-k 40
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.registry import ARCH_IDS, get_config
from repro.core import decode as D
from repro.core import protocols as P
from repro.distributed.sharding import AxisRules
from repro.launch import compile_cache
from repro.models import transformer as T


def build_sampler(args) -> D.SamplerConfig:
    return D.SamplerConfig(greedy=not args.sample,
                           temperature=args.temperature,
                           top_k=args.top_k, top_p=args.top_p)


def _serve_enc_dec(cfg, args, sampler):
    """Enc-dec serving: jitted lax.scan prompt consume + token loop
    (cross-attention decode), sampling through the shared sampler."""
    rules = AxisRules(mesh=None)
    params = T.init_lm(jax.random.PRNGKey(0), cfg)
    serve = jax.jit(P.make_serve_step(cfg, rules))
    consume = jax.jit(D.make_prompt_consume(cfg, rules))
    total = args.prompt_len + args.max_new
    caches = P.init_serve_caches(cfg, args.batch, total)
    caches["enc_out"] = jax.random.normal(
        jax.random.PRNGKey(3), caches["enc_out"].shape
    ).astype(caches["enc_out"].dtype)
    prompt = jax.random.randint(jax.random.PRNGKey(1),
                                (args.batch, args.prompt_len), 0,
                                cfg.vocab)
    keys = jax.vmap(jax.random.fold_in)(
        jnp.broadcast_to(jax.random.PRNGKey(args.seed),
                         (args.batch, 2)).astype(jnp.uint32),
        jnp.arange(args.batch))

    @jax.jit
    def pick(logits, step):
        sk = jax.vmap(jax.random.fold_in)(keys, jnp.full((args.batch,),
                                                         step, jnp.int32))
        return sample_tok(logits, sk)

    def sample_tok(logits, sk):
        return D.sample_logits(logits[:, -1, :cfg.vocab].astype(
            jnp.float32), sk, sampler)[:, None]

    t0 = time.time()
    logits, caches = consume(params, caches, prompt)
    tok = pick(logits, 0)
    tok.block_until_ready()
    t_prefill = time.time() - t0

    out_toks = [tok]
    t0 = time.time()
    for step in range(1, args.max_new):
        logits, caches = serve(params, caches, tok)
        tok = pick(logits, step)
        out_toks.append(tok)
    tok.block_until_ready()
    t_decode = time.time() - t0
    gen = jnp.concatenate(out_toks, axis=1)
    pre_tps = args.batch * args.prompt_len / max(t_prefill, 1e-9)
    dec_tps = args.batch * len(out_toks) / max(t_decode, 1e-9)
    print(f"[serve] enc-dec generated {gen.shape}: prefill "
          f"{t_prefill:.2f}s ({pre_tps:.1f} tok/s), decode "
          f"{t_decode:.2f}s ({dec_tps:.1f} tok/s)")
    print(gen[0])
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-1.5b", choices=list(ARCH_IDS))
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4,
                    help="decode slots (concurrent requests)")
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", "--gen", dest="max_new", type=int,
                    default=16, help="per-request token budget")
    ap.add_argument("--requests", type=int, default=0,
                    help="queue length (0 = one wave of --batch)")
    ap.add_argument("--segment", type=int, default=16,
                    help="fused decode steps per segment")
    ap.add_argument("--sample", action="store_true",
                    help="sample instead of greedy argmax")
    ap.add_argument("--temperature", type=float, default=1.0)
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--top-p", type=float, default=1.0)
    ap.add_argument("--eos-id", type=int, default=-1,
                    help="stop a request when it emits this token")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    compile_cache.enable()
    cfg = get_config(args.arch, smoke=args.smoke)
    sampler = build_sampler(args)
    if cfg.enc_dec or cfg.frontend is not None:
        print("[serve] modality archs: serving the text decoder only")
    if cfg.enc_dec:
        return _serve_enc_dec(cfg, args, sampler)

    rules = AxisRules(mesh=None)
    params = T.init_lm(jax.random.PRNGKey(0), cfg)
    n_req = args.requests or args.batch
    # mixed request lengths: cycle through 1/2, 3/4, 1/1 of --prompt-len
    rng = np.random.default_rng(args.seed)
    lengths = [max(1, args.prompt_len * f // 4) for f in (2, 3, 4)]
    engine = D.DecodeEngine(
        params, cfg, rules, slots=args.batch,
        capacity=args.prompt_len + args.max_new,
        segment_len=args.segment, sampler=sampler, eos_id=args.eos_id,
        seed=args.seed)
    prompts = {}
    for i in range(n_req):
        plen = lengths[i % len(lengths)]
        prompt = rng.integers(0, cfg.vocab, size=plen)
        rid = engine.submit(prompt, args.max_new)
        prompts[rid] = prompt

    t0 = time.time()
    out = engine.run()
    wall = time.time() - t0
    total_new = sum(len(t) for t in out.values())
    print(f"[serve] {len(out)} requests, {total_new} tokens in "
          f"{wall:.2f}s — sustained {total_new / max(wall, 1e-9):.1f} "
          f"tok/s ({engine.segments} fused segments of "
          f"{args.segment}, prefill {engine.prefill_tokens} tok)")
    rid0 = min(out)
    print(f"request {rid0} ({len(prompts[rid0])}-tok prompt):",
          list(out[rid0])[:24])
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
