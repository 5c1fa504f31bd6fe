"""Plain reference of a DeepSeek-V3-shaped model (Moonlight-16B-A3B) on one
chip's share, and of one HERON round over it, in straightforward
``jax.numpy``.  It imports nothing of the program: the model is restated
here from the DeepSeek-V2/V3 description, and the ZO noise stream and
the round's protocol are the GPT-2 reference's (``gpt2.py`` beside this
file), with the AdamW step restated for the untied unembedding.

Model, per token (configuration keys of ``config.json``):

* RMSNorm ``x / sqrt(mean(x^2) + eps) * w``, ``w`` stored as ``1 +
  scale`` (the program's layout; scale 0 at init);
* multi-head latent attention with no q low-rank path: ``q = x Wq`` (per
  head ``qk_nope_head_dim`` + ``qk_rope_head_dim``); ``x Wkv_a`` gives
  the latent ``c`` (``kv_lora_rank``) and one rope key ``k_r`` shared by
  all heads; ``c = RMSNorm(c)``; ``c Wkv_b`` gives each head's
  ``k_nope`` and ``v`` (``v_head_dim``); keys ``[k_nope, RoPE(k_r)]``,
  queries ``[q_nope, RoPE(q_rope)]``, causal softmax scaled by
  ``1/sqrt(nope + rope)``, ``o Wo``; computed in blocks of queries;
* the first ``first_k_dense_replace`` layers a gated SiLU MLP of
  ``intermediate_size``; the others a mixture of experts: sigmoid scores
  of the router over all ``published.n_routed_experts`` experts, the top
  ``num_experts_per_tok`` chosen on the scores, gates the chosen scores
  normalized over them and scaled by ``routed_scaling_factor``; every
  held expert (the first ``n_routed_experts``) computed densely for all
  tokens, combined by the gates of the tokens that chose it; plus the
  shared experts, one gated MLP of ``n_shared_experts *
  moe_intermediate_size``;
* a final RMSNorm and an untied unembedding on the server; the client's
  aux head projects with its embedding table.

Departures from the source (also in the configuration file): the
selection bias ``e_score_correction_bias`` is zero; RoPE rotates halves
(the source interleaves pairs); the absent experts' part is left out;
weights are random.  Everything is computed in float32 at
``Precision.HIGHEST`` with parameters stored in the configuration's
bfloat16; ``precision="fp8"`` rounds every matrix-product operand to
float8 (e4m3) first: the control.
"""
from __future__ import annotations

import functools
import importlib.util
import pathlib

import jax
import jax.numpy as jnp

_spec = importlib.util.spec_from_file_location(
    "bench_reference_gpt2_for_deepseek_v3",
    pathlib.Path(__file__).with_name("gpt2.py"))
G = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(G)

HIGHEST = G.HIGHEST
Q_BLOCK = 1024


class Dims:
    def __init__(self, cfg: dict):
        self.heads = cfg["num_attention_heads"]
        self.nope = cfg["qk_nope_head_dim"]
        self.rope = cfg["qk_rope_head_dim"]
        self.v = cfg["v_head_dim"]
        self.rank = cfg["kv_lora_rank"]
        self.theta = float(cfg["rope_theta"])
        self.eps = cfg["rms_norm_eps"]
        self.top_k = cfg["num_experts_per_tok"]
        self.experts = cfg["published"]["n_routed_experts"]
        self.held = cfg["n_routed_experts"]
        self.scale = cfg["routed_scaling_factor"]
        self.vocab = cfg["vocab_size"]


def rmsnorm(p, x, eps):
    var = jnp.mean(jnp.square(x), -1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + p["scale"])


def rope(x, pos, theta):
    """x: (B, S, H, D), halves rotated; pos: (S,)."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * freq
    sin, cos = jnp.sin(ang)[None, :, None], jnp.cos(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(q, k, v, cast):
    """Causal softmax attention, (B, S, H, D) x (B, S, H, D/Dv), in blocks
    of ``Q_BLOCK`` queries (each recomputed for the gradient)."""
    B, S, H, D = q.shape
    blk = min(S, Q_BLOCK)
    scale = D ** -0.5
    kpos = jnp.arange(S)

    @jax.checkpoint
    def one(args):
        qb, start = args
        s = jnp.einsum("bqhd,bkhd->bhqk", cast(qb), cast(k),
                       precision=HIGHEST) * scale
        qpos = start + jnp.arange(blk)
        s = jnp.where(qpos[:, None] >= kpos[None, :], s, -jnp.inf)
        a = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", cast(a), cast(v),
                          precision=HIGHEST)

    qs = jnp.moveaxis(q.reshape(B, S // blk, blk, H, D), 1, 0)
    out = jax.lax.map(one, (qs, jnp.arange(0, S, blk)))
    return jnp.moveaxis(out, 0, 1).reshape(B, S, H, v.shape[-1])


def mla(p, x, dm: Dims, cast):
    B, S, _ = x.shape
    H, dn, r = dm.heads, dm.nope, dm.rank

    def mm(a, w):
        return jnp.matmul(cast(a), cast(w), precision=HIGHEST)

    q = mm(x, p["wq"]["w"]).reshape(B, S, H, dn + dm.rope)
    kv_a = mm(x, p["wkv_a"]["w"])
    c = rmsnorm(p["norm_kv"], kv_a[..., :r], dm.eps)
    kv = mm(c, p["wkv_b"]["w"]).reshape(B, S, H, dn + dm.v)
    pos = jnp.arange(S)
    q = jnp.concatenate([q[..., :dn], rope(q[..., dn:], pos, dm.theta)], -1)
    k_r = rope(kv_a[..., None, r:], pos, dm.theta)
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_r, (B, S, H, dm.rope))], -1)
    o = attention(q, k, kv[..., dn:], cast)
    return mm(o.reshape(B, S, H * dm.v), p["wo"]["w"])


def gated_mlp(p, x, cast):
    def mm(a, w):
        return jnp.matmul(cast(a), cast(w), precision=HIGHEST)
    return mm(jax.nn.silu(mm(x, p["gate"]["w"])) * mm(x, p["up"]["w"]),
              p["down"]["w"])


def route(w_router, x, dm: Dims, cast):
    """Gates (T, E) of every expert, zero where not chosen."""
    scores = jax.nn.sigmoid(jnp.matmul(cast(x), cast(w_router),
                                       precision=HIGHEST))
    top, idx = jax.lax.top_k(scores, dm.top_k)
    gates = top / jnp.sum(top, -1, keepdims=True) * dm.scale
    return jnp.sum(jax.nn.one_hot(idx, dm.experts) * gates[..., None], 1)


def moe(p, x, dm: Dims, cast):
    B, S, d = x.shape
    xf = x.reshape(-1, d)
    comb = route(p["router"], xf, dm, cast)[:, :dm.held]       # (T, held)

    def mm(eq, a, w):
        return jnp.einsum(eq, cast(a), cast(w), precision=HIGHEST)

    h = jax.nn.silu(mm("td,edf->tef", xf, p["gate"])) \
        * mm("td,edf->tef", xf, p["up"])
    y = mm("tef,efd->td", h * comb[..., None], p["down"])
    return y.reshape(B, S, d) + gated_mlp(p["shared"], x, cast)


def block(p, x, dm: Dims, cast):
    x = x + mla(p["attn"], rmsnorm(p["norm1"], x, dm.eps), dm, cast)
    h = rmsnorm(p["norm2"], x, dm.eps)
    if "moe" in p:
        return x + moe(p["moe"], h, dm, cast)
    return x + gated_mlp(p["mlp"], h, cast)


def stack(segments, x, dm: Dims, cast):
    body = jax.checkpoint(functools.partial(block, dm=dm, cast=cast))
    for seg in segments:
        for blk in seg:
            x, _ = jax.lax.scan(lambda c, p: (body(p, c), None), x, blk)
    return x


def client_forward(cp, ids, dm, cast):
    return stack(cp["layers"], cp["embed"]["table"][ids], dm, cast)


def aux_loss(cp, smashed, labels, dm, cast):
    x = stack(cp["aux"].get("layers", []), smashed, dm, cast)
    x = rmsnorm(cp["aux"]["norm"], x, dm.eps)
    return G.lm_loss(G.unembed(x, cp["embed"]["table"], cast), labels,
                     dm.vocab)


def server_loss(sp, smashed, labels, dm, cast):
    x = rmsnorm(sp["final_norm"], stack(sp["layers"], smashed, dm, cast),
                dm.eps)
    logits = jnp.matmul(cast(x), cast(sp["unembed"]), precision=HIGHEST)
    return G.lm_loss(logits, labels, dm.vocab)


class Round(G.Round):
    """The GPT-2 reference's round (client pairs, AdamW server steps over
    the clients in turn, the Fed-Server's replay) over this model."""

    def __init__(self, cfg: dict, traffic: dict, precision: str = "f32"):
        self.dm = Dims(cfg)
        self.mu = traffic["mu"]
        self.client_lr = traffic["client_lr"]
        self.server_lr = traffic["server_lr"]
        cast = G._cast(precision)
        self._pair = jax.jit(functools.partial(self._pair_losses, cast=cast))
        # the server's parameters and AdamW state are donated: a second
        # copy of them does not fit beside the step on one chip
        self._server_donated = jax.jit(
            functools.partial(self._server_step, cast=cast),
            donate_argnums=(0, 1))
        self._replay = jax.jit(self._replay_fn)

    def _server(self, server, opt, *rest):
        if any(a is b for a, b in zip(jax.tree.leaves(opt["m"]),
                                      jax.tree.leaves(opt["v"]))):
            opt = dict(opt, v=jax.tree.map(jnp.copy, opt["v"]))
        return self._server_donated(server, opt, *rest)

    def _pair_losses(self, client, ids, labels, pair_seed, cast):
        dts = [x.dtype for x in jax.tree.leaves(client)]
        cp = G.f32(client)
        s0 = client_forward(cp, ids, self.dm, cast)
        l0 = aux_loss(cp, s0, labels, self.dm, cast)
        cq = G.perturbed(cp, dts, pair_seed, self.mu)
        s1 = client_forward(cq, ids, self.dm, cast)
        l1 = aux_loss(cq, s1, labels, self.dm, cast)
        return l0, l1, s0

    def _server_step(self, server, opt, table, smashed, labels, cast):
        del table                       # untied: the server's unembedding
        loss, g = jax.value_and_grad(
            lambda sp: server_loss(sp, smashed, labels, self.dm, cast))(
            G.f32(server))
        t = opt["step"] + 1
        b1t = 1.0 - G.ADAM_B1 ** t.astype(jnp.float32)
        b2t = 1.0 - G.ADAM_B2 ** t.astype(jnp.float32)
        m = jax.tree.map(lambda m_, g_: G.ADAM_B1 * m_ + (1 - G.ADAM_B1)
                         * g_, opt["m"], g)
        v = jax.tree.map(lambda v_, g_: G.ADAM_B2 * v_ + (1 - G.ADAM_B2)
                         * jnp.square(g_), opt["v"], g)
        new = jax.tree.map(
            lambda p, m_, v_: (p.astype(jnp.float32) - self.server_lr
                               * (m_ / b1t) / (jnp.sqrt(v_ / b2t)
                                               + G.ADAM_EPS)).astype(p.dtype),
            server, m, v)
        return new, {"step": t, "m": m, "v": v}, loss
