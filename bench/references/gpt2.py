"""Plain reference of the repository's GPT-2-shaped model, and of one HERON
round over it, in straightforward ``jax.numpy``.  It imports nothing of
the program: the model and the protocol are restated here from their
description.

Model (as the program defines it; its departures from OpenAI's GPT-2 are
listed in the configuration files): pre-norm blocks with LayerNorm (eps
1e-5, scale and bias), rotary position embeddings (theta 10000, halves
rotated) in place of learned positions, causal softmax attention scaled by
``head_dim ** -0.5``, a non-gated MLP with tanh-approximated GELU, no
projection biases, and the vocabulary projection tied to the embedding.

Parameters arrive in the program's tree layout: a list of stack segments,
each a tuple of block dicts whose leaves carry a leading axis of
repeats.  Everything is computed in float32 at ``Precision.HIGHEST``;
the stored parameters keep the dtype the configuration states, so an
update is rounded to it as the program's is.  ``precision="fp8"`` rounds
every matrix-product operand to float8 (e4m3) first (its gradient passes
through in float32): the control, one step of precision below the
configuration's bfloat16.

The round restates HERON with the lean seed-replay uplink at one local
step and one ZO pair (the cells' shape):

* client ``i`` draws its pair seed ``fold(fold(fold(seed(key), i), 0), 0)``;
  every client leaf gets ``pair_seed + fnv1a(path)`` and unit-variance
  uniform noise on its canonical 2-D view from a counter hash of
  (seed, row, col); the coefficient is ``(l(theta + mu U) - l(theta)) / mu``
  from the aux head's loss; norm parameters are perturbed in their
  stored dtype (the program materializes them so), matrices in float32;
* the server takes, client after client, one AdamW step on its loss over
  that client's clean cut-layer activations, with the vocabulary
  projection tied to the (unchanged) global client embedding;
* the Fed-Server adds ``-lr * mean_i(coeff_i U_i)`` to the client tree in
  float32 and stores it in the client's dtype.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
SQRT3 = 1.7320508075688772
ROPE_THETA = 10000.0
LN_EPS = 1e-5
ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8


# ---------------------------------------------------------------------------
# the ZO noise stream, restated
# ---------------------------------------------------------------------------

def path_hash(path: str) -> int:
    h = 2166136261
    for ch in path.encode():
        h = ((h ^ ch) * 16777619) & 0xFFFFFFFF
    return h & 0x7FFFFFFF


def fold_seed(seed, i):
    s = jnp.asarray(seed, jnp.int32).astype(jnp.uint32)
    x = (s ^ (jnp.asarray(i, jnp.int32).astype(jnp.uint32)
              * jnp.uint32(0x9E3779B9))) + jnp.uint32(0x7F4A7C15)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(0x2C1B3C6D)
    x = x ^ (x >> 12)
    return x.astype(jnp.int32)


def seed_from_key(key):
    kd = jnp.reshape(key, (-1,)).astype(jnp.uint32)
    return (kd[0] ^ kd[-1]).astype(jnp.int32)


def uniform_noise(seed, rows: int, cols: int):
    r = jax.lax.broadcasted_iota(jnp.uint32, (rows, cols), 0)
    c = jax.lax.broadcasted_iota(jnp.uint32, (rows, cols), 1)
    s = jnp.asarray(seed).astype(jnp.uint32)
    x = (r * jnp.uint32(0x9E3779B9)) ^ (c * jnp.uint32(0x85EBCA6B))
    x = x ^ (s * jnp.uint32(0x27D4EB2F) + jnp.uint32(0x165667B1))
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    x = x ^ (x >> 16)
    u01 = (x >> 8).astype(jnp.int32).astype(jnp.float32) * (2.0 ** -24)
    return (u01 * 2.0 - 1.0) * SQRT3


def leaf_noise(seed, shape):
    shape = tuple(shape) or (1,)
    rows = math.prod(shape[:-1]) if len(shape) > 1 else 1
    return uniform_noise(seed, rows, shape[-1]).reshape(shape)


def paths(tree) -> list[str]:
    """'/'-joined dict keys and sequence indices of every leaf, in
    ``jax.tree`` leaf order."""
    out = []
    for kp, _ in jax.tree_util.tree_flatten_with_path(tree)[0]:
        parts = []
        for k in kp:
            parts.append(str(getattr(k, "key", getattr(k, "idx", k))))
        out.append("/".join(parts))
    return out


def _is_norm(path: str) -> bool:
    parts = path.split("/")
    return len(parts) >= 2 and parts[-2].startswith("norm")


def perturbed(tree_f32, stored_dtypes, pair_seed, mu):
    """theta + mu U(pair_seed + hash(path)) for every leaf."""
    leaves, tdef = jax.tree.flatten(tree_f32)
    out = []
    for p, x, dt in zip(paths(tree_f32), leaves, stored_dtypes):
        y = x + mu * leaf_noise(pair_seed + jnp.int32(path_hash(p)), x.shape)
        if _is_norm(p):
            y = y.astype(dt).astype(jnp.float32)
        out.append(y)
    return jax.tree.unflatten(tdef, out)


def direction(tree, pair_seed):
    leaves, tdef = jax.tree.flatten(tree)
    return jax.tree.unflatten(tdef, [
        leaf_noise(pair_seed + jnp.int32(path_hash(p)), x.shape)
        for p, x in zip(paths(tree), leaves)])


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------

@jax.custom_vjp
def _fp8(x):
    return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)


# the operand is rounded; its cotangent passes through in float32, so the
# backward products see the rounded operands and gradients do not
# underflow in float8's range
_fp8.defvjp(lambda x: (_fp8(x), None), lambda _, g: (g,))


def _cast(precision: str):
    if precision == "f32":
        return lambda x: x
    if precision == "fp8":
        return _fp8
    raise ValueError(precision)


def f32(tree):
    return jax.tree.map(lambda x: x.astype(jnp.float32), tree)


def layernorm(p, x):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + LN_EPS) * p["scale"] + p["bias"]


def gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def rope(x, pos):
    """x: (B, S, H, D); pos: (S,)."""
    half = x.shape[-1] // 2
    freq = ROPE_THETA ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * freq           # (S, half)
    sin, cos = jnp.sin(ang)[None, :, None], jnp.cos(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def block(p, x, n_heads: int, cast):
    """One pre-norm block; p's leaves are one repeat's."""
    B, S, d = x.shape
    hd = d // n_heads

    def mm(a, w):
        return jnp.matmul(cast(a), cast(w), precision=HIGHEST)

    h = layernorm(p["norm1"], x)
    q = mm(h, p["attn"]["wq"]["w"]).reshape(B, S, n_heads, hd)
    k = mm(h, p["attn"]["wk"]["w"]).reshape(B, S, n_heads, hd)
    v = mm(h, p["attn"]["wv"]["w"]).reshape(B, S, n_heads, hd)
    pos = jnp.arange(S)
    q, k = rope(q, pos), rope(k, pos)
    s = jnp.einsum("bqhd,bkhd->bhqk", cast(q), cast(k),
                   precision=HIGHEST) * hd ** -0.5
    causal = pos[:, None] >= pos[None, :]
    s = jnp.where(causal, s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", cast(a), cast(v),
                   precision=HIGHEST).reshape(B, S, d)
    x = x + mm(o, p["attn"]["wo"]["w"])
    h = layernorm(p["norm2"], x)
    u = gelu(mm(h, p["mlp"]["up"]["w"]))
    return x + mm(u, p["mlp"]["down"]["w"])


def stack(segments, x, n_heads: int, cast, remat: bool = False):
    body = functools.partial(block, n_heads=n_heads, cast=cast)
    if remat:
        body = jax.checkpoint(body)
    for seg in segments:
        for blk in seg:
            x, _ = jax.lax.scan(lambda c, p: (body(p, c), None), x, blk)
    return x


def lm_loss(logits, labels, vocab: int):
    lp = jax.nn.log_softmax(logits[..., :vocab], axis=-1)
    return -jnp.mean(jnp.take_along_axis(lp, labels[..., None], -1))


def unembed(x, table, cast):
    return jnp.matmul(cast(x), cast(table).T, precision=HIGHEST)


def client_forward(cp, ids, n_heads, cast):
    return stack(cp["layers"], cp["embed"]["table"][ids], n_heads, cast)


def aux_loss(cp, smashed, labels, n_heads, vocab, cast):
    x = stack(cp["aux"].get("layers", []), smashed, n_heads, cast)
    x = layernorm(cp["aux"]["norm"], x)
    return lm_loss(unembed(x, cp["embed"]["table"], cast), labels, vocab)


def server_loss(sp, table, smashed, labels, n_heads, vocab, cast):
    x = stack(sp["layers"], smashed, n_heads, cast, remat=True)
    x = layernorm(sp["final_norm"], x)
    return lm_loss(unembed(x, table, cast), labels, vocab)


def full_logits(params, ids, n_heads: int, precision: str = "f32"):
    """Whole-model logits (B, S, V_padded) for token ids (B, S)."""
    cast = _cast(precision)
    cp, sp = f32(params["client"]), f32(params["server"])
    x = client_forward(cp, ids, n_heads, cast)
    x = stack(sp["layers"], x, n_heads, cast)
    x = layernorm(sp["final_norm"], x)
    return unembed(x, cp["embed"]["table"], cast)


# ---------------------------------------------------------------------------
# one HERON round
# ---------------------------------------------------------------------------

class Round:
    """``round(state, batch, key) -> (state, metrics)`` with the program's
    state layout ``{"client", "server", "opt_server": {"step", "m",
    "v"}}`` and batch ``{"inputs", "labels"}`` of shape (N, 1, B, S), for
    the configuration ``cfg`` (GPT-2's key names) under the traffic mix
    ``traffic`` (``mu``, ``client_lr``, ``server_lr``)."""

    def __init__(self, cfg: dict, traffic: dict, precision: str = "f32"):
        self.n_heads, self.vocab = cfg["n_head"], cfg["vocab_size"]
        self.mu = traffic["mu"]
        self.client_lr = traffic["client_lr"]
        self.server_lr = traffic["server_lr"]
        cast = _cast(precision)
        self._pair = jax.jit(functools.partial(self._pair_losses, cast=cast))
        self._server = jax.jit(functools.partial(self._server_step,
                                                 cast=cast))
        self._replay = jax.jit(self._replay_fn)

    def _pair_losses(self, client, ids, labels, pair_seed, cast):
        dts = [x.dtype for x in jax.tree.leaves(client)]
        cp = f32(client)
        s0 = client_forward(cp, ids, self.n_heads, cast)
        l0 = aux_loss(cp, s0, labels, self.n_heads, self.vocab, cast)
        cq = perturbed(cp, dts, pair_seed, self.mu)
        s1 = client_forward(cq, ids, self.n_heads, cast)
        l1 = aux_loss(cq, s1, labels, self.n_heads, self.vocab, cast)
        return l0, l1, s0

    def _server_step(self, server, opt, table, smashed, labels, cast):
        loss, g = jax.value_and_grad(
            lambda sp: server_loss(sp, table.astype(jnp.float32), smashed,
                                   labels, self.n_heads, self.vocab, cast))(
            f32(server))
        t = opt["step"] + 1
        b1t = 1.0 - ADAM_B1 ** t.astype(jnp.float32)
        b2t = 1.0 - ADAM_B2 ** t.astype(jnp.float32)
        m = jax.tree.map(lambda m_, g_: ADAM_B1 * m_ + (1 - ADAM_B1) * g_,
                         opt["m"], g)
        v = jax.tree.map(lambda v_, g_: ADAM_B2 * v_
                         + (1 - ADAM_B2) * jnp.square(g_), opt["v"], g)
        new = jax.tree.map(
            lambda p, m_, v_: (p.astype(jnp.float32) - self.server_lr
                               * (m_ / b1t) / (jnp.sqrt(v_ / b2t)
                                               + ADAM_EPS)).astype(p.dtype),
            server, m, v)
        return new, {"step": t, "m": m, "v": v}, loss

    def _replay_fn(self, client, pair_seeds, scales):
        acc = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), client)
        for i in range(pair_seeds.shape[0]):
            u = direction(client, pair_seeds[i])
            acc = jax.tree.map(lambda a, u_: a + scales[i] * u_, acc, u)
        return jax.tree.map(lambda p, a: (p.astype(jnp.float32)
                                          + a).astype(p.dtype), client, acc)

    def __call__(self, state, batch, key):
        inputs, labels = batch["inputs"], batch["labels"]
        n = inputs.shape[0]
        if inputs.shape[1] != 1:
            raise ValueError("the reference round restates one local step")
        cseeds = fold_seed(seed_from_key(key), jnp.arange(n))
        pair_seeds = fold_seed(fold_seed(cseeds, 0), 0)
        l0s, coeffs, smashed = [], [], []
        for i in range(n):
            l0, l1, s0 = self._pair(state["client"], inputs[i, 0],
                                    labels[i, 0], pair_seeds[i])
            l0s.append(l0)
            coeffs.append((l1 - l0) / self.mu)
            smashed.append(s0)
        server, opt, s_losses = state["server"], state["opt_server"], []
        table = state["client"]["embed"]["table"]
        for i in range(n):
            server, opt, sl = self._server(server, opt, table, smashed[i],
                                           labels[i, 0])
            s_losses.append(sl)
        scales = -self.client_lr * jnp.stack(coeffs) / n
        client = self._replay(state["client"], pair_seeds, scales)
        metrics = {"client_loss": jnp.mean(jnp.stack(l0s)),
                   "server_loss": jnp.mean(jnp.stack(s_losses))}
        return {"client": client, "server": server, "opt_server": opt}, \
            metrics
