"""The program's side of a GPT-2-shaped configuration, and its model FLOPs.

A configuration file names its model family by its ``model`` key; the
drivers take from that family's module under ``models/`` everything that
depends on the model: the program's ``ModelConfig`` (checked against the
published sizes), its federated-round API, the parameter shapes and their
initialisation from the seed, the feed drawn from the seed, and the
model FLOPs.  A model of another family brings a module of its own with
the same functions, and edits nothing here.

Model FLOPs are the work the algorithm needs, not what a compiled program
does: a multiply-add is 2 FLOPs; padding, recompute (remat), the noise
generator, the seed replay and the optimizer count as zero.  Attention is
causal: a query at position ``i`` (0-based) attends ``i + 1`` keys, so a
sequence of ``S`` tokens does ``S (S + 1) / 2`` query-key pairs, each
``2 d`` FLOPs for the scores and ``2 d`` for the weighted sum of values.
The vocabulary projection counts the published vocabulary, not the
padded one.
"""
from __future__ import annotations

import importlib


# ---------------------------------------------------------------------------
# the program
# ---------------------------------------------------------------------------

def program_config(cfg: dict):
    """The program's ModelConfig: its constructor plus the overrides, with
    the published sizes checked against it."""
    mod, fn = cfg["constructor"].split(":")
    mc = getattr(importlib.import_module(mod), fn)().replace(
        **cfg.get("overrides", {}))
    a = cfg["assumed"]
    want = {"n_layers": cfg["n_layer"], "d_model": cfg["n_embd"],
            "n_heads": cfg["n_head"], "d_ff": cfg["n_inner"],
            "vocab": cfg["vocab_size"], "cut_layers": a["cut_layers"],
            "aux_layers": a["aux_layers"], "param_dtype": a["dtype"],
            "compute_dtype": a["dtype"]}
    got = {k: getattr(mc, k) for k in want}
    if got != want:
        raise ValueError(f"constructor {cfg['constructor']} gives {got}, "
                         f"the configuration file states {want}")
    return mc


def round_api(mc):
    """The program's model API that ``make_fed_round`` drives."""
    from repro.core import protocols as P
    from repro.distributed.sharding import AxisRules
    return P.lm_api(mc, AxisRules(mesh=None))


def param_shapes(mc):
    """ShapeDtypeStructs of the program's parameter tree (client, server)."""
    from repro.models import transformer as T
    return T.init_lm(None, mc, mode="shape")


def init_params(shapes, root):
    """Every leaf of the parameter tree (``shapes``) from the seed, in the
    leaf's dtype: LayerNorm scales 1, biases 0, the embedding and every
    projection N(0, 0.02) (GPT-2's init).  Call it under ``jax.jit``: one
    call on the device."""
    import jax
    import jax.numpy as jnp

    from lib import harness as H
    leaves, tdef = jax.tree.flatten(shapes)
    out = []
    for i, (p, s) in enumerate(zip(H.leaf_paths(shapes), leaves)):
        last = p.rsplit("/", 1)[-1]
        if last == "scale":
            v = jnp.ones(s.shape, jnp.float32)
        elif last == "bias":
            v = jnp.zeros(s.shape, jnp.float32)
        else:
            v = 0.02 * jax.random.normal(jax.random.fold_in(root, i),
                                         s.shape, jnp.float32)
        out.append(v.astype(s.dtype))
    return jax.tree.unflatten(tdef, out)


def make_batch(cfg: dict, traffic: dict, key):
    """One round's feed: token ids of shape (clients, h, micro_batch,
    seq) drawn from ``key``, the labels shifted by one."""
    import jax
    toks = jax.random.randint(
        key, (traffic["clients"], traffic["h"], traffic["micro_batch"],
              traffic["seq"] + 1), 0, cfg["vocab_size"])
    return {"inputs": toks[..., :-1], "labels": toks[..., 1:]}


# ---------------------------------------------------------------------------
# model FLOPs
# ---------------------------------------------------------------------------

def sizes(cfg: dict) -> dict:
    """Sizes under GPT-2's published key names, plus the split."""
    a = cfg["assumed"]
    return {"d": cfg["n_embd"], "ff": cfg["n_inner"],
            "layers": cfg["n_layer"], "vocab": cfg["vocab_size"],
            "cut": a["cut_layers"], "aux": a["aux_layers"]}


def block_matmul_flops(d: int, ff: int) -> int:
    """Per token, one block's projections: q, k, v, o and the MLP."""
    return 2 * (4 * d * d + 2 * d * ff)


def attention_flops(d: int, seq: int) -> int:
    """One block's causal attention over a whole sequence of ``seq``."""
    return 4 * d * seq * (seq + 1) // 2


def forward_flops(cfg: dict, blocks: int, seq: int, n_seqs: int) -> int:
    """``blocks`` blocks plus the vocabulary projection, forward, over
    ``n_seqs`` sequences of ``seq`` tokens."""
    s = sizes(cfg)
    per_seq = blocks * (seq * block_matmul_flops(s["d"], s["ff"])
                        + attention_flops(s["d"], seq)) \
        + seq * 2 * s["d"] * s["vocab"]
    return n_seqs * per_seq


def fed_round_flops(cfg: dict, traffic: dict) -> dict:
    """One HERON round: every client's dual probe (two forwards of the
    client blocks, the aux blocks and the vocabulary projection, per ZO
    pair and local step) and the server's FO update (forward plus
    backward, 3 forwards, of the server blocks and the projection) for
    every client's micro-batch of every local step."""
    s = sizes(cfg)
    n_seqs = traffic["clients"] * traffic["micro_batch"] * traffic["h"]
    seq = traffic["seq"]
    client = 2 * traffic["n_pairs"] * forward_flops(
        cfg, s["cut"] + s["aux"], seq, n_seqs)
    server = 3 * forward_flops(cfg, s["layers"] - s["cut"], seq, n_seqs)
    return {"client": client, "server": server, "total": client + server}


def decode_token_flops(cfg: dict, context: int) -> int:
    """One generated token whose step attends ``context`` positions."""
    s = sizes(cfg)
    return s["layers"] * (block_matmul_flops(s["d"], s["ff"])
                          + 4 * s["d"] * context) + 2 * s["d"] * s["vocab"]


def prefill_flops(cfg: dict, prompt_len: int) -> int:
    """Admission of one prompt: every block over the whole prompt, and
    the vocabulary projection of its last position only (the one that
    gives the first token)."""
    s = sizes(cfg)
    return s["layers"] * (prompt_len * block_matmul_flops(s["d"], s["ff"])
                          + attention_flops(s["d"], prompt_len)) \
        + 2 * s["d"] * s["vocab"]
