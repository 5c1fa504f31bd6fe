"""The program's side of a DeepSeek-V3-shaped configuration (Moonlight-
16B-A3B on one chip's share), and its model FLOPs; the same functions as
``gpt2.py``.

Model FLOPs, as for GPT-2 (2 per multiply-add; padding, recompute, the
noise, the replay and the optimizer count zero), per token:

* MLA: the q, kv_a, kv_b and o projections, and causal attention over
  the query/key dim (nope + rope) for the scores and the value dim for
  the weighted sum: ``2 (Dqk + Dv)`` per head and query-key pair;
* the dense layer's gated MLP: ``2 * 3 * d * intermediate_size``;
* an MoE layer: the router over all experts, the shared experts, and the
  *expected* routed work of the experts held here: ``top_k * held /
  experts`` expert FFNs per token (0.75 for Moonlight's 6 of 64 with 8
  held), whatever the routing of a round does;
* the vocabulary projection over the slice held here.
"""
from __future__ import annotations

import importlib


# ---------------------------------------------------------------------------
# the program
# ---------------------------------------------------------------------------

def program_config(cfg: dict):
    """The program's ModelConfig, with the configuration file's sizes
    checked against it."""
    mod, fn = cfg["constructor"].split(":")
    mc = getattr(importlib.import_module(mod), fn)().replace(
        **cfg.get("overrides", {}))
    a, m = cfg["assumed"], mc.moe
    want = {"n_layers": cfg["num_hidden_layers"],
            "d_model": cfg["hidden_size"],
            "n_heads": cfg["num_attention_heads"],
            "d_ff": cfg["intermediate_size"], "vocab": cfg["vocab_size"],
            "q_lora_rank": cfg["q_lora_rank"] or 0,
            "kv_lora_rank": cfg["kv_lora_rank"],
            "qk_nope_dim": cfg["qk_nope_head_dim"],
            "qk_rope_dim": cfg["qk_rope_head_dim"],
            "v_head_dim": cfg["v_head_dim"],
            "rope_theta": float(cfg["rope_theta"]),
            "norm_eps": cfg["rms_norm_eps"],
            "n_dense_layers": cfg["first_k_dense_replace"],
            "tie_embeddings": cfg["tie_word_embeddings"],
            "experts": cfg["published"]["n_routed_experts"],
            "held": cfg["n_routed_experts"], "expert_offset": 0,
            "top_k": cfg["num_experts_per_tok"],
            "d_ff_expert": cfg["moe_intermediate_size"],
            "shared": cfg["n_shared_experts"],
            "routed_scale": cfg["routed_scaling_factor"],
            "scoring": cfg["scoring_func"], "dropless": True,
            "cut_layers": a["cut_layers"], "aux_layers": a["aux_layers"],
            "param_dtype": a["dtype"], "compute_dtype": a["dtype"]}
    got = {k: getattr(mc, k) for k in want if hasattr(mc, k)}
    got |= {"experts": m.n_experts, "held": m.held,
            "expert_offset": m.expert_offset, "top_k": m.top_k,
            "d_ff_expert": m.d_ff_expert, "shared": m.n_shared_experts,
            "routed_scale": m.routed_scale, "scoring": m.scoring,
            "dropless": m.capacity_factor is None}
    if got != want:
        raise ValueError(f"constructor {cfg['constructor']} gives {got}, "
                         f"the configuration file states {want}")
    return mc


def round_api(mc):
    from repro.core import protocols as P
    from repro.distributed.sharding import AxisRules
    return P.lm_api(mc, AxisRules(mesh=None))


def param_shapes(mc):
    from repro.models import transformer as T
    return T.init_lm(None, mc, mode="shape")


def init_params(shapes, root):
    """Every leaf from the seed, in the leaf's dtype: RMSNorm scales 0
    (weight 1 + scale), every matrix, router and the embedding N(0,
    0.02).  Call it under ``jax.jit``."""
    import jax
    import jax.numpy as jnp

    from lib import harness as H
    leaves, tdef = jax.tree.flatten(shapes)
    out = []
    for i, (p, s) in enumerate(zip(H.leaf_paths(shapes), leaves)):
        if p.rsplit("/", 1)[-1] == "scale":
            v = jnp.zeros(s.shape, jnp.float32)
        else:
            v = 0.02 * jax.random.normal(jax.random.fold_in(root, i),
                                         s.shape, jnp.float32)
        out.append(v.astype(s.dtype))
    return jax.tree.unflatten(tdef, out)


def make_batch(cfg: dict, traffic: dict, key):
    """One round's feed: ids of shape (clients, h, micro_batch, seq) from
    the vocabulary slice, the labels shifted by one."""
    import jax
    toks = jax.random.randint(
        key, (traffic["clients"], traffic["h"], traffic["micro_batch"],
              traffic["seq"] + 1), 0, cfg["vocab_size"])
    return {"inputs": toks[..., :-1], "labels": toks[..., 1:]}


# ---------------------------------------------------------------------------
# model FLOPs
# ---------------------------------------------------------------------------

def mla_params(cfg: dict) -> int:
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    dn, dr = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    r, dv = cfg["kv_lora_rank"], cfg["v_head_dim"]
    q = cfg["q_lora_rank"]
    wq = d * H * (dn + dr) if not q else d * q + q * H * (dn + dr)
    return wq + d * (r + dr) + r * H * (dn + dv) + H * dv * d


def attention_flops(cfg: dict, seq: int) -> int:
    """One MLA layer's causal attention over a sequence of ``seq``."""
    dqk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return 2 * cfg["num_attention_heads"] * (dqk + cfg["v_head_dim"]) \
        * seq * (seq + 1) // 2


def ffn_flops(cfg: dict, moe: bool) -> float:
    """Per token, one layer's FFN: the dense MLP, or the router, the shared
    experts and the expected routed work of the held experts."""
    d = cfg["hidden_size"]
    if not moe:
        return 2 * 3 * d * cfg["intermediate_size"]
    f = cfg["moe_intermediate_size"]
    experts = cfg["published"]["n_routed_experts"]
    per_token = cfg["num_experts_per_tok"] * cfg["n_routed_experts"] \
        / experts
    return 2 * (d * experts + 3 * d * cfg["n_shared_experts"] * f
                + per_token * 3 * d * f)


def layer_flops(cfg: dict, layer: int, seq: int) -> float:
    """Layer ``layer`` (0-based) over a whole sequence of ``seq``."""
    moe = layer >= cfg["first_k_dense_replace"]
    return seq * (2 * mla_params(cfg) + ffn_flops(cfg, moe)) \
        + attention_flops(cfg, seq)


def forward_flops(cfg: dict, layers, seq: int, n_seqs: int) -> float:
    """The layers ``layers`` plus the vocabulary projection, forward."""
    per_seq = sum(layer_flops(cfg, i, seq) for i in layers) \
        + seq * 2 * cfg["hidden_size"] * cfg["vocab_size"]
    return n_seqs * per_seq


def fed_round_flops(cfg: dict, traffic: dict) -> dict:
    """One HERON round: each client's dual probe (two forwards of the
    client layers, the aux layers, which are the next layers' kind, and
    the vocabulary projection) and the server's forward plus backward (3
    forwards) of its layers and projection, per micro-batch."""
    a = cfg["assumed"]
    cut, aux = a["cut_layers"], a["aux_layers"]
    n_seqs = traffic["clients"] * traffic["micro_batch"] * traffic["h"]
    seq = traffic["seq"]
    client_layers = list(range(cut)) + list(range(cut, cut + aux))
    client = 2 * traffic["n_pairs"] * forward_flops(cfg, client_layers, seq,
                                                    n_seqs)
    server = 3 * forward_flops(cfg, range(cut, cfg["num_hidden_layers"]),
                               seq, n_seqs)
    return {"client": client, "server": server, "total": client + server}


def expert_flops_per_row(cfg: dict) -> int:
    """One (token, held expert) row through the expert's up, gate and
    down projections."""
    return 2 * 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]
