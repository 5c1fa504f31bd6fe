"""Unified transformer stack for all assigned architectures.

Layer stacks are *pattern-compressed*: the per-arch layer pattern (e.g.
gemma2's [local, global], recurrentgemma's [rec, rec, attn]) is detected
as a repeating unit and executed as a ``jax.lax.scan`` over stacked
parameters — one scan step applies one unit.  This keeps the HLO compact
(a 61-layer 1T-param MoE lowers to one scan body), enables per-segment
remat, and lets the SFL cut fall anywhere (client and server each get
their own compressed stack).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Sequence

import jax
import jax.numpy as jnp

from repro.distributed.sharding import AxisRules, constrain
from repro.kernels import ops as O
from repro.models import attention as A
from repro.models import layers as L
from repro.models import moe as M
from repro.models import recurrent as R
from repro.models.config import LayerSpec, ModelConfig

ATTN_MIXERS = ("global_attn", "local_attn")


# ---------------------------------------------------------------------------
# one block
# ---------------------------------------------------------------------------

def init_block(pb: L.ParamBuilder, path: str, spec: LayerSpec,
               cfg: ModelConfig, cross: bool = False):
    d = cfg.d_model
    norm_init = L.init_rmsnorm if cfg.norm == "rmsnorm" else L.init_layernorm
    p: dict[str, Any] = {"norm1": norm_init(pb, f"{path}.norm1", d)}
    if spec.mixer in ATTN_MIXERS:
        p["attn"] = A.init_attention(pb, f"{path}.attn", cfg)
    elif spec.mixer == "mla":
        p["attn"] = A.init_mla(pb, f"{path}.attn", cfg)
    elif spec.mixer == "rg_lru":
        p["rec"] = R.init_rg_lru(pb, f"{path}.rec", cfg)
    elif spec.mixer == "mlstm":
        p["rec"] = R.init_mlstm(pb, f"{path}.rec", cfg)
    elif spec.mixer == "slstm":
        p["rec"] = R.init_slstm(pb, f"{path}.rec", cfg)
    else:
        raise ValueError(spec.mixer)
    if cross:
        p["cross_norm"] = norm_init(pb, f"{path}.cross_norm", d)
        p["cross"] = A.init_attention(pb, f"{path}.cross", cfg)
    if spec.ffn == "dense":
        p["norm2"] = norm_init(pb, f"{path}.norm2", d)
        p["mlp"] = L.init_mlp(pb, f"{path}.mlp", d, cfg.d_ff,
                              cfg.gated_mlp, False)
    elif spec.ffn == "moe":
        p["norm2"] = norm_init(pb, f"{path}.norm2", d)
        p["moe"] = M.init_moe(pb, f"{path}.moe", cfg)
    if cfg.post_norm:
        p["postnorm1"] = norm_init(pb, f"{path}.postnorm1", d)
        if spec.ffn != "none":
            p["postnorm2"] = norm_init(pb, f"{path}.postnorm2", d)
    return p


def _norm(cfg: ModelConfig, params, x, perturb=None):
    fn = L.rmsnorm if cfg.norm == "rmsnorm" else L.layernorm
    if cfg.norm_eps is not None:
        fn = functools.partial(fn, eps=cfg.norm_eps)
    return L.norm_apply(fn, params, x, perturb)


def _kernel_ffn(spec: LayerSpec, cfg: ModelConfig) -> bool:
    """Whether the block's ffn has a dual-probe kernel lowering: dense, or
    the dropless MoE over held experts (not the capacity-dropping one)."""
    return spec.ffn != "moe" or cfg.moe.capacity_factor is None


def moe_rows(caches) -> jax.Array:
    """The (token, held expert) rows the grouped dual-probe kernel
    computed, summed over the layers of ``apply_stack``'s new caches."""
    total = jnp.zeros((), jnp.int32)
    for kp, v in jax.tree_util.tree_flatten_with_path(caches)[0]:
        if getattr(kp[-1], "key", None) == "moe_rows":
            total = total + jnp.sum(v).astype(jnp.int32)
    return total


def _block_fallback(params, x, spec: LayerSpec, cfg: ModelConfig,
                    rules: AxisRules, perturb, *, positions=None,
                    enc_out=None):
    """Whole-block XLA fallback for mixers without a fused kernel lowering
    (recurrent blocks, the capacity-dropping MoE, cross-attention):
    materialize theta + mu*U for the block's seeded params and run the
    unmodified block — the noise stream (per-leaf hash seeds on canonical
    2-D coordinates) is the same one the fused path generates in-kernel,
    so replay stays exact."""
    pp = O.perturb_tree(params, perturb.seeds, perturb.mu, perturb.rep)
    if not perturb.dual:
        return apply_block(pp, x, spec, cfg, rules, positions=positions,
                           enc_out=enc_out)
    half = x.shape[0] // 2
    pos_a = pos_b = positions
    if positions is not None and positions.shape[0] == x.shape[0]:
        pos_a, pos_b = positions[:half], positions[half:]
    enc_a = enc_b = enc_out
    if enc_out is not None and enc_out.shape[0] == x.shape[0]:
        enc_a, enc_b = enc_out[:half], enc_out[half:]
    xa, _ = apply_block(params, x[:half], spec, cfg, rules,
                        positions=pos_a, enc_out=enc_a)
    xb, _ = apply_block(pp, x[half:], spec, cfg, rules,
                        positions=pos_b, enc_out=enc_b)
    return jnp.concatenate([xa, xb], axis=0), None


def apply_block(params, x, spec: LayerSpec, cfg: ModelConfig,
                rules: AxisRules, *, positions=None, cache=None,
                decode=False, enc_out=None, causal=True, perturb=None):
    """Returns (x, new_cache)."""
    if perturb is not None and not O.any_seed(perturb.seeds):
        perturb = None
    if perturb is not None and (
            spec.mixer not in ATTN_MIXERS + ("mla",)
            or not _kernel_ffn(spec, cfg)
            or ("cross" in params and enc_out is not None)):
        return _block_fallback(params, x, spec, cfg, rules, perturb,
                               positions=positions, enc_out=enc_out)
    h = _norm(cfg, params["norm1"], x, O.psub(perturb, "norm1"))
    new_cache: dict[str, Any] = {}
    if spec.mixer in ATTN_MIXERS:
        attn_cache = None if cache is None else cache.get("attn")
        o, nc = A.attention_layer(
            params["attn"], h, cfg, rules, positions=positions,
            local=(spec.mixer == "local_attn"), cache=attn_cache,
            decode=decode, perturb=O.psub(perturb, "attn"))
        if nc is not None:
            new_cache["attn"] = nc
    elif spec.mixer == "mla":
        o, nc = A.mla_layer(
            params["attn"], h, cfg, rules, positions=positions,
            cache=None if cache is None else cache.get("attn"),
            decode=decode, perturb=O.psub(perturb, "attn"))
        if nc is not None:
            new_cache["attn"] = nc
    else:
        rec_state = None if cache is None else cache.get("rec")
        fn = {"rg_lru": R.rg_lru_block, "mlstm": R.mlstm_block,
              "slstm": R.slstm_block}[spec.mixer]
        o, ns = fn(params["rec"], h, cfg, rules, state=rec_state,
                   decode=decode)
        if decode or rec_state is not None:
            new_cache["rec"] = ns
    if cfg.post_norm:
        o = _norm(cfg, params["postnorm1"], o, O.psub(perturb, "postnorm1"))
    x = x + o
    if "cross" in params and enc_out is not None:
        hc = _norm(cfg, params["cross_norm"], x)
        cdt = cfg.jnp_compute_dtype()
        hd = cfg.resolved_head_dim
        k = L.dense(params["cross"]["wk"], enc_out, cdt)
        v = L.dense(params["cross"]["wv"], enc_out, cdt)
        k = k.reshape(k.shape[:2] + (cfg.n_kv_heads, hd))
        v = v.reshape(v.shape[:2] + (cfg.n_kv_heads, hd))
        o, _ = A.attention_layer(params["cross"], hc, cfg, rules,
                                 positions=positions, cross_kv=(k, v))
        x = x + o
    if spec.ffn != "none":
        h = _norm(cfg, params["norm2"], x, O.psub(perturb, "norm2"))
        if spec.ffn == "dense":
            o = L.mlp(params["mlp"], h, cfg.activation,
                      cfg.jnp_compute_dtype(), O.psub(perturb, "mlp"))
        elif cfg.moe.capacity_factor is None:
            o, rows = M.moe_held(params["moe"], h, cfg,
                                 O.psub(perturb, "moe"))
            if rows is not None:
                new_cache["moe_rows"] = rows
        else:
            o = M.moe_ffn(params["moe"], h, cfg, rules)
        if cfg.post_norm:
            o = _norm(cfg, params["postnorm2"], o,
                      O.psub(perturb, "postnorm2"))
        x = x + o
    seq_ax = "seq_model" if (cfg.seq_sharding and not decode) else None
    x = constrain(x, rules, ("batch", seq_ax, None))
    return x, (new_cache if new_cache else None)


def init_block_cache(spec: LayerSpec, cfg: ModelConfig, batch: int,
                     seq: int, per_slot: bool = False):
    c: dict[str, Any] = {}
    if spec.mixer in ATTN_MIXERS + ("mla",):
        c["attn"] = A.init_kv_cache(cfg, batch, seq,
                                    local=(spec.mixer == "local_attn"),
                                    per_slot=per_slot)
    elif spec.mixer == "rg_lru":
        c["rec"] = R.init_rg_lru_state(cfg, batch)
    elif spec.mixer == "mlstm":
        c["rec"] = R.init_mlstm_state(cfg, batch)
    elif spec.mixer == "slstm":
        c["rec"] = R.init_slstm_state(cfg, batch)
    return c


# ---------------------------------------------------------------------------
# pattern-compressed stacks
# ---------------------------------------------------------------------------

def build_segments(specs: Sequence[LayerSpec]):
    """Greedy compression of a spec list into (unit, repeats) segments."""
    specs = list(specs)
    segments: list[tuple[tuple[LayerSpec, ...], int]] = []
    i = 0
    n = len(specs)
    while i < n:
        # find the smallest unit starting at i that repeats
        best = ((specs[i],), 1)
        for ul in range(1, min(8, n - i) + 1):
            unit = tuple(specs[i:i + ul])
            reps = 1
            j = i + ul
            while j + ul <= n and tuple(specs[j:j + ul]) == unit:
                reps += 1
                j += ul
            if reps * ul > best[1] * len(best[0]):
                best = (unit, reps)
        segments.append(best)
        i += len(best[0]) * best[1]
    return segments


def init_stack(pb: L.ParamBuilder, path: str, cfg: ModelConfig,
               specs: Sequence[LayerSpec], cross: bool = False):
    """Returns a list of segment params, each a tuple (per unit position)
    of block-param pytrees with a stacked leading 'layers' dim."""
    segments = build_segments(specs)
    out = []
    for si, (unit, reps) in enumerate(segments):
        if pb.mode == "init":
            per_rep = []
            for r in range(reps):
                per_rep.append(tuple(
                    init_block(pb, f"{path}.seg{si}.rep{r}.pos{j}", spec,
                               cfg, cross)
                    for j, spec in enumerate(unit)))
            stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *per_rep) \
                if reps > 1 else jax.tree.map(lambda x: x[None], per_rep[0])
        else:
            one = tuple(
                init_block(pb, f"{path}.seg{si}.rep0.pos{j}", spec, cfg,
                           cross)
                for j, spec in enumerate(unit))
            if pb.mode == "shape":
                stacked = jax.tree.map(
                    lambda s: jax.ShapeDtypeStruct((reps,) + s.shape,
                                                   s.dtype), one)
            else:  # axes
                stacked = jax.tree.map(
                    lambda ax: ("layers",) + tuple(ax), one,
                    is_leaf=lambda x: isinstance(x, tuple) and all(
                        isinstance(e, (str, type(None))) for e in x))
        out.append(stacked)
    return out


def init_stack_cache(cfg: ModelConfig, specs: Sequence[LayerSpec],
                     batch: int, seq: int, per_slot: bool = False):
    segments = build_segments(specs)
    out = []
    for unit, reps in segments:
        one = tuple(init_block_cache(spec, cfg, batch, seq, per_slot)
                    for spec in unit)
        stacked = jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (reps,) + x.shape), one)
        out.append(stacked)
    return out


def apply_stack(stack_params, x, cfg: ModelConfig, rules: AxisRules,
                specs: Sequence[LayerSpec], *, positions=None, caches=None,
                decode=False, enc_out=None, perturb=None):
    """Returns (x, new_caches).  ``perturb.seeds`` (if given) is a list
    mirroring ``stack_params``: one scalar seed per stacked leaf.  The
    scan body carries the repeat index so each rep addresses its own row
    band of the stacked leaf's noise field (``Perturb.rep``) — and, under
    ``attn_probe="scores"``, its own ``rep * n_heads * Sq`` row band of
    the per-layer attention score field (see
    :func:`repro.models.attention._dual_probe_attention`)."""
    segments = build_segments(specs)
    new_caches = []
    for si, (unit, reps) in enumerate(segments):
        seg_params = stack_params[si]
        seg_cache = None if caches is None else caches[si]
        seg_seeds = (perturb.seeds[si] if perturb is not None
                     and perturb.seeds is not None else None)
        seg_perturb = (dataclasses.replace(perturb, seeds=seg_seeds)
                       if perturb is not None and O.any_seed(seg_seeds)
                       else None)

        def body(carry, per_rep, unit=unit, seg_perturb=seg_perturb):
            xb = carry
            params_rep, cache_rep, rep_idx = per_rep
            ncs = []
            for j, spec in enumerate(unit):
                cj = None if cache_rep is None else cache_rep[j]
                pj = None
                if seg_perturb is not None and O.any_seed(
                        seg_perturb.seeds[j]):
                    pj = dataclasses.replace(seg_perturb,
                                             seeds=seg_perturb.seeds[j],
                                             rep=rep_idx)
                xb, nc = apply_block(params_rep[j], xb, spec, cfg, rules,
                                     positions=positions, cache=cj,
                                     decode=decode, enc_out=enc_out,
                                     perturb=pj)
                ncs.append(nc if nc is not None else {})
            return xb, tuple(ncs)

        if cfg.remat and not decode and caches is None:
            if cfg.remat_policy == "save_gathers":
                body = jax.checkpoint(
                    body,
                    policy=jax.checkpoint_policies.save_only_these_names(
                        "moe_wgather"))
            else:
                body = jax.checkpoint(body)

        if cfg.scan_layers and reps > 1:
            x, ncs = jax.lax.scan(body, x, (seg_params, seg_cache,
                                            jnp.arange(reps)))
        else:
            # unrolled
            ncs_list = []
            for r in range(reps):
                pr = jax.tree.map(lambda p: p[r], seg_params)
                cr = None if seg_cache is None else jax.tree.map(
                    lambda c: c[r], seg_cache)
                x, nc = body(x, (pr, cr, jnp.asarray(r)))
                ncs_list.append(nc)
            ncs = jax.tree.map(lambda *xs: jnp.stack(xs), *ncs_list) \
                if ncs_list and any(jax.tree.leaves(n) for n in ncs_list) \
                else None
        new_caches.append(ncs)
    return x, new_caches


# ---------------------------------------------------------------------------
# full language model with SFL split structure
# ---------------------------------------------------------------------------

def client_specs(cfg: ModelConfig):
    all_specs = (cfg.layer_specs() if not cfg.enc_dec
                 else cfg.layer_specs()[: cfg.n_enc_layers])
    return all_specs[: cfg.cut_layers]


def server_specs(cfg: ModelConfig):
    if cfg.enc_dec:
        return cfg.layer_specs()[cfg.cut_layers: cfg.n_enc_layers]
    return cfg.layer_specs()[cfg.cut_layers:]


def decoder_specs(cfg: ModelConfig):
    """enc-dec only: the decoder stack (server side)."""
    return cfg.layer_specs()[cfg.n_enc_layers:]


def init_lm(rng, cfg: ModelConfig, mode: str = "init"):
    """Returns {"client": ..., "server": ...} param pytree.

    client = embedding + first ``cut_layers`` blocks + aux head
    server = remaining blocks (+ decoder for enc-dec) + final norm
             (+ unembed when embeddings are untied)
    """
    pb = L.ParamBuilder(rng, mode, cfg.jnp_param_dtype())
    norm_init = (L.init_rmsnorm if cfg.norm == "rmsnorm"
                 else L.init_layernorm)
    client: dict[str, Any] = {
        "embed": L.init_embedding(pb, "embed", cfg.vocab_padded,
                                  cfg.d_model),
        "layers": init_stack(pb, "client", cfg, client_specs(cfg)),
        "aux": init_aux(pb, cfg),
    }
    server: dict[str, Any] = {
        "layers": init_stack(pb, "server", cfg, server_specs(cfg)),
        "final_norm": norm_init(pb, "final_norm", cfg.d_model),
    }
    if cfg.enc_dec:
        server["dec_embed"] = L.init_embedding(pb, "dec_embed",
                                               cfg.vocab_padded,
                                               cfg.d_model)
        server["decoder"] = init_stack(pb, "decoder", cfg,
                                       decoder_specs(cfg), cross=True)
    if not cfg.tie_embeddings:
        server["unembed"] = pb.param(
            "unembed", (cfg.d_model, cfg.vocab_padded),
            ("d_model", "vocab"), "normal", 0.02)
    return {"client": client, "server": server}


def init_aux(pb: L.ParamBuilder, cfg: ModelConfig):
    """Aux head: optional extra blocks + norm + (tied) unembed."""
    norm_init = (L.init_rmsnorm if cfg.norm == "rmsnorm"
                 else L.init_layernorm)
    p: dict[str, Any] = {"norm": norm_init(pb, "aux.norm", cfg.d_model)}
    if cfg.aux_layers > 0:
        specs = tuple(cfg.layer_specs()[cfg.cut_layers:
                                        cfg.cut_layers + cfg.aux_layers])
        p["layers"] = init_stack(pb, "aux", cfg, specs)
    return p


def embed_inputs(client_params, cfg: ModelConfig, tokens_or_embeds):
    cdt = cfg.jnp_compute_dtype()
    if jnp.issubdtype(tokens_or_embeds.dtype, jnp.integer):
        x = L.embed(client_params["embed"], tokens_or_embeds, cdt)
        if cfg.frontend is not None:
            pass  # pre-embedded path is the float branch
    else:
        x = tokens_or_embeds.astype(cdt)  # modality frontend stub output
    if cfg.name.startswith("gemma") or cfg.name.startswith("recurrentgemma"):
        x = x * jnp.asarray(cfg.d_model ** 0.5, cdt)
    return x


def _embed_perturbed(client_params, cfg: ModelConfig, inputs, perturb):
    """embed_inputs with the ZO table perturbation.  The noise rows are
    gathered per token id (``uniform_noise_at``), never materializing the
    (vocab, d_model) field; in dual mode returns the stacked
    [clean; perturbed] embedding on a doubled batch axis."""
    cdt = cfg.jnp_compute_dtype()
    if jnp.issubdtype(inputs.dtype, jnp.integer):
        x = L.embed(client_params["embed"], inputs, cdt)
        pe = O.psub(perturb, "embed")
        st = None if pe is None else pe.seeds.get("table")
        if st is None:
            xp = x
        else:
            u = O.uniform_noise_at(st, inputs[..., None],
                                   jnp.arange(x.shape[-1]))
            xp = (x.astype(jnp.float32)
                  + jnp.asarray(perturb.mu, jnp.float32) * u).astype(cdt)
    else:
        x = xp = inputs.astype(cdt)
    x = jnp.concatenate([x, xp], axis=0) if perturb.dual else xp
    if cfg.name.startswith("gemma") or cfg.name.startswith("recurrentgemma"):
        x = x * jnp.asarray(cfg.d_model ** 0.5, cdt)
    return x


def dual_positions(positions):
    """Positions for a dual-probe batch ([clean; perturbed] halves):
    doubled along the batch axis, which is the one before the sequence
    axis — (B, S), or (3, B, S) for M-RoPE."""
    return jnp.concatenate([positions, positions], axis=positions.ndim - 2)


def client_forward(client_params, cfg: ModelConfig, rules: AxisRules,
                   inputs, positions=None, caches=None, decode=False,
                   perturb=None):
    """Embedding + client blocks -> smashed data (cut-layer activations).

    With ``perturb`` (a :class:`repro.kernels.ops.Perturb`) the forward
    is the ZO-perturbed client pass: weight noise is fused into the
    matmul kernels per layer; ``perturb.dual`` stacks the clean and
    perturbed probes on the leading batch axis so one pass yields both
    losses of the two-point estimator."""
    if perturb is not None and not O.any_seed(perturb.seeds):
        perturb = None
    if perturb is None:
        x = embed_inputs(client_params, cfg, inputs)
    else:
        assert caches is None and not decode
        x = _embed_perturbed(client_params, cfg, inputs, perturb)
        if perturb.dual and positions is not None:
            positions = dual_positions(positions)
    seq_ax = "seq_model" if (cfg.seq_sharding and not decode) else None
    x = constrain(x, rules, ("batch", seq_ax, None))
    x, ncs = apply_stack(client_params["layers"], x, cfg, rules,
                         client_specs(cfg), positions=positions,
                         caches=caches, decode=decode,
                         perturb=O.psub(perturb, "layers"))
    return x, ncs


def aux_forward(client_params, cfg: ModelConfig, rules: AxisRules,
                smashed, positions=None, perturb=None):
    """Aux head on smashed data -> (logits, the aux blocks' new caches)
    (client-local predictor).

    In dual mode ``smashed`` carries [clean; perturbed] halves and the
    tied unembedding perturbs the table for the second half only (same
    table noise the embedding applied — one leaf, one seed)."""
    if perturb is not None and not O.any_seed(perturb.seeds):
        perturb = None
    aux = client_params["aux"]
    pa = O.psub(perturb, "aux")
    x = smashed
    ncs = None
    if "layers" in aux:
        specs = tuple(cfg.layer_specs()[cfg.cut_layers:
                                        cfg.cut_layers + cfg.aux_layers])
        x, ncs = apply_stack(aux["layers"], x, cfg, rules, specs,
                             positions=positions,
                             perturb=O.psub(pa, "layers"))
    x = _norm(cfg, aux["norm"], x, O.psub(pa, "norm"))
    pe = O.psub(perturb, "embed")
    st = None if pe is None else pe.seeds.get("table")
    if st is None:
        logits = L.unembed(client_params["embed"], x, jnp.float32)
    else:
        table = client_params["embed"]["table"].astype(jnp.float32)
        tp = table + jnp.asarray(perturb.mu, jnp.float32) \
            * O.leaf_noise(st, table.shape)
        if perturb.dual:
            half = x.shape[0] // 2
            logits = jnp.concatenate(
                [x[:half].astype(jnp.float32) @ table.T,
                 x[half:].astype(jnp.float32) @ tp.T], axis=0)
        else:
            logits = x.astype(jnp.float32) @ tp.T
    logits = constrain(logits, rules, ("batch", None, "vocab"))
    return L.softcap(logits, cfg.final_softcap), ncs


def server_forward(params, cfg: ModelConfig, rules: AxisRules, smashed,
                   positions=None, caches=None, decode=False,
                   dec_tokens=None, dec_caches=None, dec_positions=None):
    """Server blocks on smashed data -> logits."""
    server = params["server"]
    x, ncs = apply_stack(server["layers"], x := smashed, cfg, rules,
                         server_specs(cfg), positions=positions,
                         caches=caches, decode=decode)
    dec_ncs = None
    if cfg.enc_dec:
        enc_out = _norm(cfg, server["final_norm"], x)
        y = L.embed(server["dec_embed"], dec_tokens,
                    cfg.jnp_compute_dtype())
        y, dec_ncs = apply_stack(server["decoder"], y, cfg, rules,
                                 decoder_specs(cfg),
                                 positions=dec_positions,
                                 caches=dec_caches, decode=decode,
                                 enc_out=enc_out)
        x = y
        x = _norm(cfg, server.get("dec_final_norm", server["final_norm"]),
                  x)
    else:
        x = _norm(cfg, server["final_norm"], x)
    if cfg.tie_embeddings:
        logits = L.unembed(params["client"]["embed"], x, jnp.float32)
    else:
        logits = x.astype(jnp.float32) @ server["unembed"].astype(
            jnp.float32)
    logits = constrain(logits, rules, ("batch", None, "vocab"))
    logits = L.softcap(logits, cfg.final_softcap)
    return logits, (ncs, dec_ncs)


def full_forward(params, cfg: ModelConfig, rules: AxisRules, inputs,
                 positions=None, dec_tokens=None):
    """Whole-model forward (no split) -> logits.  Training/prefill."""
    smashed, _ = client_forward(params["client"], cfg, rules, inputs,
                                positions=positions)
    logits, _ = server_forward(params, cfg, rules, smashed,
                               positions=positions, dec_tokens=dec_tokens,
                               dec_positions=positions if cfg.enc_dec
                               else None)
    return logits


def lm_loss(logits, labels, vocab: int):
    """Mean next-token cross entropy; labels==-100 are masked; the padded
    vocab tail is excluded from the softmax."""
    V = logits.shape[-1]
    if V > vocab:
        # additive mask (elementwise broadcast) — preserves vocab sharding
        mask = jnp.where(jnp.arange(V) >= vocab, -1e30, 0.0
                         ).astype(logits.dtype)
        logits = logits + mask
    valid = labels != -100
    labels_safe = jnp.where(valid, labels, 0)
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, labels_safe[..., None],
                             axis=-1)[..., 0]
    return -jnp.sum(ll * valid) / jnp.maximum(jnp.sum(valid), 1)
