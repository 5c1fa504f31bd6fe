"""ResNet-style CNN for the paper's vision experiments (ResNet-18 on
CIFAR-10).  Functional JAX; BatchNorm is replaced by GroupNorm to keep
the model state-free under vmap'd federated simulation (noted deviation
in DESIGN.md — the split point "after the second norm layer" is kept).

Split per the paper: the client holds the stem (conv-norm-relu) and the
first residual block(s) up to ``client_blocks``; the aux head is a single
pooled fully-connected layer; the server holds the rest.
"""
from __future__ import annotations

import dataclasses
from typing import Sequence

import jax
import jax.numpy as jnp

from repro.kernels import ops as O
from repro.models.layers import ParamBuilder, dense


@dataclasses.dataclass(frozen=True)
class CNNConfig:
    widths: tuple[int, ...] = (64, 128, 256, 512)
    blocks_per_stage: int = 2
    classes: int = 10
    client_blocks: int = 1       # residual blocks on the client
    groups: int = 8
    param_dtype: str = "float32"
    forward_impl: str = "kernel"  # kernel | kernel_interpret: backend of
                                  # the ZO client's dual-probe matmuls
                                  # (convs lower via im2col); see
                                  # ModelConfig.forward_impl


def _conv_init(pb: ParamBuilder, path, kh, kw, cin, cout):
    return pb.param(path, (kh, kw, cin, cout),
                    (None, None, None, "d_ff"), "normal",
                    scale=(2.0 / (kh * kw * cin)) ** 0.5)


def _gn_init(pb: ParamBuilder, path, c):
    return {"scale": pb.param(f"{path}.s", (c,), (None,), "ones"),
            "bias": pb.param(f"{path}.b", (c,), (None,), "zeros")}


def conv(w, x, stride=1):
    return jax.lax.conv_general_dilated(
        x, w.astype(x.dtype), (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def groupnorm(p, x, groups=8, eps=1e-5):
    B, H, W, C = x.shape
    g = min(groups, C)
    xg = x.reshape(B, H, W, g, C // g).astype(jnp.float32)
    mu = jnp.mean(xg, axis=(1, 2, 4), keepdims=True)
    var = jnp.var(xg, axis=(1, 2, 4), keepdims=True)
    xn = ((xg - mu) * jax.lax.rsqrt(var + eps)).reshape(B, H, W, C)
    return (xn * p["scale"] + p["bias"]).astype(x.dtype)


def _block_init(pb, path, cin, cout, stride):
    p = {"c1": _conv_init(pb, f"{path}.c1", 3, 3, cin, cout),
         "n1": _gn_init(pb, f"{path}.n1", cout),
         "c2": _conv_init(pb, f"{path}.c2", 3, 3, cout, cout),
         "n2": _gn_init(pb, f"{path}.n2", cout)}
    if stride != 1 or cin != cout:
        p["proj"] = _conv_init(pb, f"{path}.proj", 1, 1, cin, cout)
    return p


def _im2col(x, kh, kw, stride):
    """SAME-padded patch extraction: (B,H,W,C) -> (B,Ho,Wo,kh*kw*C) with
    patch channel order (i, j, c) — the linearization of a (kh,kw,cin,·)
    conv weight's leading axes, so ``patches @ w.reshape(kh*kw*cin, cout)``
    is the conv and the weight's canonical 2-D noise field applies
    unchanged.  Padding splits match XLA SAME (lo = pad//2)."""
    B, H, W, C = x.shape
    ho = -(-H // stride)
    wo = -(-W // stride)
    ph = max((ho - 1) * stride + kh - H, 0)
    pw = max((wo - 1) * stride + kw - W, 0)
    xp = jnp.pad(x, ((0, 0), (ph // 2, ph - ph // 2),
                     (pw // 2, pw - pw // 2), (0, 0)))
    cols = []
    for i in range(kh):
        for j in range(kw):
            cols.append(jax.lax.slice(
                xp, (0, i, j, 0),
                (B, i + (ho - 1) * stride + 1,
                 j + (wo - 1) * stride + 1, C),
                (1, stride, stride, 1)))
    return jnp.concatenate(cols, axis=-1), ho, wo


def conv_perturbed(w, x, stride, seed, perturb):
    """Conv with the ZO weight perturbation fused into a zo_matmul over
    im2col patches (1x1 convs lower to a plain reshaped matmul).  In dual
    mode the [clean; perturbed] halves ride the leading batch axis and
    one fused pass serves both probes."""
    kh, kw, cin, cout = w.shape
    if kh == kw == 1 and stride == 1:
        cols, ho, wo = x, x.shape[1], x.shape[2]
    else:
        cols, ho, wo = _im2col(x, kh, kw, stride)
    w2 = w.reshape(kh * kw * cin, cout)
    x2 = cols.reshape(-1, kh * kw * cin)
    if perturb.dual:
        half = x2.shape[0] // 2
        ya, yb = O.zo_dual_matmul(x2[:half], x2[half:], w2, seed, 0.0,
                                  perturb.mu, impl=perturb.impl)
        y2 = jnp.concatenate([ya, yb], axis=0)
    else:
        y2 = O.zo_matmul(x2, w2, seed, perturb.mu, impl=perturb.impl)
    return y2.reshape(x.shape[0], ho, wo, cout)


def _conv_maybe(w, x, stride, seed, perturb):
    if seed is None:
        return conv(w, x, stride)
    return conv_perturbed(w, x, stride, seed, perturb)


def _gn_maybe(p, x, groups, seeds, perturb):
    if perturb is None or not O.any_seed(seeds):
        return groupnorm(p, x, groups)
    pp = O.perturb_tree(p, seeds, perturb.mu)
    if not perturb.dual:
        return groupnorm(pp, x, groups)
    half = x.shape[0] // 2
    return jnp.concatenate([groupnorm(p, x[:half], groups),
                            groupnorm(pp, x[half:], groups)], axis=0)


def _block_apply(p, x, stride, groups, perturb=None):
    if perturb is not None and not O.any_seed(perturb.seeds):
        perturb = None
    if perturb is None:
        h = jax.nn.relu(groupnorm(p["n1"], conv(p["c1"], x, stride),
                                  groups))
        h = groupnorm(p["n2"], conv(p["c2"], h), groups)
        sc = conv(p["proj"], x, stride) if "proj" in p else x
        return jax.nn.relu(h + sc)
    s = perturb.seeds
    h = _conv_maybe(p["c1"], x, stride, s.get("c1"), perturb)
    h = jax.nn.relu(_gn_maybe(p["n1"], h, groups, s.get("n1"), perturb))
    h = _gn_maybe(p["n2"], _conv_maybe(p["c2"], h, 1, s.get("c2"),
                                       perturb),
                  groups, s.get("n2"), perturb)
    sc = _conv_maybe(p["proj"], x, stride, s.get("proj"), perturb) \
        if "proj" in p else x
    return jax.nn.relu(h + sc)


def _stage_plan(cfg: CNNConfig):
    """[(stage, block_idx, cin, cout, stride)] flat block list."""
    plan = []
    cin = cfg.widths[0]
    for si, w in enumerate(cfg.widths):
        for bi in range(cfg.blocks_per_stage):
            stride = 2 if (si > 0 and bi == 0) else 1
            plan.append((si, bi, cin, w, stride))
            cin = w
    return plan


def init_cnn(rng, cfg: CNNConfig, mode: str = "init"):
    pb = ParamBuilder(rng, mode, jnp.dtype(cfg.param_dtype))
    plan = _stage_plan(cfg)
    stem = {"conv": _conv_init(pb, "stem.conv", 3, 3, 3, cfg.widths[0]),
            "norm": _gn_init(pb, "stem.norm", cfg.widths[0])}
    blocks = [_block_init(pb, f"block{idx}", cin, cout, stride)
              for idx, (_, _, cin, cout, stride) in enumerate(plan)]
    cb = cfg.client_blocks
    client = {
        "stem": stem,
        "blocks": blocks[:cb],
        "aux": {"fc": {"w": pb.param("aux.fc.w",
                                     (plan[cb - 1][3] if cb else
                                      cfg.widths[0], cfg.classes),
                                     (None, None), "normal"),
                       "b": pb.param("aux.fc.b", (cfg.classes,), (None,),
                                     "zeros")}},
    }
    server = {
        "blocks": blocks[cb:],
        "fc": {"w": pb.param("server.fc.w", (cfg.widths[-1], cfg.classes),
                             (None, None), "normal"),
               "b": pb.param("server.fc.b", (cfg.classes,), (None,),
                             "zeros")},
    }
    return {"client": client, "server": server}


def client_forward(client, x, cfg: CNNConfig, perturb=None):
    """x: (B, H, W, 3) -> smashed feature map.  With ``perturb`` the
    client pass is ZO-perturbed (convs lower onto the fused zo_matmul via
    im2col); ``perturb.dual`` doubles the batch into [clean; perturbed]
    halves at entry."""
    if perturb is not None and not O.any_seed(perturb.seeds):
        perturb = None
    if perturb is not None and perturb.dual:
        x = jnp.concatenate([x, x], axis=0)
    ps = O.psub(perturb, "stem")
    h = _conv_maybe(client["stem"]["conv"], x, 1,
                    None if ps is None else ps.seeds.get("conv"),
                    perturb)
    h = jax.nn.relu(_gn_maybe(client["stem"]["norm"], h, cfg.groups,
                              None if ps is None else ps.seeds.get("norm"),
                              perturb))
    pblocks = O.psub(perturb, "blocks")
    plan = _stage_plan(cfg)
    for i, (p, (_, _, _, _, stride)) in enumerate(zip(client["blocks"],
                                                      plan)):
        h = _block_apply(p, h, stride, cfg.groups, O.psub(pblocks, i))
    return h


def aux_logits(client, smashed, cfg: CNNConfig, perturb=None):
    pooled = jnp.mean(smashed, axis=(1, 2))
    fc = client["aux"]["fc"]
    pf = O.psub(O.psub(perturb, "aux"), "fc")
    if pf is not None:
        return dense(fc, pooled.astype(jnp.float32), jnp.float32, pf)
    return pooled.astype(jnp.float32) @ fc["w"].astype(jnp.float32) \
        + fc["b"].astype(jnp.float32)


def server_logits(server, smashed, cfg: CNNConfig):
    plan = _stage_plan(cfg)[cfg.client_blocks:]
    h = smashed
    for p, (_, _, _, _, stride) in zip(server["blocks"], plan):
        h = _block_apply(p, h, stride, cfg.groups)
    pooled = jnp.mean(h, axis=(1, 2))
    fc = server["fc"]
    return pooled.astype(jnp.float32) @ fc["w"].astype(jnp.float32) \
        + fc["b"].astype(jnp.float32)


def xent(logits, labels):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None],
                                         axis=-1))


def accuracy(logits, labels):
    return jnp.mean((jnp.argmax(logits, -1) == labels).astype(jnp.float32))
