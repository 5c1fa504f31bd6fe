import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any other import (jax locks the
# device count at first init).  512 host devices back the production
# meshes: 16x16 single-pod and 2x16x16 multi-pod.

import argparse      # noqa: E402
import json          # noqa: E402
import sys           # noqa: E402
import time          # noqa: E402
import traceback     # noqa: E402

import jax           # noqa: E402
import jax.numpy as jnp                      # noqa: E402
import numpy as np   # noqa: E402

from repro.configs import base as CB          # noqa: E402
from repro.configs.registry import ARCH_IDS, get_config  # noqa: E402
from repro.core import protocols as P         # noqa: E402
from repro.core import zo as Z                # noqa: E402
from repro.distributed.sharding import AxisRules, DATA_AXES  # noqa: E402
from repro.launch import roofline as RL       # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.models import transformer as T     # noqa: E402
from repro.optim.optimizers import make_optimizer  # noqa: E402

FSDP_THRESHOLD = 3e9  # params; above this, shard storage over data axes
TARGET_KIND = "TPU v5 lite"  # the chip the production pod meshes model


# ---------------------------------------------------------------------------
# sharding assembly
# ---------------------------------------------------------------------------

def _is_axes_leaf(x):
    return isinstance(x, tuple) and all(
        isinstance(e, (str, type(None))) for e in x)


def build_rules(cfg, mesh, n_params: float) -> AxisRules:
    rules = AxisRules(mesh=mesh, enable_fsdp=n_params > FSDP_THRESHOLD)
    if rules.enable_fsdp:
        rules = rules.with_updates(d_model=DATA_AXES)
    if getattr(cfg, "seq_sharding", False):
        # sequence-parallel attention: projections replicated over the
        # model axis (FSDP-stored over data) so q/k/v/o stay seq-sharded
        # end-to-end -- no head-TP psum, no GSPMD resharding conflicts.
        rules = rules.with_updates(heads=(), kv_heads=(),
                                   d_model=DATA_AXES)
    return rules


def sharded_params_sds(cfg, rules):
    sds = T.init_lm(None, cfg, mode="shape")
    axes = T.init_lm(None, cfg, mode="axes")

    def one(ax, s):
        sh = rules.sharding_for(s.shape, ax)
        return jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh)

    return jax.tree.map(one, axes, sds, is_leaf=_is_axes_leaf), axes


def _strip(sds_tree):
    return jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype),
                        sds_tree)


def opt_state_specs(opt_name: str, opt, params_sds_sharded, rules):
    """eval_shape the optimizer init and attach parameter shardings."""
    plain = _strip(params_sds_sharded)
    st = jax.eval_shape(opt.init, plain)

    def attach_like_params(sub):
        return jax.tree.map(
            lambda s, p: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                              sharding=p.sharding),
            sub, params_sds_sharded)

    out = dict(st)
    if opt_name in ("adamw", "adam"):
        out["m"] = attach_like_params(st["m"])
        out["v"] = attach_like_params(st["v"])
    elif opt_name in ("sgdm",):
        out["m"] = attach_like_params(st["m"])
    elif opt_name == "adafactor":
        def fac(vdict, p):
            spec = p.sharding.spec if p.sharding is not None else None
            new = {}
            for k, s in vdict.items():
                if spec is None:
                    new[k] = s
                    continue
                ent = tuple(spec) + (None,) * (len(p.shape) - len(spec))
                if k == "vr":
                    sub = ent[:-1]
                elif k == "vc":
                    sub = ent[:-2] + ent[-1:]
                else:
                    sub = ent
                sh = jax.sharding.NamedSharding(
                    rules.mesh, jax.sharding.PartitionSpec(*sub))
                new[k] = jax.ShapeDtypeStruct(s.shape, s.dtype,
                                              sharding=sh)
            return new

        is_v = lambda x: isinstance(x, dict) and ("v" in x or "vr" in x)
        out["v"] = jax.tree.map(fac, st["v"], params_sds_sharded,
                                is_leaf=is_v)
    return out


def batch_specs_sharded(cfg, shape, rules):
    specs = CB.train_batch_specs(cfg, shape)
    out = {}
    for k, s in specs.items():
        if k == "positions" and len(s.shape) == 3:
            logical = (None, "batch", None)
        else:
            logical = ("batch",) + (None,) * (len(s.shape) - 1)
        out[k] = jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=rules.sharding_for(s.shape, logical))
    return out


_CACHE_LOGICAL = {
    "k": ("batch", "seq_shard", "kv_heads", None),
    "v": ("batch", "seq_shard", "kv_heads", None),
    "h": ("batch", "lru"),
    "conv": ("batch", None, "lru"),
    "enc_out": ("batch", "seq_shard", None),
}


def cache_specs_sharded(cfg, shape, rules):
    sds = CB.serve_cache_specs(cfg, shape)
    flat, treedef = jax.tree_util.tree_flatten_with_path(sds)
    out = []
    for path, s in flat:
        keys = [str(getattr(k, "key", getattr(k, "idx", k))) for k in path]
        name = next((k for k in reversed(keys) if k in _CACHE_LOGICAL
                     or k == "pos"), None)
        if name == "pos" or name is None:
            logical = None
        else:
            logical = _CACHE_LOGICAL[name]
        if logical is None:
            # cell states (tuples under "cell") and scalars
            if "cell" in keys and len(s.shape) >= 2:
                logical = ("batch", "heads") + (None,) * (len(s.shape) - 2)
            else:
                out.append(jax.ShapeDtypeStruct(s.shape, s.dtype))
                continue
        # right-align (stacked 'layers' dims on the left)
        pad = len(s.shape) - len(logical)
        logical = (None,) * pad + tuple(logical)
        out.append(jax.ShapeDtypeStruct(
            s.shape, s.dtype,
            sharding=rules.sharding_for(s.shape, logical)))
    return jax.tree.unflatten(treedef, out)


# ---------------------------------------------------------------------------
# parameter accounting
# ---------------------------------------------------------------------------

def param_counts(cfg, params_sds):
    flat, _ = jax.tree_util.tree_flatten_with_path(params_sds)
    total = 0
    expert = 0
    embed = 0
    for path, s in flat:
        keys = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                        for k in path)
        n = int(np.prod(s.shape))
        total += n
        if "moe/up" in keys or "moe/gate" in keys or "moe/down" in keys:
            expert += n
        if "embed" in keys and "table" in keys:
            embed += n
    active = total - embed
    if cfg.moe is not None and expert:
        active -= int(expert * (1.0 - cfg.moe.top_k / cfg.moe.n_experts))
    return {"total": total, "expert": expert, "embed": embed,
            "active_nonembed": active}


# ---------------------------------------------------------------------------
# cell runners
# ---------------------------------------------------------------------------

def lower_train(cfg, shape, mesh, method="heron"):
    counts_probe = param_counts(cfg, T.init_lm(None, cfg, mode="shape"))
    rules = build_rules(cfg, mesh, counts_probe["total"])
    api = P.lm_api(cfg, rules)
    c_name = "zo_sgd" if method == "heron" else "adamw"
    copt = make_optimizer(c_name, 1e-3)
    sopt = make_optimizer(cfg.optimizer, 1e-3)
    params_sds, _ = sharded_params_sds(cfg, rules)
    state_sds = {
        "params": params_sds,
        "opt_client": opt_state_specs(c_name, copt, params_sds["client"],
                                      rules),
        "opt_server": opt_state_specs(cfg.optimizer, sopt,
                                      params_sds["server"], rules),
        "step": jax.ShapeDtypeStruct((), jnp.int32),
        "rng": jax.ShapeDtypeStruct((2,), jnp.uint32),
    }
    batch_sds = batch_specs_sharded(cfg, shape, rules)
    step = P.make_train_step(
        api, method, Z.ZOConfig(mu=1e-3, n_pairs=1), copt, sopt)
    with mesh:
        lowered = jax.jit(step, donate_argnums=0).lower(state_sds,
                                                        batch_sds)
    return lowered, counts_probe


def lower_prefill(cfg, shape, mesh):
    counts = param_counts(cfg, T.init_lm(None, cfg, mode="shape"))
    rules = build_rules(cfg, mesh, counts["total"])
    params_sds, _ = sharded_params_sds(cfg, rules)
    batch_sds = batch_specs_sharded(cfg, shape, rules)
    prefill = P.make_prefill_step(cfg, rules)
    with mesh:
        lowered = jax.jit(prefill).lower(params_sds, batch_sds)
    return lowered, counts


def lower_decode(cfg, shape, mesh):
    counts = param_counts(cfg, T.init_lm(None, cfg, mode="shape"))
    rules = build_rules(cfg, mesh, counts["total"])
    params_sds, _ = sharded_params_sds(cfg, rules)
    cache_sds = cache_specs_sharded(cfg, shape, rules)
    tok_spec = CB.decode_token_specs(cfg, shape)
    tok_sharded = jax.ShapeDtypeStruct(
        tok_spec.shape, tok_spec.dtype,
        sharding=rules.sharding_for(tok_spec.shape, ("batch", None)))
    serve = P.make_serve_step(cfg, rules)
    with mesh:
        lowered = jax.jit(serve, donate_argnums=1).lower(
            params_sds, cache_sds, tok_sharded)
    return lowered, counts


def _parse_overrides(pairs):
    out = {}
    for kv in pairs or []:
        k, v = kv.split("=", 1)
        try:
            out[k] = int(v)
        except ValueError:
            try:
                out[k] = float(v)
            except ValueError:
                out[k] = v
    return out


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             method: str = "heron", overrides=None) -> dict:
    cfg = get_config(arch)
    if overrides:
        cfg = cfg.replace(**overrides)
    shape = CB.SHAPES[shape_name]
    rec = {"arch": arch, "shape": shape_name,
           "mesh": "2x16x16" if multi_pod else "16x16",
           "method": method if shape.kind == "train" else shape.kind,
           "overrides": overrides or {}}
    ok, why = CB.supports_shape(cfg, shape)
    if not ok:
        rec.update(status="skipped", reason=why)
        return rec
    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    if shape.kind == "train":
        lowered, counts = lower_train(cfg, shape, mesh, method)
        tokens = shape.global_batch * shape.seq_len
    elif shape.kind == "prefill":
        lowered, counts = lower_prefill(cfg, shape, mesh)
        tokens = shape.global_batch * shape.seq_len
    else:
        lowered, counts = lower_decode(cfg, shape, mesh)
        tokens = shape.global_batch
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0
    n_chips = mesh.size
    terms = RL.roofline_terms(compiled, device_kind=TARGET_KIND)
    mem = RL.memory_summary(compiled)
    mf_global = RL.model_flops(cfg, tokens, counts["active_nonembed"])
    if shape.kind == "train":
        mf_global *= 1.0          # fwd+bwd already in the 6ND convention
    else:
        mf_global /= 3.0          # inference: 2ND
    mf_per_chip = mf_global / n_chips
    rec.update(
        status="ok",
        seconds_lower=round(t_lower, 1),
        seconds_compile=round(t_compile, 1),
        chips=n_chips,
        tokens_global=tokens,
        params=counts,
        model_flops_per_chip=mf_per_chip,
        useful_flops_ratio=(mf_per_chip / terms["flops"]
                            if terms["flops"] else 0.0),
        memory=mem,
        **{k: v for k, v in terms.items()},
    )
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, choices=list(ARCH_IDS))
    ap.add_argument("--shape", default=None, choices=list(CB.SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--method", default="heron")
    ap.add_argument("--out", default=None)
    ap.add_argument("--set", action="append", default=[],
                    help="config overrides key=value (repeatable)")
    args = ap.parse_args(argv)
    assert args.arch and args.shape, "--arch and --shape required"
    try:
        rec = run_cell(args.arch, args.shape, args.multi_pod, args.method,
                       _parse_overrides(args.set))
    except Exception as e:  # pragma: no cover
        rec = {"arch": args.arch, "shape": args.shape,
               "mesh": "2x16x16" if args.multi_pod else "16x16",
               "status": "error", "error": repr(e),
               "trace": traceback.format_exc()[-2000:]}
    line = json.dumps(rec)
    print(line)
    if args.out:
        with open(args.out, "a") as f:
            f.write(line + "\n")
    return 0 if rec.get("status") in ("ok", "skipped") else 1


if __name__ == "__main__":
    sys.exit(main())
