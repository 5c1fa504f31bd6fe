"""Logical-axis sharding rules -> NamedSharding / PartitionSpec.

The model code annotates arrays with *logical* axis names ("batch", "seq",
"heads", "kv_heads", "d_model", "d_ff", "vocab", "experts", "expert_ff",
"layers", ...).  A :class:`AxisRules` maps logical names to mesh axis
names.  A logical axis is only sharded when its size is divisible by the
mesh-axis size — otherwise it silently falls back to replication (this is
what makes e.g. 12-head attention on a 16-way model axis legal; the
resulting replication shows up in the roofline and is a hillclimb target,
not a crash).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Mapping, Sequence

import jax
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

# ---------------------------------------------------------------------------
# Default logical -> mesh axis rules.
# "data-like" axes: ("pod", "data") — batch and FSDP storage sharding.
# "model-like" axis: "model" — tensor/expert parallelism.
# ---------------------------------------------------------------------------

DATA_AXES: tuple[str, ...] = ("pod", "data")
MODEL_AXIS: str = "model"

DEFAULT_RULES: dict[str, tuple[str, ...]] = {
    "batch": DATA_AXES,
    "clients": DATA_AXES,      # federated client cohort (seed replay)
    "seq": (),                 # replicated by default; SP constraint opt-in
    "seq_shard": DATA_AXES,    # explicit sequence sharding (long-context decode)
    "seq_model": (MODEL_AXIS,),  # sequence-parallel residual/attention
    "heads": (MODEL_AXIS,),
    "kv_heads": (MODEL_AXIS,),
    "head_dim": (),
    "d_model": (),
    "d_ff": (MODEL_AXIS,),
    "vocab": (MODEL_AXIS,),
    "experts": (MODEL_AXIS,),
    "expert_ff": (),
    "fsdp": DATA_AXES,         # parameter storage sharding (ZeRO-3)
    "layers": (),              # stacked-scan leading dim
    "conv": (),
    "lru": (MODEL_AXIS,),
}


@dataclasses.dataclass(frozen=True)
class AxisRules:
    """Maps logical axis names to mesh axes, with divisibility fallback."""

    rules: Mapping[str, tuple[str, ...]] = dataclasses.field(
        default_factory=lambda: dict(DEFAULT_RULES)
    )
    mesh: Mesh | None = None
    # if False, "fsdp" rules resolve to replication (small models)
    enable_fsdp: bool = True

    @property
    def partitioned(self) -> bool:
        """Whether GSPMD splits the program over several devices (it
        cannot partition a Mosaic kernel, so Pallas calls stay off)."""
        return self.mesh is not None and self.mesh.size > 1

    def with_updates(self, **updates: tuple[str, ...]) -> "AxisRules":
        new = dict(self.rules)
        new.update(updates)
        return dataclasses.replace(self, rules=new)

    # -- resolution ---------------------------------------------------------
    def _axis_size(self, mesh_axes: Sequence[str]) -> int:
        if self.mesh is None:
            return 1
        size = 1
        for a in mesh_axes:
            if a in self.mesh.shape:
                size *= self.mesh.shape[a]
        return size

    def resolve(self, logical: Sequence[str | None]) -> P:
        """Resolve logical axis names to a PartitionSpec.

        A dim is sharded only if (a) the rule maps to mesh axes present in
        the mesh, and (b) no mesh axis is used twice in one spec.
        Divisibility is checked by callers via :meth:`spec_for`.
        """
        used: set[str] = set()
        out: list[Any] = []
        for name in logical:
            if name is None:
                out.append(None)
                continue
            if name == "fsdp" and not self.enable_fsdp:
                out.append(None)
                continue
            axes = tuple(
                a
                for a in self.rules.get(name, ())
                if self.mesh is not None and a in self.mesh.shape and a not in used
            )
            if not axes:
                out.append(None)
            elif len(axes) == 1:
                out.append(axes[0])
                used.add(axes[0])
            else:
                out.append(axes)
                used.update(axes)
        return P(*out)

    def spec_for(self, shape: Sequence[int], logical: Sequence[str | None]) -> P:
        """Like resolve() but drops shardings that don't divide the dim."""
        assert len(shape) == len(logical), (shape, logical)
        base = self.resolve(logical)
        out: list[Any] = []
        for dim, entry in zip(shape, tuple(base) + (None,) * (len(shape) - len(base))):
            if entry is None:
                out.append(None)
                continue
            axes = (entry,) if isinstance(entry, str) else tuple(entry)
            size = self._axis_size(axes)
            if size > 1 and dim % size == 0:
                out.append(entry)
            else:
                # try a prefix of the axes that divides (size-1 axes are
                # dropped: sharding over them is a no-op)
                kept: list[str] = []
                rem = dim
                for a in axes:
                    s = self._axis_size((a,))
                    if s > 1 and rem % s == 0:
                        kept.append(a)
                        rem //= s
                if kept:
                    out.append(kept[0] if len(kept) == 1 else tuple(kept))
                else:
                    out.append(None)
        return P(*out)

    def sharding_for(self, shape: Sequence[int], logical: Sequence[str | None]):
        if self.mesh is None:
            return None
        return NamedSharding(self.mesh, self.spec_for(shape, logical))


def auto_mesh(shape: Sequence[int], axes: Sequence[str], devices=None):
    """``jax.make_mesh`` with Auto axes.  The model's :func:`constrain`
    calls are sharding hints, which ``make_mesh``'s default Explicit
    axes reject."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def constrain(x: jax.Array, rules: AxisRules, logical: Sequence[str | None]) -> jax.Array:
    """with_sharding_constraint using logical names; no-op without a mesh."""
    if rules.mesh is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(rules.mesh, rules.spec_for(x.shape, logical))
    )


def tree_shardings(rules: AxisRules, tree_logical, tree_shapes):
    """Build a pytree of NamedShardings from matching pytrees of logical
    axis tuples and shapes (ShapeDtypeStructs)."""
    def one(logical, sds):
        return rules.sharding_for(sds.shape, logical)

    return jax.tree.map(one, tree_logical, tree_shapes,
                        is_leaf=lambda x: isinstance(x, tuple) and all(
                            isinstance(e, (str, type(None))) for e in x))


def mesh_axis_size(mesh: Mesh | None, *names: str) -> int:
    if mesh is None:
        return 1
    size = 1
    for n in names:
        size *= mesh.shape.get(n, 1)
    return size
