"""Logical-axis sharding rules (MaxText-style) with divisibility fallbacks.

Model code names tensor dimensions with *logical* axes ('batch', 'ff',
'q_heads', ...). A ``ShardingCtx`` maps logical axes to mesh axes and applies
``with_sharding_constraint`` where a mesh is active. When a dimension is not
divisible by the product of its mapped mesh axes, the mapping silently falls
back to replication for that dimension — this is what makes every assigned
architecture (e.g. arctic's 56 q-heads or phi3's 10 kv-heads on a 16-way
model axis) lower cleanly on the same rule set.
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
from typing import Any, Sequence

import jax
from jax import shard_map  # noqa: F401  (re-exported for model code)
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# Default logical-axis -> mesh-axis rules for the production meshes
# (data, model) and (pod, data, model).
DEFAULT_RULES: dict[str, tuple[str, ...]] = {
    "batch": ("pod", "data"),
    "seq": ("model",),          # context parallelism for long activations
    "embed": (),
    "q_heads": ("model",),
    "kv_heads": ("model",),
    "head": (),
    "ff": ("model",),
    "vocab": ("model",),
    "experts": ("model",),
    "expert_ff": (),
    "layers": (),
    "kv_seq": ("model",),       # decode KV caches: shard the sequence axis
    "state": (),
    "zero": ("pod", "data"),    # optimizer-state (ZeRO-1) extra axis
    "none": (),
}


@dataclasses.dataclass
class ShardingCtx:
    mesh: Mesh | None = None
    rules: dict[str, tuple[str, ...]] = dataclasses.field(
        default_factory=lambda: dict(DEFAULT_RULES)
    )

    def mesh_axes(self, logical: str) -> tuple[str, ...]:
        if self.mesh is None:
            return ()
        axes = self.rules.get(logical, ())
        return tuple(a for a in axes if a in self.mesh.shape)

    def axes_size(self, logical: str) -> int:
        size = 1
        for a in self.mesh_axes(logical):
            size *= self.mesh.shape[a]
        return size

    def spec(self, logical_axes: Sequence[str | None], shape: Sequence[int] | None
             ) -> P:
        """PartitionSpec for the given logical axes, with divisibility checks
        when ``shape`` is provided."""
        parts: list[Any] = []
        used: set[str] = set()
        for i, name in enumerate(logical_axes):
            if name is None or name == "none" or self.mesh is None:
                parts.append(None)
                continue
            axes = tuple(a for a in self.mesh_axes(name) if a not in used)
            if not axes:
                parts.append(None)
                continue
            size = 1
            for a in axes:
                size *= self.mesh.shape[a]
            if shape is not None and shape[i] % size != 0:
                # divisibility fallback: try a prefix of the axes
                while axes and shape[i] % size != 0:
                    size //= self.mesh.shape[axes[-1]]
                    axes = axes[:-1]
                if not axes:
                    parts.append(None)
                    continue
            used.update(axes)
            parts.append(axes if len(axes) > 1 else axes[0])
        return P(*parts)

    def sharding(self, logical_axes: Sequence[str | None],
                 shape: Sequence[int] | None = None) -> NamedSharding | None:
        if self.mesh is None:
            return None
        return NamedSharding(self.mesh, self.spec(logical_axes, shape))


_tls = threading.local()


def set_ctx(ctx: ShardingCtx | None) -> None:
    _tls.ctx = ctx


def current_ctx() -> ShardingCtx:
    ctx = getattr(_tls, "ctx", None)
    return ctx if ctx is not None else ShardingCtx()


def current_mesh() -> Mesh | None:
    return current_ctx().mesh


@contextlib.contextmanager
def use_ctx(mesh: Mesh | None,
            rules: dict[str, tuple[str, ...]] | None = None):
    prev = getattr(_tls, "ctx", None)
    ctx = ShardingCtx(mesh=mesh)
    if rules:
        ctx.rules.update(rules)
    set_ctx(ctx)
    try:
        yield ctx
    finally:
        set_ctx(prev)


def axis_size(logical: str) -> int:
    """Product of mesh-axis sizes a logical axis maps to (1 if no mesh)."""
    return current_ctx().axes_size(logical)


def shard(x: jax.Array, *logical_axes: str | None) -> jax.Array:
    """Apply a sharding constraint by logical axis names (no-op without mesh).

    Dimensions that do not divide their mapped mesh axes fall back to
    replication.
    """
    ctx = current_ctx()
    if ctx.mesh is None:
        return x
    spec = ctx.spec(logical_axes, x.shape)
    return jax.lax.with_sharding_constraint(x, NamedSharding(ctx.mesh, spec))


def logical_sharding(logical_axes: Sequence[str | None],
                     shape: Sequence[int]) -> NamedSharding | None:
    return current_ctx().sharding(logical_axes, shape)


def abstract_sharded(tree_struct, axes_tree) -> Any:
    """Attach NamedShardings to a ShapeDtypeStruct pytree given logical axes."""
    ctx = current_ctx()

    def one(sds, axes):
        sh = ctx.sharding(axes, sds.shape)
        if sh is None:
            return sds
        return jax.ShapeDtypeStruct(sds.shape, sds.dtype, sharding=sh)

    return jax.tree.map(one, tree_struct, axes_tree,
                        is_leaf=lambda x: isinstance(x, (list, tuple)) and
                        all(isinstance(i, (str, type(None))) for i in x))
