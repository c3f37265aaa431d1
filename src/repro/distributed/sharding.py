"""Logical-axis -> mesh-axis sharding rules (t5x/MaxText style).

Parameters are annotated with *logical* axis names at init time (nn.module.P).
This module maps those names onto the axes of the active mesh, with

  * late binding  — rules mention mesh axes by name; axes absent from the
    active mesh are dropped, so the same model code runs on a 1-device CPU,
    an 8-device test mesh, a (16,16) pod and a (2,16,16) multi-pod mesh;
  * divisibility fallbacks — a dim whose size does not divide the mapped mesh
    axes is replicated instead (e.g. smollm's 15 query heads vs model=16 —
    the *flattened* heads*head_dim dim shards fine, but a (15, ...) per-head
    param would fall back to replication);
  * ZeRO-1 — optimizer-state shardings extend the param sharding by
    partitioning the largest still-replicated dim over the data axis.

The rule table is a plain tuple of (logical_name, mesh_axes) pairs so perf
hillclimbing = editing/overriding rules per architecture.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from repro.nn.module import P

__all__ = [
    "LOGICAL_RULES",
    "FSDP_RULES",
    "ShardingRules",
    "logical_to_spec",
    "named_sharding",
    "param_shardings",
    "batch_sharding",
    "zero1_shardings",
    "mesh_axis_size",
    "api_param_shardings",
    "replicated_sharding",
    "kv_cache_shardings",
    "paged_kv_shardings",
]

# Default tensor-parallel rule table. Entries may map one logical axis to a
# tuple of mesh axes (sharded over their product). Order matters: first match
# wins. "data"-family axes are reserved for the batch / ZeRO; "model" carries
# tensor parallelism; "pod" is the cross-pod data axis.
LOGICAL_RULES: Tuple[Tuple[str, Tuple[str, ...]], ...] = (
    ("batch", ("pod", "data")),
    ("vocab", ("model",)),
    ("heads", ("model",)),
    ("kv_heads", ("model",)),
    ("ffn", ("model",)),
    ("ssm_inner", ("model",)),
    ("expert", ("model",)),
    ("embed", ()),  # replicated by default (TP); FSDP_RULES shards it
    ("layers", ()),
    ("ssm_heads", ()),
)

# FSDP/ZeRO-3-style variant: weights additionally sharded over "data" along
# the embed dim (all assigned d_models divide 16). Gathered per scan step.
FSDP_RULES: Tuple[Tuple[str, Tuple[str, ...]], ...] = (
    ("embed", ("data",)),
) + tuple((k, v) for k, v in LOGICAL_RULES if k != "embed")


@dataclasses.dataclass(frozen=True)
class ShardingRules:
    """A rule table bound to helper methods. ``overrides`` prepend to rules."""

    rules: Tuple[Tuple[str, Tuple[str, ...]], ...] = LOGICAL_RULES

    def with_overrides(self, *pairs: Tuple[str, Tuple[str, ...]]) -> "ShardingRules":
        return ShardingRules(tuple(pairs) + self.rules)

    def lookup(self, name: Optional[str]) -> Tuple[str, ...]:
        if name is None:
            return ()
        for k, v in self.rules:
            if k == name:
                return v
        return ()


def mesh_axis_size(mesh: Mesh, axes: Sequence[str]) -> int:
    return int(np.prod([mesh.shape[a] for a in axes], dtype=np.int64)) if axes else 1


def _filter_axes(mesh: Mesh, axes: Tuple[str, ...]) -> Tuple[str, ...]:
    return tuple(a for a in axes if a in mesh.shape)


def logical_to_spec(
    logical_axes: Optional[Tuple[Optional[str], ...]],
    mesh: Mesh,
    rules: ShardingRules = ShardingRules(),
    shape: Optional[Tuple[int, ...]] = None,
) -> PartitionSpec:
    """Logical axis names (one per dim) -> PartitionSpec for ``mesh``.

    With ``shape`` given, any dim whose size does not divide the mapped mesh
    axes' product is replicated (divisibility fallback), and a mesh axis is
    never used twice in one spec (first dim wins).
    """
    if logical_axes is None:
        return PartitionSpec()
    used: set = set()
    out = []
    for i, name in enumerate(logical_axes):
        axes = _filter_axes(mesh, rules.lookup(name))
        axes = tuple(a for a in axes if a not in used)
        if axes and shape is not None and shape[i] % mesh_axis_size(mesh, axes) != 0:
            axes = ()
        used.update(axes)
        if not axes:
            out.append(None)
        elif len(axes) == 1:
            out.append(axes[0])
        else:
            out.append(axes)
    while out and out[-1] is None:
        out.pop()
    return PartitionSpec(*out)


def named_sharding(
    mesh: Mesh,
    logical_axes: Optional[Tuple[Optional[str], ...]],
    rules: ShardingRules = ShardingRules(),
    shape: Optional[Tuple[int, ...]] = None,
) -> NamedSharding:
    return NamedSharding(mesh, logical_to_spec(logical_axes, mesh, rules, shape))


def _shape_of(leaf) -> Optional[Tuple[int, ...]]:
    return tuple(leaf.shape) if hasattr(leaf, "shape") else None


def param_shardings(mesh: Mesh, boxed_params, rules: ShardingRules = ShardingRules()):
    """Boxed param tree (P leaves; values may be ShapeDtypeStructs) ->
    matching tree of NamedShardings."""

    def one(p: P):
        return named_sharding(mesh, p.axes, rules, _shape_of(p.value))

    return jax.tree_util.tree_map(one, boxed_params, is_leaf=lambda x: isinstance(x, P))


def api_param_shardings(mesh: Mesh, api, rules: ShardingRules = ShardingRules()):
    """NamedShardings for a ModelAPI's (unboxed) param tree: abstract-init
    the boxed tree (P leaves carry the logical axes) and map it through
    ``param_shardings``. What the serving runtime uses to place checkpoints
    it receives as plain value trees."""
    boxed = jax.eval_shape(api.init, jax.random.PRNGKey(0))
    return param_shardings(mesh, boxed, rules)


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    """Fully-replicated placement on ``mesh`` (host scalars, token ids,
    per-slot positions — everything the serving runtime keeps tiny)."""
    return NamedSharding(mesh, PartitionSpec())


def kv_cache_shardings(mesh: Mesh, cache, rules: ShardingRules = ShardingRules()):
    """NamedShardings for a serving KV cache pytree (the ``models/base.py``
    ``KVCacheLayout`` contract: every leaf ``(layers, slots, max_len,
    kv_heads, hd)``, scale leaves with a trailing 1).

    Only the ``kv_heads`` dim maps to a mesh axis (``model`` under the
    default rules), so each device owns whole attention heads for every slot
    and position — the slot splice and the per-row decode scatter stay
    device-local. A head count that does not divide the mapped axes falls
    back to replication per leaf (the standard divisibility fallback), so a
    GQA cache with e.g. 1 kv head serves on any mesh unchanged.
    """
    from repro.models.base import KV_CACHE_LOGICAL_AXES

    def one(leaf):
        return named_sharding(mesh, KV_CACHE_LOGICAL_AXES, rules, tuple(leaf.shape))

    return jax.tree_util.tree_map(one, cache)


def paged_kv_shardings(mesh: Mesh, pool, layout, rules: ShardingRules = ShardingRules()):
    """NamedShardings for the paged KV block pool (the ``models/base.py``
    ``PagedKVLayout`` contract ``layout``: leaves ``(layers, blocks,
    block_size, row_lanes)``, int8 scales ``(..., kv_heads)``).

    The lane dim maps to ``kv_heads``. It splits only when the heads divide
    the mapped mesh axes and the rows are not padded: a contiguous split of
    the lanes is then a split by head group, and each device owns whole
    heads for every block and offset. Otherwise the pool replicates, since
    a split of the lanes would cut heads apart.
    """
    from repro.models.base import PAGED_KV_LOGICAL_AXES

    axes = _filter_axes(mesh, rules.lookup("kv_heads"))
    by_head = (layout.n_kv_heads % mesh_axis_size(mesh, axes) == 0
               and layout.row_lanes == layout.kv_width)
    logical = PAGED_KV_LOGICAL_AXES if by_head else None

    def one(leaf):
        return named_sharding(mesh, logical, rules, tuple(leaf.shape))

    return jax.tree_util.tree_map(one, pool)


def batch_sharding(mesh: Mesh, ndim: int = 2, batch_dim: int = 0,
                   rules: ShardingRules = ShardingRules()) -> NamedSharding:
    """Sharding for a host batch array: batch dim over the data axes."""
    axes = _filter_axes(mesh, rules.lookup("batch"))
    spec = [None] * ndim
    if axes:
        spec[batch_dim] = axes if len(axes) > 1 else axes[0]
    return NamedSharding(mesh, PartitionSpec(*spec))


def zero1_shardings(
    mesh: Mesh,
    boxed_params,
    rules: ShardingRules = ShardingRules(),
    opt_axes: Tuple[str, ...] = ("data",),
):
    """ZeRO-1: optimizer moments sharded like params *plus* the largest
    still-replicated dim partitioned over ``opt_axes`` (when divisible)."""
    axes_avail = _filter_axes(mesh, opt_axes)
    size = mesh_axis_size(mesh, axes_avail)

    def one(p: P):
        spec = list(logical_to_spec(p.axes, mesh, rules, _shape_of(p.value)))
        shape = _shape_of(p.value)
        already = {
            a for e in spec if e is not None
            for a in (e if isinstance(e, tuple) else (e,))
        }
        if axes_avail and shape is not None and not (set(axes_avail) & already):
            spec = spec + [None] * (len(shape) - len(spec))
            # largest replicated dim that divides the opt axes product
            cands = [
                (shape[i], i)
                for i in range(len(shape))
                if spec[i] is None and shape[i] % size == 0 and shape[i] >= size
            ]
            if cands:
                _, i = max(cands)
                spec[i] = axes_avail if len(axes_avail) > 1 else axes_avail[0]
            while spec and spec[-1] is None:
                spec.pop()
        return NamedSharding(mesh, PartitionSpec(*spec))

    return jax.tree_util.tree_map(one, boxed_params, is_leaf=lambda x: isinstance(x, P))
