"""Activation sharding constraints (MaxText-style ``with_sharding_constraint``
pins inside the model code).

Without these, GSPMD propagation can *replicate* whole subgraphs when a dim
doesn't divide the mesh (e.g. smollm's 15 query heads vs model=16 replicated
every attention score tensor on all 256 devices — measured 285x the useful
FLOPs in the baseline dry-run). ``constrain(x, names)`` pins each dim to the
mesh axes of its logical name *iff* the dim divides them; otherwise that dim
is left unconstrained — never wrong, at worst a no-op.

Outside a mesh context (unit tests, single CPU) it is the identity.
"""
from __future__ import annotations

import contextlib
from typing import Optional, Tuple

import jax
from jax.sharding import PartitionSpec

__all__ = ["constrain", "activation_rules", "mesh_context"]

# logical activation-dim name -> mesh axes (late-bound against the context mesh)
ACT_RULES = {
    "batch": ("pod", "data"),
    "heads": ("model",),
    "kv_heads": ("model",),
    "ffn": ("model",),
    "vocab": ("model",),
    "ssm_inner": ("model",),
    "embed": (),
    "seq": (),
    None: (),
}


def mesh_context(mesh):
    """``jax.set_mesh(mesh)`` as a context manager, or a no-op for None.
    Serving and training enter it around tracing and running, so the
    in-model constraints and the TP kernel routes see the mesh."""
    return jax.set_mesh(mesh) if mesh is not None else contextlib.nullcontext()


def _context_mesh():
    """The abstract mesh set by ``mesh_context``, or None outside one.
    Readable inside ``jit`` (it carries axis names and sizes only)."""
    mesh = jax.sharding.get_abstract_mesh()
    return None if mesh.empty else mesh


def activation_rules(name: Optional[str], mesh) -> Tuple[str, ...]:
    axes = ACT_RULES.get(name, ())
    return tuple(a for a in axes if a in mesh.shape)


def constrain(x: jax.Array, names: Tuple[Optional[str], ...]) -> jax.Array:
    """Pin x's sharding by logical dim names, with divisibility fallback."""
    mesh = _context_mesh()
    if mesh is None or mesh.size == 1:
        return x
    used = set()
    spec = []
    for dim, name in zip(x.shape, names):
        axes = tuple(a for a in activation_rules(name, mesh) if a not in used)
        size = 1
        for a in axes:
            size *= mesh.shape[a]
        if not axes or dim % size != 0:
            spec.append(None)
            continue
        used.update(axes)
        spec.append(axes if len(axes) > 1 else axes[0])
    while spec and spec[-1] is None:
        spec.pop()
    if not any(s is not None for s in spec):
        return x
    return jax.lax.with_sharding_constraint(x, PartitionSpec(*spec))
