"""GPipe-style pipeline parallelism on a mesh axis via shard_map + ppermute.

Layers are grouped into S stages; stage s's parameters live only on mesh
slice ``stage=s`` (leading param dim sharded over the axis). Microbatches
stream through the fill/compute/drain schedule: at tick t, stage s processes
microbatch t-s, then hands its activation to stage s+1 with a single
``lax.ppermute`` — the same collective-permute pattern a 1000-node pipeline
would run over ICI/DCN. The whole schedule is a ``lax.scan`` (HLO size
independent of microbatch count) and the stage body may be rematerialized.

This wrapper demonstrates/validates PP; the default 40-cell dry-run uses
DP x TP (DESIGN.md §5) with PP available per config.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as PS

__all__ = ["pipeline_apply"]


def pipeline_apply(
    stage_params,
    x: jax.Array,
    stage_fn: Callable,
    mesh: Mesh,
    *,
    axis: str = "stage",
    n_microbatches: int = None,
    remat: bool = True,
):
    """Run ``stage_fn(params_s, x)`` as an S-stage pipeline.

    stage_params: pytree with leading dim S (= mesh.shape[axis]), sharded over
                  ``axis``; stage_fn must be shape-preserving (x -> x), as for
                  homogeneous transformer stages.
    x:            (n_microbatches, mb, ...) microbatched input (replicated).
    Returns y with x's shape, fully replicated over ``axis``.
    """
    n_stages = mesh.shape[axis]
    if n_microbatches is None:
        n_microbatches = x.shape[0]
    assert x.shape[0] == n_microbatches
    total_ticks = n_microbatches + n_stages - 1
    fn = jax.checkpoint(stage_fn) if remat else stage_fn

    param_specs = jax.tree_util.tree_map(lambda _: PS(axis), stage_params)

    def run(params, xs):  # per-stage body; leading stage dim is size 1 here
        params = jax.tree_util.tree_map(lambda p: p[0], params)
        idx = jax.lax.axis_index(axis)
        state = jnp.zeros_like(xs[0])  # current activation at this stage
        outs = jnp.zeros_like(xs)
        perm = [(i, i + 1) for i in range(n_stages - 1)]

        def tick(carry, t):
            state, outs = carry
            # stage 0 ingests microbatch t during the fill phase
            mb = jax.lax.dynamic_index_in_dim(
                xs, jnp.minimum(t, n_microbatches - 1), 0, keepdims=False
            )
            state = jnp.where(idx == 0, mb, state)
            y = fn(params, state)
            # last stage emits microbatch t - (S-1) during the drain phase
            slot = t - (n_stages - 1)
            emit = jnp.logical_and(idx == n_stages - 1, slot >= 0)
            outs = jax.lax.cond(
                emit,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, y, jnp.maximum(slot, 0), 0
                ),
                lambda o: o,
                outs,
            )
            state = jax.lax.ppermute(y, axis, perm)
            return (state, outs), None

        (_, outs), _ = jax.lax.scan(tick, (state, outs), jnp.arange(total_ticks))
        # broadcast the last stage's collected outputs to every stage
        outs = jax.lax.psum(
            jnp.where(idx == n_stages - 1, outs, jnp.zeros_like(outs)), axis
        )
        return outs

    other = tuple(a for a in mesh.axis_names if a != axis)
    return jax.shard_map(
        run,
        mesh=mesh,
        in_specs=(param_specs, PS()),
        out_specs=PS(),
        check_vma=False,
    )(stage_params, x)
