"""KV slot manager for continuous batching.

Owns ONE device-resident KV cache shaped per the ``models/base.py``
``KVCacheLayout`` contract — every leaf ``(n_layers, n_slots, max_len,
n_kv_heads, head_dim)`` (fp or quantized int8+scale form) — and treats the
batch axis as a pool of request slots:

- ``alloc()`` / ``free(slot)`` — host-side slot bookkeeping (a min-heap plus
  a membership set: lowest-index alloc and double-free detection are
  O(log n) / O(1), no device traffic). Freeing does not zero the slot: every
  position a future request can attend to is overwritten first (prefill
  rewrites ``[0, max_len)``; decode writes position ``p`` before any row
  attends to it, and unwritten tail positions are masked out by the per-row
  ``valid_len``).
- ``write_prefill(slot, prefill_cache)`` — splice a single-request prefill
  cache (leaves ``(n_layers, 1, max_len, ...)``) into the slot row with one
  jitted donate+dynamic_update_slice per leaf. The slot index is a traced
  scalar, so this compiles exactly once per cache pytree structure.

With ``mesh=`` the pool shards per the KV layout contract: ``kv_heads`` over
the ``model`` axis (divisibility fallback to replication — see
``distributed.sharding.kv_cache_shardings``), everything else local. The
splice program pins matching in/out NamedShardings, so it stays a
single-device-local dynamic_update_slice on every shard (the slot axis is
never split) and still compiles exactly once.
"""
from __future__ import annotations

import heapq
from functools import partial
from typing import List, Optional

import jax
import jax.numpy as jnp

from repro.distributed.constraints import mesh_context
from repro.models.base import KVCacheLayout, kv_cache_layout

__all__ = ["KVSlotManager"]


def _splice_body(cache, pcache, slot):
    """Write a batch-1 prefill cache into row ``slot`` of the slot cache.
    Shared by the plain jitted program and the mesh path's pinned-shardings
    jit — one definition of the splice semantics."""

    def one(buf, upd):
        start = (0, slot) + (0,) * (buf.ndim - 2)
        return jax.lax.dynamic_update_slice(buf, upd.astype(buf.dtype), start)

    return jax.tree_util.tree_map(one, cache, pcache)


_splice_slot = partial(jax.jit, donate_argnums=(0,))(_splice_body)


class KVSlotManager:
    def __init__(self, api, *, n_slots: int, max_len: int, quantized: bool = False,
                 mesh=None, rules=None):
        self.n_slots = n_slots
        self.max_len = max_len
        self.quantized = quantized
        self.mesh = mesh
        self.cache = api.init_cache(n_slots, max_len, quantized=quantized)
        if mesh is not None:
            from repro.distributed.sharding import (
                ShardingRules, kv_cache_shardings, replicated_sharding,
            )

            self.rules = rules if rules is not None else ShardingRules()
            self._cache_sh = kv_cache_shardings(mesh, self.cache, self.rules)
            self._rep = replicated_sharding(mesh)
            self.cache = jax.device_put(self.cache, self._cache_sh)
        else:
            self.rules = rules
            self._cache_sh = None
        self._splice = None  # built lazily: needs the prefill-cache structure
        self.layout: KVCacheLayout = kv_cache_layout(self.cache)
        assert self.layout.n_slots == n_slots and self.layout.max_len == max_len, self.layout
        # lowest-index-first free pool: a heap for O(log n) alloc plus a
        # parallel membership set for O(1) double-free detection (the old
        # sorted-list pool paid O(n) `in` + sort() on every free)
        self._free_heap: List[int] = list(range(n_slots))
        self._free_set = set(self._free_heap)

    # -- slot bookkeeping ---------------------------------------------------

    @property
    def n_free(self) -> int:
        return len(self._free_set)

    def alloc(self) -> Optional[int]:
        """Claim a free slot (lowest index first); None when fully occupied."""
        if not self._free_set:
            return None
        slot = heapq.heappop(self._free_heap)
        self._free_set.discard(slot)
        return slot

    def free(self, slot: int) -> None:
        if not 0 <= slot < self.n_slots:
            raise ValueError(f"slot {slot} out of range [0, {self.n_slots})")
        if slot in self._free_set:
            raise ValueError(f"double free of slot {slot}")
        heapq.heappush(self._free_heap, slot)
        self._free_set.add(slot)

    def reset(self) -> None:
        """Return every slot to the free pool (cache contents stay; see
        module docstring for why stale data is unreachable)."""
        self._free_heap = list(range(self.n_slots))
        self._free_set = set(self._free_heap)

    # -- device ops ---------------------------------------------------------

    def _splice_fn(self, prefill_cache):
        """The jitted splice for this pool: the module-level program off-mesh,
        or a pinned-shardings instance program on a mesh (built once — the
        prefill cache structure is fixed per pool)."""
        if self.mesh is None:
            return _splice_slot
        if self._splice is None:
            from repro.distributed.sharding import kv_cache_shardings

            pcache_sh = kv_cache_shardings(self.mesh, prefill_cache, self.rules)
            self._splice = jax.jit(
                _splice_body,
                donate_argnums=(0,),
                in_shardings=(self._cache_sh, pcache_sh, self._rep),
                out_shardings=self._cache_sh,
            )
        return self._splice

    def write_prefill(self, slot: int, prefill_cache) -> None:
        """Splice a batch-1 prefill cache (leaves (L, 1, max_len, ...)) into
        row ``slot``. The prefill must have been run with the pool's
        ``max_len`` and quantization so leaf shapes/dtypes line up."""
        pl = kv_cache_layout(prefill_cache)
        if pl.n_slots != 1 or pl.max_len != self.max_len:
            raise ValueError(f"prefill cache layout {pl} does not match pool {self.layout}")
        with mesh_context(self.mesh):
            self.cache = self._splice_fn(prefill_cache)(
                self.cache, prefill_cache, jnp.asarray(slot, jnp.int32)
            )
