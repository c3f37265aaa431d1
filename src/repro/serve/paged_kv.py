"""Paged KV cache: block pool + block tables + shared-prefix reuse.

The dense slot pool (serve/kv.py) spends ``n_slots x max_len`` positions of
device memory whether or not requests share content and forces admission to
prefill whole prompts in one bucketed shot. ``PagedKVManager`` instead owns
ONE device-resident *block pool* per the ``models/base.PagedKVLayout``
contract — every payload leaf lane-dense, ``(n_layers, n_phys_blocks,
block_size, row_lanes)`` with the kv heads side by side in rows padded to
whole lane tiles, which the TPU stores row-major so the fused kernel reads
it in place with no layout copy (DESIGN.md §6.1) — and gives each slot a
host-side *block table* naming which physical blocks hold its logical
positions ``[0, max_len)``:

- **Allocation** is block-granular and host-side (heap free list, O(log n)).
  A request's full span (chunk-padded prompt + decode budget) is reserved at
  admission, so decode never allocates mid-flight — backpressure is purely
  an admission-time "not yet" (``try_admit`` returns None, the scheduler
  leaves the request queued; completions free blocks, so no deadlock).
- **Shared-prefix reuse**: prompts are content-hashed per full block with a
  chained hash (block ``i``'s key commits to tokens ``[0, (i+1)*bs)``), so a
  hash hit guarantees the whole prefix matches. Matching blocks are attached
  to the new slot's table with a refcount bump — zero recompute, zero copy.
  The final prompt token is never served from the cache (its logits seed
  the first emitted token), so the block containing it stays private.
- **Copy-on-write**: blocks a slot would mutate must be private
  (``refcount == 1`` and unregistered). By construction decode only writes
  positions ``>= prompt_len``, which always land in private blocks, but
  ``ensure_private`` implements the general contract: a shared block is
  device-copied into a fresh block before the writer's table is repointed.
- **Eviction** is LRU over refcount-zero blocks: when a request finishes,
  its registered blocks stay in the prefix map (refcount 0, evictable);
  allocation draws free blocks first, then evicts the least recently used
  cached block.

Physical block ``n_phys - 1`` is the reserved *parking block*: freed decode
rows keep ticking for shape stability (DESIGN.md §4.1) and their junk
writes land there, never on a live block. Table entries beyond a slot's
reserved span also point at the parking block, which is what makes
speculative verify windows (DESIGN.md §10) safe for free: a ``spec_k``-wide
write that overhangs the reservation parks its overhang instead of
corrupting a neighbor, and rejected-window rollback is pure position
arithmetic — spec writes touch exactly the private block set normal decode
would, never a shared prefix block.

With ``mesh=`` the pool's lane dim shards over ``model`` by head group
when ``n_kv_heads`` divides the axis and the rows are unpadded, and
replicates otherwise (``distributed.sharding.paged_kv_shardings``); block
and offset dims are local, so the paged gather/scatter never cross
devices.

Numerics: with fp KV the paged engine is token-for-token identical to the
dense continuous engine (tests/test_paged_kv.py). With ``quantized_kv`` it
is deterministic but NOT bit-identical to dense: chunked prefill must
attend earlier chunks through the int8+scale round-trip, whereas the dense
whole-prompt prefill attends raw fp keys and only quantizes what it stores.
"""
from __future__ import annotations

import hashlib
import heapq
from collections import OrderedDict
from functools import partial
from typing import Dict, List, Optional

import jax
import numpy as np

from repro.distributed.constraints import mesh_context
from repro.models.base import PagedKVLayout
from repro.obs.trace import NULL_TRACER

__all__ = ["PagedKVManager", "hash_prompt_blocks"]


def hash_prompt_blocks(prompt: np.ndarray, block_size: int) -> List[bytes]:
    """Chained content hashes for each FULL block of ``prompt``: block i's
    digest commits to tokens [0, (i+1)*block_size), so equal digests imply
    equal whole prefixes (not just equal blocks)."""
    prompt = np.ascontiguousarray(prompt, np.int32)
    out: List[bytes] = []
    h = b""
    for i in range(len(prompt) // block_size):
        h = hashlib.sha256(h + prompt[i * block_size:(i + 1) * block_size].tobytes()).digest()
        out.append(h)
    return out


def _copy_block_body(cache, src, dst):
    """Copy physical block ``src`` -> ``dst`` on every leaf (COW). The block
    axis is dim 1 of the (layers, n_phys_blocks, block_size, lanes) layout —
    the copy spans every layer of the one block, all heads at once."""

    def one(buf):
        return buf.at[:, dst].set(buf[:, src])

    return jax.tree_util.tree_map(one, cache)


_copy_block = partial(jax.jit, donate_argnums=(0,))(_copy_block_body)


class PagedKVManager:
    def __init__(
        self,
        api,
        *,
        n_slots: int,
        max_len: int,
        block_size: int = 16,
        n_blocks: Optional[int] = None,
        prefix_cache: bool = True,
        quantized: bool = False,
        mesh=None,
        rules=None,
        tracer=None,
    ):
        if max_len % block_size:
            raise ValueError(f"max_len {max_len} must be a multiple of block_size {block_size}")
        self.n_slots = n_slots
        self.max_len = max_len
        self.block_size = block_size
        self.blocks_per_slot = max_len // block_size
        self.n_blocks = n_blocks if n_blocks is not None else n_slots * self.blocks_per_slot
        if self.n_blocks < self.blocks_per_slot:
            raise ValueError(
                f"n_blocks {self.n_blocks} cannot cover one slot's "
                f"{self.blocks_per_slot} blocks — no request could ever admit"
            )
        self.prefix_cache = prefix_cache
        self.quantized = quantized
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self.mesh = mesh
        # +1 physical row: the reserved parking block for inactive decode rows
        self.parking_block = self.n_blocks
        self.layout: PagedKVLayout = api.paged_kv_layout(self.n_blocks + 1, block_size)
        self.cache = api.init_paged_cache(self.n_blocks + 1, block_size, quantized=quantized)
        self.layout.check(self.cache)
        if mesh is not None:
            from repro.distributed.sharding import (
                ShardingRules, paged_kv_shardings, replicated_sharding,
            )

            self.rules = rules if rules is not None else ShardingRules()
            self._cache_sh = paged_kv_shardings(mesh, self.cache, self.layout, self.rules)
            self._rep = replicated_sharding(mesh)
            self.cache = jax.device_put(self.cache, self._cache_sh)
        else:
            self.rules = rules
            self._cache_sh = None
            self._rep = None
        self._copy = None  # lazily-built pinned-shardings COW program (mesh)
        # -- host state --------------------------------------------------
        self._slot_free_heap: List[int] = list(range(n_slots))
        self._slot_free_set = set(self._slot_free_heap)
        self._free_heap: List[int] = list(range(self.n_blocks))
        self._free_set = set(self._free_heap)
        self._ref = np.zeros(self.n_blocks, np.int64)
        # per-slot ordered owned blocks (prefix of the table that is real)
        self._slot_blocks: List[List[int]] = [[] for _ in range(n_slots)]
        self.tables = np.full((n_slots, self.blocks_per_slot), self.parking_block, np.int32)
        # prefix cache: chained hash -> block id, inverse map, and the LRU of
        # refcount-zero cached blocks (oldest first = evicted first)
        self._hash_to_block: Dict[bytes, int] = {}
        self._block_hash: Dict[int, bytes] = {}
        self._lru: "OrderedDict[int, None]" = OrderedDict()
        # -- gauges ------------------------------------------------------
        self.evictions = 0
        self.cow_copies = 0

    # -- introspection ------------------------------------------------------

    @property
    def n_free_slots(self) -> int:
        return len(self._slot_free_set)

    @property
    def blocks_free(self) -> int:
        return len(self._free_set)

    @property
    def blocks_active(self) -> int:
        """Blocks attached to at least one live slot."""
        return int((self._ref > 0).sum())

    @property
    def blocks_cached(self) -> int:
        """Refcount-zero blocks kept (evictable) for prefix reuse."""
        return len(self._lru)

    @property
    def blocks_in_use(self) -> int:
        return self.n_blocks - self.blocks_free

    @property
    def bytes_per_block(self) -> int:
        """Device bytes one physical block costs across every cache leaf and
        layer (int8 pools count their per-position scales). Global bytes —
        under a mesh this is the whole sharded pool, not one device's part."""
        return sum(
            leaf.nbytes // self.layout.n_phys_blocks
            for leaf in jax.tree_util.tree_leaves(self.cache)
        )

    @property
    def pool_bytes(self) -> int:
        """Total device bytes of the block pool (parking block included)."""
        return sum(leaf.nbytes for leaf in jax.tree_util.tree_leaves(self.cache))

    @property
    def kv_bytes_in_use(self) -> int:
        """Bytes of pool actually referenced by live or cached blocks."""
        return self.bytes_per_block * self.blocks_in_use

    @property
    def bytes_per_token(self) -> float:
        """KV bytes one logical token position costs — the capacity figure
        the int8 pool shrinks ~4x (int8 payload + f32 scale vs f32 payload)."""
        return self.bytes_per_block / self.block_size

    def refcount(self, block: int) -> int:
        return int(self._ref[block])

    # -- slot bookkeeping ---------------------------------------------------

    def alloc_slot(self) -> Optional[int]:
        if not self._slot_free_set:
            return None
        slot = heapq.heappop(self._slot_free_heap)
        self._slot_free_set.discard(slot)
        return slot

    def free_slot(self, slot: int) -> None:
        """Release a slot: every owned block drops a ref; registered blocks
        at refcount zero go to the LRU (still hit-able), private ones back to
        the free list. The table row is re-parked."""
        if slot in self._slot_free_set:
            raise ValueError(f"double free of slot {slot}")
        for b in self._slot_blocks[slot]:
            self._unref(b)
        self._slot_blocks[slot] = []
        self.tables[slot, :] = self.parking_block
        heapq.heappush(self._slot_free_heap, slot)
        self._slot_free_set.add(slot)

    def reset(self) -> None:
        for slot in range(self.n_slots):
            if slot not in self._slot_free_set:
                self.free_slot(slot)

    # -- block primitives ---------------------------------------------------

    def _unref(self, block: int) -> None:
        self._ref[block] -= 1
        assert self._ref[block] >= 0, f"refcount underflow on block {block}"
        if self._ref[block] == 0:
            if block in self._block_hash:
                self._lru[block] = None  # newest end; evicted last
            else:
                heapq.heappush(self._free_heap, block)
                self._free_set.add(block)

    def _unregister(self, block: int) -> None:
        h = self._block_hash.pop(block, None)
        if h is not None and self._hash_to_block.get(h) == block:
            del self._hash_to_block[h]

    def _alloc_block(self) -> int:
        """Claim a fresh block (refcount 1, unregistered): free list first,
        then evict the least-recently-used cached block. Callers must have
        checked availability (``try_admit`` does)."""
        if self._free_set:
            b = heapq.heappop(self._free_heap)
            self._free_set.discard(b)
        else:
            b, _ = self._lru.popitem(last=False)  # oldest
            self._unregister(b)
            self.evictions += 1
            if self.tracer.enabled:
                self.tracer.event("prefix_eviction", block=b,
                                  blocks_cached=len(self._lru))
        self._ref[b] = 1
        return b

    # -- admission ----------------------------------------------------------

    def match_prefix(self, prompt: np.ndarray) -> List[int]:
        """Longest chain of cached blocks matching the prompt's full blocks,
        capped so the final prompt token is always recomputed (its logits
        seed the first emitted token)."""
        if not self.prefix_cache:
            return []
        limit = (len(prompt) - 1) // self.block_size
        matched: List[int] = []
        for h in hash_prompt_blocks(prompt, self.block_size)[:limit]:
            b = self._hash_to_block.get(h)
            if b is None:
                break
            matched.append(b)
        return matched

    def plan_span(self, prompt_len: int, budget: int, chunk: int, cached_len: int) -> int:
        """Last logical position + 1 this request will ever write: the
        chunk-padded prefill end and the final decode write, capped at
        max_len (the scheduler caps the budget the same way)."""
        n_chunks = -(-(max(prompt_len - cached_len, 1)) // chunk)
        chunk_end = min(cached_len + n_chunks * chunk, self.max_len)
        return max(chunk_end, min(prompt_len + budget - 1, self.max_len))

    def try_admit(self, slot: int, prompt: np.ndarray, *, budget: int,
                  chunk: int) -> Optional[int]:
        """Build ``slot``'s block table: attach cached prefix blocks
        (refcount bump), reserve fresh blocks for the rest of the span.
        Returns the number of prompt tokens served from the prefix cache, or
        None when not enough blocks are free/evictable (admission defers —
        nothing is mutated). Runs in a ``serve.kv_reserve`` span."""
        with self.tracer.span("serve.kv_reserve"):
            assert slot not in self._slot_free_set and not self._slot_blocks[slot]
            matched = self.match_prefix(prompt)
            cached_len = len(matched) * self.block_size
            span = self.plan_span(len(prompt), budget, chunk, cached_len)
            n_total = -(-span // self.block_size)
            need = n_total - len(matched)
            matched_set = set(matched)
            evictable = sum(1 for b in self._lru if b not in matched_set)
            if need > len(self._free_set) + evictable:
                return None
            for b in matched:
                if self._ref[b] == 0:
                    self._lru.pop(b)
                self._ref[b] += 1
            blocks = matched + [self._alloc_block() for _ in range(need)]
            self._slot_blocks[slot] = blocks
            self.tables[slot, :] = self.parking_block
            self.tables[slot, :n_total] = blocks
            return cached_len

    def register_prompt(self, slot: int, prompt: np.ndarray) -> int:
        """After the slot's prefill completed: publish its full prompt
        blocks into the prefix map so future requests can share them.
        Returns how many new blocks were registered. Runs in a
        ``serve.kv_register`` span."""
        if not self.prefix_cache:
            return 0
        n = 0
        with self.tracer.span("serve.kv_register"):
            for i, h in enumerate(hash_prompt_blocks(prompt, self.block_size)):
                b = self._slot_blocks[slot][i]
                if h in self._hash_to_block or b in self._block_hash:
                    continue  # already published (possibly by another slot)
                self._hash_to_block[h] = b
                self._block_hash[b] = h
                n += 1
        return n

    # -- copy-on-write ------------------------------------------------------

    def is_private(self, slot: int, index: int) -> bool:
        b = self._slot_blocks[slot][index]
        return self._ref[b] == 1 and b not in self._block_hash

    def ensure_private(self, slot: int, index: int) -> int:
        """Make table entry ``index`` of ``slot`` safe to mutate. Shared
        blocks (refcount > 1) are device-copied into a fresh block; a block
        this slot owns exclusively but that is published in the prefix map
        is unregistered instead (cheaper — the bytes are about to change).
        Returns the (possibly new) physical block id."""
        b = self._slot_blocks[slot][index]
        if self._ref[b] > 1:
            if not self._free_set and not self._lru:
                raise RuntimeError("copy-on-write with no free or evictable block")
            nb = self._alloc_block()
            with mesh_context(self.mesh):
                self.cache = self._copy_fn()(
                    self.cache, np.int32(b), np.int32(nb)
                )
            self._ref[b] -= 1
            self._slot_blocks[slot][index] = nb
            self.tables[slot, index] = nb
            self.cow_copies += 1
            if self.tracer.enabled:
                self.tracer.event("cow_copy", slot=slot, index=index,
                                  src_block=b, dst_block=nb)
            return nb
        if b in self._block_hash:
            self._unregister(b)
        return b

    def _copy_fn(self):
        if self.mesh is None:
            return _copy_block
        if self._copy is None:
            self._copy = jax.jit(
                _copy_block_body,
                donate_argnums=(0,),
                in_shardings=(self._cache_sh, self._rep, self._rep),
                out_shardings=self._cache_sh,
            )
        return self._copy
