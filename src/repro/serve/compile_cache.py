"""Shape-bucketed prefill with a per-(bucket, batch) jit cache.

A heavy-traffic stream has ~as many distinct prompt lengths as requests; a
naive ``jit(prefill)`` recompiles for every one of them. Here prompts are
right-padded to power-of-two length buckets, so the whole stream compiles
``O(log2(max_len))`` programs and then only ever hits the cache.

Right-padding (not the static engine's left-padding) is what keeps bucketing
*exact*: with causal attention the pad tokens sit strictly in the future of
every real token, so the real prefix's activations — and the KV rows
``[0, prompt_len)`` — are bit-identical to an unpadded prefill. The logits
for the last real token are picked out with ``prefill(..., last_index=
prompt_len - 1)``; pad rows of the emitted cache are never attended because
decode masks KV positions ``>= valid_len`` per row.

The emitted cache is padded to the pool's full ``max_len`` (leaves
``(L, 1, max_len, ...)``), so the slot splice in serve/kv.py has a single
shape regardless of bucket.

With ``mesh=`` the per-bucket programs pin their in/out placements: params
arrive under ``param_shardings``, tokens/last_index replicated, the emitted
cache under the KV layout contract (``kv_heads`` over the ``model`` axis,
divisibility fallback to replication) and the logits replicated. Explicit
shardings mean a request whose operands arrive placed differently is
resharded, not recompiled — the one-compile-per-bucket guarantee survives
sharded inputs.
"""
from __future__ import annotations

from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.distributed.constraints import mesh_context
from repro.obs.trace import NULL_TRACER

__all__ = ["BucketedPrefill", "ChunkedPrefill", "bucket_for"]


def bucket_for(prompt_len: int, max_len: int, *, min_bucket: int = 16) -> int:
    """Smallest power-of-two >= prompt_len (floored at min_bucket, capped at
    max_len — the terminal bucket is max_len itself, pow2 or not)."""
    if prompt_len > max_len:
        raise ValueError(f"prompt_len {prompt_len} exceeds max_len {max_len}")
    b = max(min_bucket, 1 << max(prompt_len - 1, 0).bit_length())
    return min(b, max_len)


class BucketedPrefill:
    """Callable wrapper over ``api.prefill`` with bucketing + jit caching.

    ``__call__(params, prompt)`` takes one un-padded int32 prompt and returns
    ``(first_logits (1,1,V), cache)`` where ``first_logits`` are the logits
    after the last real token and ``cache`` covers the full ``max_len``.
    ``prompt_len``/``last_index`` ride through as traced values, so requests
    of every length inside a bucket share one compiled program.
    """

    def __init__(self, api, *, max_len: int, quantized: bool = False,
                 min_bucket: int = 16, mesh=None, rules=None,
                 param_sh=None, tracer=None):
        self.api = api
        self.max_len = max_len
        self.quantized = quantized
        self.min_bucket = min_bucket
        self.mesh = mesh
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self._fns: Dict[Tuple[int, int], Callable] = {}
        self.hits = 0
        self.misses = 0
        if mesh is not None:
            from repro.distributed.sharding import (
                ShardingRules, api_param_shardings, replicated_sharding,
            )
            from repro.nn.module import unbox

            self.rules = rules if rules is not None else ShardingRules()
            self._param_sh = (param_sh if param_sh is not None
                              else api_param_shardings(mesh, api, self.rules))
            self._rep = replicated_sharding(mesh)
            # unboxed ShapeDtypeStruct tree of the params — abstract-traced
            # once here, reused to eval_shape every bucket's output layout
            self._param_struct = unbox(jax.eval_shape(api.init, jax.random.PRNGKey(0)))
        else:
            self.rules = rules
            self._param_sh = None
            self._rep = None
            self._param_struct = None

    def _mesh_ctx(self):
        """Activate the mesh while tracing/running so in-model constraints
        and the TP kernel routes see it; identity off-mesh."""
        return mesh_context(self.mesh)

    @property
    def compiled_buckets(self) -> List[Tuple[int, int]]:
        return sorted(self._fns)

    def bucket_for(self, prompt_len: int) -> int:
        return bucket_for(prompt_len, self.max_len, min_bucket=self.min_bucket)

    def fn(self, bucket: int, batch: int = 1) -> Callable:
        """The jitted prefill program for one (bucket, batch) shape."""
        key = (bucket, batch)
        cached = self._fns.get(key)
        if cached is not None:
            self.hits += 1
            return cached
        self.misses += 1

        def prefill(params, tokens, last_index):
            return self.api.prefill(
                params, {"tokens": tokens}, max_len=self.max_len,
                quantized=self.quantized, last_index=last_index,
            )

        if self.mesh is None:
            fn = jax.jit(prefill)
        else:
            from repro.distributed.sharding import kv_cache_shardings

            with self._mesh_ctx():
                out_struct = jax.eval_shape(
                    prefill,
                    self._param_struct,
                    jax.ShapeDtypeStruct((batch, bucket), jnp.int32),
                    jax.ShapeDtypeStruct((batch,), jnp.int32),
                )
            cache_sh = kv_cache_shardings(self.mesh, out_struct[1], self.rules)
            fn = jax.jit(
                prefill,
                in_shardings=(self._param_sh, self._rep, self._rep),
                out_shardings=(self._rep, cache_sh),
            )
        self._fns[key] = fn
        return fn

    def __call__(self, params, prompt: np.ndarray):
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        plen = len(prompt)
        if plen < 1:
            raise ValueError("empty prompt")
        bucket = self.bucket_for(plen)
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :plen] = prompt  # right-pad: exact under causal attention
        misses0 = self.misses
        t0 = self.tracer.clock() if self.tracer.enabled else 0.0
        with self._mesh_ctx():
            logits, cache = self.fn(bucket, 1)(
                params, jnp.asarray(toks), jnp.asarray([plen - 1], jnp.int32)
            )
        if self.tracer.enabled and self.misses > misses0:
            # jit compiles lazily on the first call, so this first-call span
            # is trace + compile + run for the new (bucket, 1) shape
            self.tracer.add_span("compile", "scheduler", t0, self.tracer.clock(),
                                 kind="prefill_bucket", bucket=bucket, batch=1)
        return logits, cache


class ChunkedPrefill:
    """Chunked prefill into a paged block pool: ONE compiled program total.

    Where ``BucketedPrefill`` compiles ``O(log2 max_len)`` bucket shapes and
    must run a whole prompt in one shot, the chunked path appends the prompt
    ``chunk`` tokens at a time through ``api.prefill_chunk`` — a single
    ``(1, chunk)`` program whose ``start``/``last_in_chunk`` ride through as
    traced scalars. Each chunk's queries attend the pool's gathered view, so
    later chunks see earlier chunks' (and any shared prefix's) cached KV;
    the final chunk is right-padded, which is exact for the same causal
    reason as bucketing (pad queries sit in the future; their junk KV writes
    land past the prompt and are overwritten by decode before attended).

    ``__call__`` starts at ``cached_len`` (the shared-prefix hit length from
    ``PagedKVManager.try_admit``), so a prefix hit skips those chunks
    entirely — the TTFT win of prefix reuse.

    The pool is donated through every chunk call; callers thread the
    returned cache back into their manager. With ``mesh=`` the program pins
    params/pool placements exactly like the bucketed path.
    """

    def __init__(self, api, *, chunk: int, max_len: int, mesh=None, rules=None,
                 param_sh=None, cache_sh=None, tracer=None):
        if chunk < 1:
            raise ValueError(f"chunk must be >= 1, got {chunk}")
        self.api = api
        self.chunk = chunk
        self.max_len = max_len
        self.mesh = mesh
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self.hits = 0
        self.misses = 0
        self._fn: Optional[Callable] = None

        def run(params, cache, toks, table, start, last_in_chunk):
            return self.api.prefill_chunk(params, toks, cache, table, start, last_in_chunk)

        if mesh is None:
            self._build = lambda: jax.jit(run, donate_argnums=(1,))
        else:
            from repro.distributed.sharding import (
                ShardingRules, api_param_shardings, replicated_sharding,
            )

            rules = rules if rules is not None else ShardingRules()
            psh = param_sh if param_sh is not None else api_param_shardings(mesh, api, rules)
            rep = replicated_sharding(mesh)
            assert cache_sh is not None, "mesh path needs the pool's shardings"
            self._build = lambda: jax.jit(
                run,
                donate_argnums=(1,),
                in_shardings=(psh, cache_sh, rep, rep, rep, rep),
                out_shardings=(rep, cache_sh),
            )

    def _mesh_ctx(self):
        return mesh_context(self.mesh)

    def fn(self) -> Callable:
        if self._fn is None:
            self.misses += 1  # one miss ever: the single (1, chunk) program
            self._fn = self._build()
        else:
            self.hits += 1
        return self._fn

    def __call__(self, params, cache, table_row: np.ndarray, prompt: np.ndarray,
                 cached_len: int = 0, *, rid: Optional[int] = None):
        """Append ``prompt[cached_len:]`` to the pool chunk by chunk.

        Returns ``(last_logits (1,1,V), cache, n_chunks)`` where
        ``last_logits`` are the logits after the prompt's final token —
        bit-identical to the bucketed whole-prompt prefill the dense
        continuous engine admits with (tests/test_paged_kv.py).

        Each chunk's staging, upload and dispatch is one
        ``serve.prefill_chunk`` span (``rid``, ``chunk``) on the scheduler
        track; the one-ever program build additionally records a
        ``compile`` span with the same stamps.
        """
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        plen = len(prompt)
        if plen < 1:
            raise ValueError("empty prompt")
        if not 0 <= cached_len <= plen - 1:
            raise ValueError(f"cached_len {cached_len} outside [0, {plen - 1}]")
        table = jnp.asarray(table_row, jnp.int32).reshape(1, -1)
        tracer = self.tracer
        logits = None
        n_chunks = 0
        start = cached_len
        while start < plen:
            end = min(start + self.chunk, plen)
            misses0 = self.misses
            with tracer.span("serve.prefill_chunk", rid=rid, chunk=n_chunks) as span:
                toks = np.zeros((1, self.chunk), np.int32)
                toks[0, : end - start] = prompt[start:end]
                last = (plen - 1 - start) if end == plen else (self.chunk - 1)
                with self._mesh_ctx():
                    logits, cache = self.fn()(
                        params, cache, jnp.asarray(toks), table,
                        jnp.asarray([start], jnp.int32), jnp.asarray([last], jnp.int32),
                    )
            if tracer.enabled and self.misses > misses0:
                tracer.add_span("compile", "scheduler", span.t0, span.t1,
                                kind="prefill_chunk", chunk_size=self.chunk)
            n_chunks += 1
            start = end
        return logits, cache, n_chunks
