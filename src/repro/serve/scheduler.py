"""Slot-based continuous-batching scheduler.

The decode batch is a fixed pool of ``n_slots`` rows. A request is admitted
by prefilling it ALONE (batch-1, shape-bucketed — see compile_cache.py) and
splicing its KV into a free slot row; from then on it rides the shared jitted
decode step. When a request hits EOS / its token budget, its slot is freed
*immediately* and the next queued request is admitted on the following tick —
no head-of-line blocking on the slowest request in a batch.

The jitted tick has ONE signature for the whole run —
``(params, tok (S,1), cache, positions (S,))`` — with per-slot positions
carried as a device array (the per-row decode path in nn/attention.py), so
admissions/completions never trigger a recompile. EOS / budget / activity
bookkeeping lives on the host, which must sync every step anyway to stream
tokens out.

Inactive (free) rows keep decoding junk at their last position — shape
stability is worth one wasted row of FLOPs — and their writes are harmless:
a freshly admitted request's prefill overwrites ``[0, max_len)`` of its slot,
and decode overwrites position ``p`` before any attention step can see it
(positions ``>= valid_len`` are masked per row).

Supported families: attention-KV models (``family == "lm"``) without MoE.
Recurrent state (hybrid/xlstm) cannot be right-pad-bucketed (pad tokens
corrupt the state), and MoE capacity routing couples batch rows, which both
breaks bit-exactness and would let junk rows steal expert capacity.

Multi-device: pass ``mesh=`` (+ optional ``rules=``) and the whole runtime
tensor-parallelizes — params placed with ``param_shardings``, the KV slot
pool sharded ``kv_heads``-over-``model`` per the layout contract, and the
jitted tick / bucketed prefill / slot splice all pinning explicit in/out
NamedShardings so the one-compile-per-shape guarantee survives sharded
inputs (DESIGN.md §5). Scheduling state (tokens, positions, the queue)
stays host-side and replicated: scheduling decisions are identical on every
device, so outputs are token-for-token the single-device outputs.

Speculative decoding (``spec_draft=`` + ``spec_k=``, DESIGN.md §10): a cheap
draft model rides the same slot indices in its own dense KV pool and
proposes ``spec_k - 1`` tokens per slot per round; the target scores the
whole ``spec_k``-wide window in ONE batched verify step. The accept rule —
keep the longest prefix of draft tokens that match the target's own greedy
choices, then always take the target's next token — makes greedy spec
decode EXACT: emitted tokens are token-for-token what target-only decode
would produce, for any draft. Rollback of a rejected suffix is free: both
caches are position-masked, so resetting the host-side ``_pos`` makes the
stale writes unattendable, and the next round's ``spec_k`` consecutive
writes (advance is always 1..spec_k) overwrite them before any query can
reach them. ``spec_k=1`` degenerates to the plain tick (no draft machinery
is built). Speculative + ``mesh`` is not implemented.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.distributed.constraints import mesh_context
from repro.models.base import ArchConfig, ModelAPI
from repro.obs.trace import Tracer, get_tracer
from repro.serve.compile_cache import BucketedPrefill, ChunkedPrefill
from repro.serve.kv import KVSlotManager
from repro.serve.metrics import RequestMetrics, RunMetrics
from repro.serve.paged_kv import PagedKVManager

__all__ = [
    "PagedSlotScheduler",
    "Request",
    "SlotScheduler",
    "replay_arrivals",
    "scheduler_supports",
]


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray  # (S,) int32
    max_new_tokens: int = 16
    eos_id: Optional[int] = None
    output: Optional[np.ndarray] = None
    # streaming: called once per emitted token (including EOS), in order
    on_token: Optional[Callable[[int], None]] = None
    metrics: Optional[RequestMetrics] = None


def scheduler_supports(arch: ArchConfig) -> bool:
    """Whether SlotScheduler can serve this architecture (see module doc).
    SWA is excluded too: the ring cache is shorter than max_len, which
    breaks the full-length KVCacheLayout contract the slot pool assumes."""
    return arch.family == "lm" and arch.n_experts == 0 and arch.window is None


@dataclasses.dataclass
class _SlotState:
    req: Request
    remaining: int  # tokens still allowed (after the prefill token)
    emitted: List[int]


class SlotScheduler:
    engine_name = "continuous"  # registry/trace label (paged overrides)

    def __init__(
        self,
        api: ModelAPI,
        params,
        arch: ArchConfig,
        *,
        n_slots: int = 4,
        max_len: int = 256,
        quantized_kv: bool = False,
        min_bucket: int = 16,
        clock: Callable[[], float] = time.monotonic,
        tracer: Optional[Tracer] = None,
        registry=None,
        mesh=None,
        rules=None,
        spec_draft=None,
        spec_k: int = 1,
    ):
        if not scheduler_supports(arch):
            raise ValueError(
                f"SlotScheduler supports non-MoE, non-SWA 'lm' models; got family="
                f"{arch.family!r} n_experts={arch.n_experts} window={arch.window} "
                f"(use the static engine)"
            )
        if spec_k < 1:
            raise ValueError(f"spec_k must be >= 1, got {spec_k}")
        if spec_draft is not None and spec_k > 1 and mesh is not None:
            raise NotImplementedError(
                "speculative decoding is single-device for now (the draft "
                "pool and fused round program are not mesh-pinned)"
            )
        self.api = api
        self.arch = arch
        self.n_slots = n_slots
        self.max_len = max_len
        self.clock = clock
        # observability: explicit tracer wins, else the process-global hook
        # (NULL_TRACER unless launch --trace-out installed one); the registry
        # stays None when the caller didn't opt in
        self.tracer = tracer if tracer is not None else get_tracer()
        self.registry = registry
        self._tick_compiled = False  # first _run_tick compiles the step
        self.mesh = mesh
        if mesh is not None:
            from repro.distributed.sharding import (
                ShardingRules, api_param_shardings, replicated_sharding,
            )

            self.rules = rules if rules is not None else ShardingRules()
            self._param_sh = api_param_shardings(mesh, api, self.rules)
            self._rep = replicated_sharding(mesh)
            params = jax.device_put(params, self._param_sh)
        else:
            self.rules = rules
            self._param_sh = None
            self._rep = None
        self.params = params
        self._min_bucket = min_bucket
        self._init_kv_prefill(api, quantized_kv, min_bucket)
        self.metrics = RunMetrics(n_slots=n_slots)
        self._bind_metrics()
        self._stamp_kv_gauges()
        # prefill-compile counter at the start of the current metrics window:
        # BucketedPrefill.misses is cumulative across the scheduler's life,
        # so a timed window must report the delta, not the total (otherwise
        # warmup-run compiles leak into the timed report).
        self._prefill_miss_base = self.prefill.misses
        self.queue: List[Request] = []
        self.completed: List[Request] = []
        self._slots: List[Optional[_SlotState]] = [None] * n_slots
        self._tok = np.zeros(n_slots, np.int32)  # last emitted token per slot
        self._pos = np.zeros(n_slots, np.int32)  # cache position of the NEXT write
        self._tick_fn = self._build_tick()
        # speculative decoding: spec_k == 1 degenerates to the plain tick
        self.spec_k = spec_k
        self._spec_api = None
        if spec_draft is not None and spec_k > 1:
            self._init_spec(spec_draft)
            self._spec_fn = self._build_spec_fn()

    def _mesh_ctx(self):
        return mesh_context(self.mesh)

    def _metric_labels(self) -> dict:
        return dict(mode=self.arch.compute_mode, engine=self.engine_name,
                    route=self.arch.paged_attn_route)

    def _bind_metrics(self) -> None:
        if self.registry is not None:
            self.metrics.bind_registry(self.registry, **self._metric_labels())

    # -- dense-vs-paged hooks (PagedSlotScheduler overrides these) ----------

    def _init_kv_prefill(self, api, quantized_kv: bool, min_bucket: int) -> None:
        self.kv = KVSlotManager(api, n_slots=self.n_slots, max_len=self.max_len,
                                quantized=quantized_kv, mesh=self.mesh, rules=self.rules)
        self.prefill = BucketedPrefill(
            api, max_len=self.max_len, quantized=quantized_kv, min_bucket=min_bucket,
            mesh=self.mesh, rules=self.rules, param_sh=self._param_sh,
            tracer=self.tracer,
        )

    @property
    def _slots_available(self) -> int:
        return self.kv.n_free

    def _release_slot(self, slot: int) -> None:
        self.kv.free(slot)

    # -- KV byte accounting (DESIGN.md §7) ----------------------------------

    def _kv_pool_bytes(self) -> int:
        return sum(l.nbytes for l in jax.tree_util.tree_leaves(self.kv.cache))

    def _kv_bytes_per_token(self) -> float:
        return self._kv_pool_bytes() / (self.n_slots * self.max_len)

    def _stamp_kv_gauges(self) -> None:
        self.metrics.kv_pool_bytes = self._kv_pool_bytes()
        self.metrics.kv_bytes_per_token = self._kv_bytes_per_token()

    def _decode_kv_bytes(self, active: List[int]) -> int:
        """Modeled KV bytes one decode tick reads from HBM: the dense per-row
        decode streams each active row's live context once."""
        bpt = self.metrics.kv_bytes_per_token
        return int(bpt * sum(int(self._pos[i]) + 1 for i in active))

    def _run_tick(self) -> np.ndarray:
        tracer = self.tracer
        with tracer.span("serve.upload"):
            tok, pos = jnp.asarray(self._tok), jnp.asarray(self._pos)
        with self._mesh_ctx(), tracer.span("serve.dispatch"):
            nxt, self.kv.cache = self._tick_fn(self.params, self.kv.cache, tok, pos)
        with tracer.span("serve.token_pull"):
            # repro: noqa-RPA001 -- the tick barrier: emitted tokens must reach
            # the host to route into per-request queues / detect EOS
            return np.asarray(nxt)

    def _build_tick(self):
        decode = self.api.decode_step

        def tick(params, cache, tok, pos):
            logits, cache = decode(params, tok[:, None], cache, pos)
            return jnp.argmax(logits[:, -1], -1).astype(jnp.int32), cache

        if self.mesh is None:
            return jax.jit(tick, donate_argnums=(1,))
        # pinned in/out placements: params under param_shardings, the slot
        # cache under the KV layout contract (donated in place), next-token
        # ids and per-slot positions replicated — so the one tick program
        # keeps its single signature no matter how operands arrive placed
        return jax.jit(
            tick,
            donate_argnums=(1,),
            in_shardings=(self._param_sh, self.kv._cache_sh, self._rep, self._rep),
            out_shardings=(self._rep, self.kv._cache_sh),
        )

    # -- speculative decoding (DESIGN.md §10) -------------------------------

    def _spec_verify_api(self):
        """The target's multi-token verify callable for this KV layout
        (paged overrides with decode_verify_paged)."""
        return self.api.decode_verify

    def _spec_operands(self):
        """Extra per-round operands of the verify step (paged: the live
        block tables — data, not shape, so the program never recompiles)."""
        return ()

    def _init_spec(self, spec_draft) -> None:
        """Build the draft-side state: the draft model rides the SAME slot
        indices as the target in its own dense fp KV pool (a rejected window
        needs no rollback work — positions are the only bookkeeping), plus a
        bucketed prefill so admission can seed the draft cache."""
        if self._spec_verify_api() is None:
            raise ValueError(
                f"speculative decoding needs a model family with a "
                f"multi-token verify path; {self.arch.family!r} has none"
            )
        dapi, dparams, darch = spec_draft
        if darch.window is not None or darch.n_experts:
            raise ValueError("draft model must be a non-MoE, non-SWA 'lm' model")
        self._spec_api = dapi
        self._spec_params = dparams
        self._spec_arch = darch
        self._spec_kv = KVSlotManager(
            dapi, n_slots=self.n_slots, max_len=self.max_len,
            quantized=False, mesh=None, rules=None,
        )
        self._spec_prefill = BucketedPrefill(
            dapi, max_len=self.max_len, quantized=False,
            min_bucket=self._min_bucket, mesh=None, rules=None,
            param_sh=None, tracer=self.tracer,
        )

    def _build_spec_fn(self):
        """ONE jitted program per speculative round (both caches donated):
        the draft rolls ``spec_k`` sequential decode steps under lax.scan —
        consuming the current token then its own proposals, so its KV always
        covers the window — and the target verifies the ``spec_k``-wide
        window (current token + spec_k-1 proposals) in a single batched
        step. Two host dispatches per round would also work; one keeps the
        draft loop off the dispatch critical path entirely."""
        draft_decode = self._spec_api.decode_step
        verify = self._spec_verify_api()
        c = self.spec_k

        def spec_round(params, cache, dparams, dcache, tok, pos, *extra):
            def roll(carry, j):
                t, dc = carry
                logits, dc = draft_decode(dparams, t[:, None], dc, pos + j)
                nt = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)
                return (nt, dc), nt

            # scan step j consumes window token j at position pos + j and
            # writes its draft KV there; the last step's proposal (props[-1])
            # is beyond the window and discarded
            (_, dcache), props = jax.lax.scan(
                roll, (tok, dcache), jnp.arange(c, dtype=jnp.int32)
            )
            window = jnp.concatenate([tok[:, None], props[:-1].T], axis=1)
            logits, cache = verify(params, window, cache, pos, *extra)
            nxt = jnp.argmax(logits, -1).astype(jnp.int32)  # (S, C) greedy
            return window, nxt, cache, dcache

        return jax.jit(spec_round, donate_argnums=(1, 3))

    def _run_spec_tick(self):
        with self._mesh_ctx():
            window, nxt, self.kv.cache, self._spec_kv.cache = self._spec_fn(
                self.params, self.kv.cache, self._spec_params,
                self._spec_kv.cache, jnp.asarray(self._tok),
                jnp.asarray(self._pos), *self._spec_operands(),
            )
        # repro: noqa-RPA001 -- tick barrier (see SlotScheduler._run_tick):
        # the accept rule compares draft vs target tokens on the host
        return np.asarray(window), np.asarray(nxt)

    def _spec_admit(self, slot: int, req: Request) -> None:
        """Seed the draft's KV for a freshly admitted request (no-op when
        speculation is off). The draft prefill's own next-token logits are
        discarded — the target's admission token is the ground truth the
        first round drafts from."""
        if self._spec_api is None:
            return
        _logits, dcache = self._spec_prefill(self._spec_params, req.prompt)
        self._spec_kv.write_prefill(slot, dcache)

    def _spec_tick(self) -> bool:
        """One speculative round over the slot batch: draft spec_k-1 tokens,
        verify the window in one target step, emit the accepted prefix plus
        the target's correction. Per row the advance ``e`` is 1..spec_k
        tokens; ``_pos += e`` IS the rollback — stale cache writes past the
        new position are causally masked and overwritten next round."""
        self._admit()
        active = [i for i, s in enumerate(self._slots) if s is not None]
        if not active:
            return False
        if not self._tick_compiled and self.tracer.enabled:
            with self.tracer.span("compile", "scheduler", kind="spec_round",
                                  n_slots=self.n_slots, spec_k=self.spec_k):
                window, nxt = self._run_spec_tick()
        else:
            window, nxt = self._run_spec_tick()
        self._tick_compiled = True
        self.metrics.record_step(
            len(active), kv_bytes_read=self._decode_kv_bytes(active))
        c = self.spec_k
        drafted = accepted = 0
        for i in active:
            st = self._slots[i]
            w, g = window[i], nxt[i]
            # accept rule: longest prefix of draft tokens matching the
            # target's greedy choices (w[j] drafted token j, g[j-1] the
            # target's token after window slot j-1), then ALWAYS take
            # the target's next token g[a-1] — exactness for free
            a = 1
            while a < c and w[a] == g[a - 1]:
                a += 1
            e = 0
            done = False
            for j in range(a):
                e += 1
                done = self._emit(st, int(g[j]))
                if done:
                    break  # budget/EOS truncation: e <= a tokens used
            self._tok[i] = g[e - 1]
            self._pos[i] += e
            drafted += c - 1
            accepted += e - 1
            if done:
                self._finish(st.req, st, i)
                self._slots[i] = None
                self._release_slot(i)
                self._tok[i] = 0
                self._pos[i] = 0
        self.metrics.record_spec_round(len(active), drafted, accepted)
        if self.tracer.enabled:
            # per-round event carrying the same counts the metrics
            # accumulate — trace_report-style reconciliation sums these
            self.tracer.event("spec_round", track="scheduler",
                              rows=len(active), drafted=drafted,
                              accepted=accepted)
        return True

    # -- queue --------------------------------------------------------------

    @property
    def n_active(self) -> int:
        return sum(s is not None for s in self._slots)

    @property
    def has_work(self) -> bool:
        return bool(self.queue) or self.n_active > 0

    def reset_metrics(self) -> None:
        """Start a fresh RunMetrics window (aggregates are otherwise
        cumulative across run() calls — e.g. warmup + timed run). Snapshots
        the prefill-compile counter so the new window reports only compiles
        it actually triggered. A bound registry carries over: its counters
        keep accumulating (Prometheus semantics), only the summary gauges
        restart with the window."""
        self.metrics = RunMetrics(n_slots=self.n_slots)
        self._bind_metrics()
        self._prefill_miss_base = self.prefill.misses
        self._stamp_kv_gauges()

    def window_prefill_compiles(self) -> int:
        """Bucketed-jit cache misses since the current metrics window began."""
        return self.prefill.misses - self._prefill_miss_base

    def submit(self, req: Request) -> None:
        plen = len(req.prompt)
        if plen < 1:
            raise ValueError(f"req {req.rid}: empty prompt")
        if req.max_new_tokens < 1:
            raise ValueError(f"req {req.rid}: max_new_tokens must be >= 1")
        if plen >= self.max_len:
            raise ValueError(
                f"req {req.rid}: prompt length {plen} >= max_len {self.max_len} "
                f"leaves no room to generate"
            )
        req.metrics = RequestMetrics(rid=req.rid, prompt_len=plen, t_submit=self.clock())
        if self.tracer.enabled:
            self.tracer.event("submit", track="scheduler", rid=req.rid,
                              prompt_len=plen)
        self.queue.append(req)

    # -- lifecycle ----------------------------------------------------------

    def _finish(self, req: Request, st: _SlotState, slot: int) -> None:
        req.output = np.asarray(st.emitted, np.int32)
        rm = req.metrics
        rm.t_done = self.clock()
        rm.n_tokens = len(st.emitted)
        self.metrics.finish_request(rm)
        self.completed.append(req)
        if self.tracer.enabled:
            # decode span: first token -> done, on the slot's track. Its
            # duration / (n_tokens - 1) IS this request's TPOT (same stamps).
            self.tracer.add_span(
                "decode", f"slot{slot}", rm.t_first_token, rm.t_done,
                rid=req.rid, n_tokens=rm.n_tokens, tpot_s=rm.tpot)
            # whole-lifecycle async span (requests overlap freely)
            self.tracer.add_span(
                "request", "requests", rm.t_submit, rm.t_done,
                async_id=req.rid, rid=req.rid, prompt_len=rm.prompt_len,
                n_tokens=rm.n_tokens, ttft_s=rm.ttft)

    def _emit(self, st: _SlotState, token: int) -> bool:
        """Record one generated token; returns True when the request is done."""
        st.emitted.append(token)
        st.remaining -= 1
        req = st.req
        if req.metrics.t_first_token is None:
            req.metrics.t_first_token = self.clock()
        if req.on_token is not None:
            req.on_token(token)
        return st.remaining <= 0 or (req.eos_id is not None and token == req.eos_id)

    def _admit_one(self, req: Request) -> bool:
        """Admit one request into a free slot. Returns False when admission
        must defer (paged block backpressure); the dense pool always admits.
        ``t_admit`` is stamped when the slot is claimed — BEFORE the prefill
        — so queue_wait is pure scheduling delay and prefill_s is the
        admission prefill (metrics.py)."""
        slot = self.kv.alloc()
        assert slot is not None
        rm = req.metrics
        rm.t_admit = self.clock()
        logits, pcache = self.prefill(self.params, req.prompt)
        self.metrics.prefills += 1
        with self.tracer.span("serve.first_token", rid=req.rid):
            # repro: noqa-RPA001 -- admission emits the prefill token to the host
            t0 = int(np.argmax(np.asarray(logits)[0, -1]))
        plen = rm.prompt_len
        # decode writes go to plen .. plen+n-2; keep them inside the cache
        budget = min(req.max_new_tokens, self.max_len - plen + 1)
        st = _SlotState(req=req, remaining=budget, emitted=[])
        done = self._emit(st, t0)
        self._trace_admission(req, slot, bucket=self.prefill.bucket_for(plen))
        if done:
            self._finish(req, st, slot)
            self.kv.free(slot)
            return True
        self.kv.write_prefill(slot, pcache)
        self._slots[slot] = st
        self._tok[slot] = t0
        self._pos[slot] = plen
        self._spec_admit(slot, req)
        return True

    def _trace_admission(self, req: Request, slot: int, **extra) -> None:
        """Queued + prefill spans from the request's own clock stamps:
        queued.dur + prefill.dur == TTFT exactly (same floats)."""
        if not self.tracer.enabled:
            return
        rm = req.metrics
        self.tracer.add_span("queued", "requests", rm.t_submit, rm.t_admit,
                             async_id=req.rid, rid=req.rid)
        self.tracer.add_span("prefill", f"slot{slot}", rm.t_admit,
                             rm.t_first_token, rid=req.rid,
                             prompt_len=rm.prompt_len, **extra)

    def _admit(self) -> None:
        """FIFO admission: the queue head either admits or (paged) defers —
        a deferral blocks everything behind it, which is what makes block
        backpressure deadlock-free (completions always free blocks)."""
        with self.tracer.span("serve.admit"):
            while self.queue and self._slots_available:
                if not self._admit_one(self.queue[0]):
                    break
                self.queue.pop(0)

    def tick(self) -> bool:
        """Admit waiting requests, then run one decode step over the slot
        batch. Returns False when there was nothing to do. Its host work
        runs in ``serve.*`` spans (obs/trace.py): ``serve.tick`` around it
        all, ``serve.admit``, the step's ``serve.upload`` /
        ``serve.dispatch`` / ``serve.token_pull``, and ``serve.emit``."""
        if self._spec_api is not None:
            return self._spec_tick()
        tracer = self.tracer
        with tracer.span("serve.tick"):
            self._admit()
            active = [i for i, s in enumerate(self._slots) if s is not None]
            if not active:
                return False
            if not self._tick_compiled and tracer.enabled:
                with tracer.span("compile", "scheduler", kind="decode_tick",
                                 n_slots=self.n_slots):
                    nxt = self._run_tick()
            else:
                nxt = self._run_tick()
            self._tick_compiled = True
            with tracer.span("serve.emit", rows=len(active)):
                self.metrics.record_step(
                    len(active), kv_bytes_read=self._decode_kv_bytes(active))
                for i in active:
                    st = self._slots[i]
                    self._tok[i] = nxt[i]
                    self._pos[i] += 1
                    if self._emit(st, int(nxt[i])):
                        self._finish(st.req, st, i)
                        self._slots[i] = None
                        self._release_slot(i)
                        # park the freed row at a safe in-bounds position; its
                        # junk writes are overwritten by the next admission's
                        # prefill (or land in the paged pool's parking block)
                        self._tok[i] = 0
                        self._pos[i] = 0
        return True

    def run(self) -> List[Request]:
        """Drain queue + slots to completion; returns finished requests in
        completion order."""
        if self.metrics.t_start is None:
            self.metrics.t_start = self.clock()
        while self.has_work:
            self.tick()
        self.metrics.t_end = self.clock()
        self.metrics.prefill_compiles = self.window_prefill_compiles()
        self.metrics.publish()
        done, self.completed = self.completed, []
        return done


class PagedSlotScheduler(SlotScheduler):
    """Slot scheduler over a paged KV block pool (serve/paged_kv.py).

    Three behavioral deltas from the dense scheduler, all bit-neutral:

    - **Chunked prefill**: prompts append block-by-block through ONE
      compiled ``(1, chunk)`` program (compile_cache.ChunkedPrefill) instead
      of ``O(log2 max_len)`` bucket shapes — long prompts stop paying a
      whole-prompt prefill's worth of TTFT tail for a fresh bucket compile.
    - **Shared-prefix reuse**: matching prompt prefixes attach cached blocks
      (refcount++) and skip their chunks entirely; finished requests'
      prompt blocks stay evictable-LRU in the prefix map.
    - **Block backpressure**: admission reserves the request's whole span of
      blocks up front; when blocks run short the queue head *defers* (FIFO,
      deadlock-free — completions free blocks) instead of overcommitting.

    The jitted tick gains one operand — the (S, T) block tables — and keeps
    the single-signature guarantee: tables are data, not shape.
    """

    engine_name = "paged"

    def __init__(
        self,
        api: ModelAPI,
        params,
        arch: ArchConfig,
        *,
        block_size: int = 16,
        n_blocks: Optional[int] = None,
        prefix_cache: bool = True,
        chunk: int = 32,
        **kw,
    ):
        if api.decode_paged is None or api.prefill_chunk is None:
            raise ValueError(
                "paged serving needs a model family with decode_paged/"
                "prefill_chunk (attention-KV 'lm'); use engine='continuous'"
            )
        self.block_size = block_size
        self._n_blocks_arg = n_blocks
        self.prefix_enabled = prefix_cache
        self.chunk = chunk
        super().__init__(api, params, arch, **kw)
        self._evict_base = 0

    # -- hook overrides -----------------------------------------------------

    def _init_kv_prefill(self, api, quantized_kv: bool, min_bucket: int) -> None:
        self.kv = PagedKVManager(
            api, n_slots=self.n_slots, max_len=self.max_len,
            block_size=self.block_size, n_blocks=self._n_blocks_arg,
            prefix_cache=self.prefix_enabled, quantized=quantized_kv,
            mesh=self.mesh, rules=self.rules, tracer=self.tracer,
        )
        self.prefill = ChunkedPrefill(
            api, chunk=self.chunk, max_len=self.max_len, mesh=self.mesh,
            rules=self.rules, param_sh=self._param_sh, cache_sh=self.kv._cache_sh,
            tracer=self.tracer,
        )
        # f32 bytes of one row's dequantized k+v window — what the gather
        # route materializes per row when the pool is int8
        lay = self.kv.layout
        self._fp_window_bytes = (
            2 * lay.n_layers * self.max_len * lay.n_kv_heads * lay.head_dim * 4
        )

    @property
    def _slots_available(self) -> int:
        return self.kv.n_free_slots

    def _release_slot(self, slot: int) -> None:
        self.kv.free_slot(slot)

    def _kv_pool_bytes(self) -> int:
        return self.kv.pool_bytes

    def _kv_bytes_per_token(self) -> float:
        return self.kv.bytes_per_token

    def _decode_kv_bytes(self, active: List[int]) -> int:
        """Modeled per-tick KV HBM traffic of the paged decode routes
        (DESIGN.md §7). The fused kernel streams each row's *live* blocks
        once (whole-block skip ends the walk at the row's position); the
        gather route reads the row's FULL table window from the pool, writes
        the gathered dense copy, and reads it back for attention (3x), and
        with an int8 pool additionally materializes the window as f32
        (dequant write + attention read)."""
        bpb = self.kv.bytes_per_block
        bs = self.block_size
        if self.arch.paged_attn_route == "fused":
            return int(bpb * sum(-(-(int(self._pos[i]) + 1) // bs) for i in active))
        window = bpb * self.kv.blocks_per_slot
        per_row = 3 * window
        if self.kv.quantized:
            per_row += 2 * self._fp_window_bytes
        return int(per_row * len(active))

    def _run_tick(self) -> np.ndarray:
        tracer = self.tracer
        with tracer.span("serve.upload"):
            tok, pos = jnp.asarray(self._tok), jnp.asarray(self._pos)
            tables = jnp.asarray(self.kv.tables)
        with self._mesh_ctx(), tracer.span("serve.dispatch"):
            nxt, self.kv.cache = self._tick_fn(self.params, self.kv.cache, tok, pos, tables)
        with tracer.span("serve.token_pull"):
            # repro: noqa-RPA001 -- tick barrier (see SlotScheduler._run_tick)
            return np.asarray(nxt)

    def _build_tick(self):
        decode = self.api.decode_paged

        def tick(params, cache, tok, pos, tables):
            logits, cache = decode(params, tok[:, None], cache, pos, tables)
            return jnp.argmax(logits[:, -1], -1).astype(jnp.int32), cache

        if self.mesh is None:
            return jax.jit(tick, donate_argnums=(1,))
        return jax.jit(
            tick,
            donate_argnums=(1,),
            in_shardings=(self._param_sh, self.kv._cache_sh, self._rep, self._rep,
                          self._rep),
            out_shardings=(self._rep, self.kv._cache_sh),
        )

    def reset_metrics(self) -> None:
        super().reset_metrics()
        self._evict_base = self.kv.evictions

    def _admit_one(self, req: Request) -> bool:
        slot = self.kv.alloc_slot()
        assert slot is not None
        plen = req.metrics.prompt_len
        # decode writes go to plen .. plen+n-2; keep them inside the cache
        budget = min(req.max_new_tokens, self.max_len - plen + 1)
        cached = self.kv.try_admit(slot, req.prompt, budget=budget, chunk=self.chunk)
        if cached is None:
            self.kv.free_slot(slot)  # owns no blocks yet; just re-parks
            self.metrics.admission_deferrals += 1
            if self.tracer.enabled:
                self.tracer.event("admission_deferral", track="scheduler",
                                  rid=req.rid, prompt_len=plen,
                                  blocks_free=self.kv.blocks_free)
            return False
        req.metrics.t_admit = self.clock()
        if self.tracer.enabled:
            self.tracer.event("prefix_hit" if cached else "prefix_miss",
                              track="scheduler", rid=req.rid, prompt_len=plen,
                              cached_tokens=cached)
        logits, self.kv.cache, n_chunks = self.prefill(
            self.params, self.kv.cache, self.kv.tables[slot], req.prompt, cached,
            rid=req.rid,
        )
        self.metrics.prefills += 1
        self.metrics.prefill_chunks += n_chunks
        self.metrics.prefix_prompt_tokens += plen
        self.metrics.prefix_hit_tokens += cached
        self.metrics.prefix_evictions = self.kv.evictions - self._evict_base
        self.metrics.record_blocks(self.kv.blocks_in_use,
                                   bytes_in_use=self.kv.kv_bytes_in_use)
        # publish this prompt's full blocks before any chance of freeing, so
        # even an instant-EOS request seeds the prefix cache
        self.kv.register_prompt(slot, req.prompt)
        with self.tracer.span("serve.first_token", rid=req.rid):
            # repro: noqa-RPA001 -- admission emits the prefill token to the host
            t0 = int(np.argmax(np.asarray(logits)[0, -1]))
        st = _SlotState(req=req, remaining=budget, emitted=[])
        done = self._emit(st, t0)
        self._trace_admission(req, slot, cached_tokens=cached, n_chunks=n_chunks)
        if done:
            self._finish(req, st, slot)
            self.kv.free_slot(slot)
            return True
        self._slots[slot] = st
        self._tok[slot] = t0
        self._pos[slot] = plen
        self._spec_admit(slot, req)
        return True

    # -- speculative hooks --------------------------------------------------

    def _spec_verify_api(self):
        return self.api.decode_verify_paged

    def _spec_operands(self):
        # the LIVE tables at round time — admissions/releases between rounds
        # repoint rows, and a verify window overhanging a row's reserved
        # span lands in the parking block (tables default to it), exactly
        # like an inactive row's junk decode writes
        return (jnp.asarray(self.kv.tables),)


def replay_arrivals(
    sched: SlotScheduler,
    timed_requests,
    *,
    submit: Optional[Callable[[Request, float], None]] = None,
    sleep: Callable[[float], None] = time.sleep,
) -> Tuple[List[Request], float]:
    """Open-loop arrival replay: tick the scheduler, admitting each request
    the moment its arrival offset elapses (used by launch/serve.py
    --arrival-rate and benchmarks/serving_bench.py).

    ``timed_requests`` is ``[(arrival_offset_s, Request), ...]`` sorted by
    offset. ``submit(req, t_abs)`` (default ``sched.submit``) lets callers
    stamp measurement taps with the absolute arrival time before submission.
    Returns ``(completed_requests, makespan_s)`` and stamps the scheduler's
    run metrics (t_start/t_end/prefill_compiles).
    """
    clock = sched.clock
    pending = list(timed_requests)
    t0 = clock()
    if sched.metrics.t_start is None:
        sched.metrics.t_start = t0
    while pending or sched.has_work:
        now = clock() - t0
        while pending and pending[0][0] <= now:
            t_arr, req = pending.pop(0)
            if submit is not None:
                submit(req, t0 + t_arr)
            else:
                sched.submit(req)
        if not sched.tick() and pending:
            sleep(max(0.0, pending[0][0] - (clock() - t0)))
    t_end = clock()
    sched.metrics.t_end = t_end
    sched.metrics.prefill_compiles = sched.window_prefill_compiles()
    sched.metrics.publish()
    done, sched.completed = sched.completed, []
    return done, t_end - t0
