"""Serving engines: continuous-batching slot scheduler + static packed batches.

``ServeEngine`` fronts two scheduling policies behind one queue/submit/run
API:

- ``engine="continuous"`` — the slot scheduler (serve/scheduler.py): fixed
  decode batch of ``n_slots`` rows, bucketed per-request prefill-into-slot,
  slots freed the moment a request finishes, queued requests admitted
  mid-flight. No head-of-line blocking; the jitted decode step never
  recompiles.
- ``engine="paged"`` — the continuous scheduler over a paged KV block pool
  (serve/paged_kv.py): block-granular allocation, shared-prefix block reuse
  with refcount/copy-on-write/LRU eviction, and chunked prefill through one
  compiled chunk program (``kv_block_size`` / ``kv_n_blocks`` /
  ``prefix_cache`` / ``prefill_chunk`` kwargs). Token-for-token identical
  to ``continuous`` with fp KV caches (the dense pool remains the parity
  oracle in tests); with ``quantized_kv`` it is deterministic but NOT
  bit-equal to dense — chunked prefill attends earlier chunks through the
  int8+scale round-trip, where the dense whole-prompt prefill attends raw
  fp keys (serve/paged_kv.py).
- ``engine="static"`` — the original drainer (kept for A/B benchmarking and
  for model families the scheduler does not cover): pack up to
  ``batch_size`` requests, left-pad to a shared length, run the whole group
  to completion before admitting anything else.
- ``engine="auto"`` (default) — continuous when the architecture supports it
  (non-MoE ``lm``), static otherwise. ``run(extra_batch=...)`` (encdec
  frames etc.) always routes through the static path: extra inputs are
  packed-batch-shaped by construction.

With ``phase='serve'`` the engine runs hardware-form parameters — int8
thresholds + packed signs for BiKA, packed sign bits for BNN, int8 weights +
requant scales for QNN — the TPU rendition of the paper's deployment story:
serving weight traffic drops to ~9 bits/edge (bika) or ~1 bit/edge (bnn).

``ServeEngine.from_trained`` is the train->deploy step: it converts a trained
float checkpoint through the QuantBackend registry (``core.convert.
tree_to_serve``) and builds the serve-phase model around it, so ANY
registered quantized mode (including future ones) deploys through the same
two lines.

``spec_draft=`` + ``spec_k=`` turn on speculative decoding (DESIGN.md §10)
on the continuous/paged engines: a cheaper registry form of the SAME
trained weights drafts ``spec_k - 1`` tokens per slot and the target
verifies the window in one batched step — greedy outputs stay
token-for-token identical to target-only decode. ``from_trained`` accepts
the draft as a preset string ("dense"/"bika"/"bnn"/"qnn8"/"small",
resolved via serve/spec.py); the raw constructor wants the prebuilt
``(draft_api, draft_params, draft_arch)`` triple. ``spec_k=1`` degenerates
to plain decode; the static engine and mesh serving reject speculation.

``mesh=`` (+ optional ``rules=``) tensor-parallelizes either engine across a
device mesh: params are placed with ``param_shardings``, KV caches shard
``kv_heads`` over the ``model`` axis per the layout contract, the jitted
programs pin explicit in/out NamedShardings, and the Pallas kernel routes
run column-parallel under shard_map (kernels/ops.py) — outputs stay
token-for-token identical to the single-device engine (DESIGN.md §5).
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.convert import tree_to_serve
from repro.distributed.constraints import mesh_context
from repro.models.base import ArchConfig, ModelAPI
from repro.serve.scheduler import (
    PagedSlotScheduler,
    Request,
    SlotScheduler,
    scheduler_supports,
)

__all__ = ["Request", "ServeEngine", "serve_batch", "serve_params_from_train"]


def serve_params_from_train(train_params, spec):
    """Trained float params (any model tree) -> hardware serve form via the
    backend registry. Thin serving-layer alias of ``convert.tree_to_serve``."""
    return tree_to_serve(train_params, spec)


class ServeEngine:
    def __init__(
        self,
        api: ModelAPI,
        params,
        arch: ArchConfig,
        *,
        batch_size: int = 4,
        max_len: int = 256,
        quantized_kv: bool = False,
        engine: str = "auto",
        n_slots: Optional[int] = None,
        min_bucket: int = 16,
        kv_block_size: int = 16,
        kv_n_blocks: Optional[int] = None,
        prefix_cache: bool = True,
        prefill_chunk: int = 32,
        mesh=None,
        rules=None,
        tracer=None,
        registry=None,
        spec_draft=None,
        spec_k: int = 1,
    ):
        self.api = api
        self.arch = arch
        self.batch_size = batch_size
        self.max_len = max_len
        self.quantized_kv = quantized_kv
        self.mesh = mesh
        self.rules = rules
        if engine not in ("auto", "static", "continuous", "paged"):
            raise ValueError(f"unknown engine {engine!r}")
        if engine == "auto":
            engine = "continuous" if scheduler_supports(arch) else "static"
        self.engine = engine
        self.tracer = tracer
        self.registry = registry
        # speculative decoding (DESIGN.md §10): spec_draft is a prebuilt
        # (draft_api, draft_params, draft_arch) triple — ``from_trained``
        # resolves string presets ("bnn"/"qnn8"/"bika"/"dense"/"small")
        # because only the train checkpoint can derive a weight-tied draft
        if spec_draft is not None and engine not in ("continuous", "paged"):
            raise ValueError(
                f"spec_draft needs a slot-scheduler engine (continuous/paged); "
                f"got engine={engine!r}"
            )
        obs_kw = dict(tracer=tracer, registry=registry,
                      spec_draft=spec_draft, spec_k=spec_k)
        self.scheduler: Optional[SlotScheduler] = None
        if engine == "paged":
            self.scheduler = PagedSlotScheduler(
                api, params, arch,
                n_slots=n_slots or batch_size,
                max_len=max_len,
                quantized_kv=quantized_kv,
                block_size=kv_block_size,
                n_blocks=kv_n_blocks,
                prefix_cache=prefix_cache,
                chunk=prefill_chunk,
                mesh=mesh,
                rules=rules,
                **obs_kw,
            )
            params = self.scheduler.params  # already mesh-placed
        elif engine == "continuous":
            self.scheduler = SlotScheduler(
                api, params, arch,
                n_slots=n_slots or batch_size,
                max_len=max_len,
                quantized_kv=quantized_kv,
                min_bucket=min_bucket,
                mesh=mesh,
                rules=rules,
                **obs_kw,
            )
            params = self.scheduler.params  # already mesh-placed
        prefill = lambda p, batch: api.prefill(p, batch, max_len=max_len,
                                               quantized=quantized_kv)
        if mesh is None:
            self._prefill = jax.jit(prefill)
            self._decode = jax.jit(api.decode_step, donate_argnums=(2,))
        else:
            from repro.distributed.sharding import (
                ShardingRules, api_param_shardings, named_sharding,
                replicated_sharding,
            )
            from repro.models.base import KV_CACHE_LOGICAL_AXES

            self.rules = rules = rules if rules is not None else ShardingRules()
            param_sh = (self.scheduler._param_sh if self.scheduler is not None
                        else api_param_shardings(mesh, api, rules))
            rep = replicated_sharding(mesh)
            if arch.family == "lm" and arch.window is None:
                # static packed cache follows the KV layout contract: one
                # spec prefix covers every leaf (kv_heads dim is shared)
                cache_sh = named_sharding(
                    mesh, KV_CACHE_LOGICAL_AXES, rules,
                    (arch.n_layers, batch_size, max_len, arch.n_kv_heads, arch.hd),
                )
            else:
                cache_sh = rep  # recurrent/ring caches: replicate
            if self.scheduler is None:
                params = jax.device_put(params, param_sh)
            self._prefill = jax.jit(prefill, in_shardings=(param_sh, rep),
                                    out_shardings=(rep, cache_sh))
            self._decode = jax.jit(api.decode_step, donate_argnums=(2,),
                                   in_shardings=(param_sh, rep, cache_sh, rep),
                                   out_shardings=(rep, cache_sh))
        self.params = params
        self.queue: List[Request] = []

    def _mesh_ctx(self):
        return mesh_context(self.mesh)

    @classmethod
    def from_trained(
        cls,
        train_params,
        arch: ArchConfig,
        *,
        batch_size: int = 4,
        max_len: int = 256,
        quantized_kv: bool = False,
        **kw,
    ) -> "ServeEngine":
        """Build a serve-phase engine directly from a trained checkpoint:
        converts every linear leaf through its registered backend's
        ``to_serve`` and instantiates the ``phase='serve'`` model around the
        result.

        ``spec_draft`` may be a string preset here ("bnn", "qnn8", "bika",
        "dense", "small"): the SAME trained weights are converted through
        the cheaper backend (or depth-sliced) into the speculative draft —
        the registry-native draft/target pair (serve/spec.py)."""
        from repro.models import build_model

        spec_draft = kw.get("spec_draft")
        if isinstance(spec_draft, str):
            from repro.serve.spec import build_draft_from_train

            kw["spec_draft"] = build_draft_from_train(train_params, arch, spec_draft)
        api = build_model(arch, phase="serve")
        params = serve_params_from_train(train_params, arch.linear_spec())
        return cls(api, params, arch, batch_size=batch_size, max_len=max_len,
                   quantized_kv=quantized_kv, **kw)

    @property
    def metrics(self):
        """RunMetrics of the continuous scheduler (None for static)."""
        return self.scheduler.metrics if self.scheduler is not None else None

    def submit(self, req: Request):
        if req.max_new_tokens < 1:
            raise ValueError(f"req {req.rid}: max_new_tokens must be >= 1")
        if len(req.prompt) >= self.max_len:
            raise ValueError(
                f"req {req.rid}: prompt length {len(req.prompt)} >= max_len "
                f"{self.max_len} leaves no room to generate"
            )
        if self.scheduler is not None:
            self.scheduler.submit(req)
        else:
            self.queue.append(req)

    # -- static path --------------------------------------------------------

    def _pack(self, reqs: Sequence[Request]):
        s = max(len(r.prompt) for r in reqs)
        s = max(s, 1)
        toks = np.zeros((len(reqs), s), np.int32)
        for i, r in enumerate(reqs):
            toks[i, s - len(r.prompt):] = r.prompt  # left-pad (causal-safe)
        return jnp.asarray(toks), s

    @staticmethod
    def _slice_extra(extra_batch: Dict, n: int) -> Dict:
        """Trim batched extra inputs (encdec frames, ...) to the packed batch
        size — the final partial group of a drain is smaller than
        batch_size."""
        out = {}
        for k, v in extra_batch.items():
            if hasattr(v, "ndim") and v.ndim >= 1 and v.shape[0] > n:
                v = v[:n]
            out[k] = v
        return out

    def step_batch(self, reqs: Sequence[Request], extra_batch: Optional[Dict] = None):
        """Static path: prefill + greedy-decode one packed batch to
        completion; fills req.output. The host loop breaks as soon as every
        row is finished (EOS or its token budget) instead of always running
        to max(max_new_tokens)."""
        tokens, s = self._pack(reqs)
        batch = {"tokens": tokens}
        if extra_batch:
            batch.update(self._slice_extra(extra_batch, len(reqs)))
        with self._mesh_ctx():
            logits, cache = self._prefill(self.params, batch)
        tok = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)[:, None]
        # decode writes go to positions s .. s+n_steps-2; cap the loop at the
        # KV cache end instead of silently wrapping/corrupting row max_len-1
        n_steps = max(r.max_new_tokens for r in reqs)
        n_steps = max(1, min(n_steps, self.max_len - s + 1))
        need = np.array([r.max_new_tokens for r in reqs])
        eos = np.array([-1 if r.eos_id is None else r.eos_id for r in reqs])
        # repro: noqa-RPA001 -- streaming emits host token ids; one sync
        # per step is the engine's contract (tests assert callback order)
        cur = np.asarray(tok)[:, 0]
        outs = [cur]
        finished = (cur == eos) | (need <= 1)
        self._stream(reqs, cur, np.zeros(len(reqs), bool), 0, need)
        for t in range(1, n_steps):
            if finished.all():
                break
            pos = jnp.asarray(s + t - 1, jnp.int32)
            with self._mesh_ctx():
                logits, cache = self._decode(self.params, tok, cache, pos)
            tok = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)[:, None]
            cur = np.asarray(tok)[:, 0]  # repro: noqa-RPA001 -- see above
            self._stream(reqs, cur, finished, t, need)
            outs.append(cur)
            finished = finished | (cur == eos) | (t + 1 >= need)
        gen = np.stack(outs, axis=1)  # (B, <= n_steps)
        for i, r in enumerate(reqs):
            g = gen[i, : r.max_new_tokens]
            if r.eos_id is not None:
                hits = np.where(g == r.eos_id)[0]
                if hits.size:
                    g = g[: hits[0] + 1]
            r.output = g
        return reqs

    @staticmethod
    def _stream(reqs, cur, already_finished, t, need):
        for i, r in enumerate(reqs):
            if r.on_token is not None and not already_finished[i] and t < need[i]:
                r.on_token(int(cur[i]))

    def run(self, extra_batch: Optional[Dict] = None) -> List[Request]:
        """Drain all submitted requests. Continuous: slot scheduler; static:
        batch_size groups run to completion."""
        if self.scheduler is not None:
            if extra_batch is not None:
                raise ValueError(
                    "extra_batch is packed-batch-shaped and only supported by "
                    "the static engine (pass engine='static')"
                )
            return self.scheduler.run()
        done: List[Request] = []
        while self.queue:
            batch, self.queue = self.queue[: self.batch_size], self.queue[self.batch_size:]
            done.extend(self.step_batch(batch, extra_batch))
        return done


def serve_batch(api: ModelAPI, params, prompts: jax.Array, *, max_new_tokens: int = 8,
                max_len: Optional[int] = None):
    """One-shot functional helper used by tests/benchmarks."""
    b, s = prompts.shape
    ml = max_len or (s + max_new_tokens)
    logits, cache = api.prefill(params, {"tokens": prompts}, max_len=ml)
    tok = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)[:, None]
    toks = [tok]
    for t in range(1, max_new_tokens):
        logits, cache = api.decode_step(params, tok, cache, jnp.asarray(s + t - 1, jnp.int32))
        tok = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)[:, None]
        toks.append(tok)
    return jnp.concatenate(toks, axis=1)
