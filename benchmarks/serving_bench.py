"""Serving load test: Poisson-arrival mixed-length traffic, static vs
continuous engines, across the quantized backends.

    PYTHONPATH=src:. python benchmarks/serving_bench.py --smoke
    PYTHONPATH=src:. python benchmarks/serving_bench.py \
        --modes dense,bika,bnn,qnn8 --requests 32 --out BENCH_serving.json

Both engines replay the SAME open-loop arrival trace (exponential
inter-arrival gaps, mixed prompt lengths, mixed token budgets) and are
measured through their streaming ``on_token`` callbacks, so TTFT/TPOT mean
the same thing for both. Goodput = completed output tokens / makespan.

The static engine loses on exactly the two axes this subsystem attacks:
head-of-line blocking (every packed group decodes until its LAST request
finishes, and nothing new is admitted meanwhile) and per-shape prefill
recompiles (one program per distinct packed prompt width vs. the continuous
engine's power-of-two bucket cache).

Paged rows: every mode also runs the paged-KV engine on the same mixed
trace (``results[mode]["continuous_paged"]`` — the CI gate bounds its
goodput at >= 90% of dense continuous) and a shared-system-prompt workload
through both continuous engines (``results[mode]["shared_prefix"]``), where
the paged engine's prefix cache serves the system prompt from cached blocks
after the first admission and the reported ``ttft_improvement`` isolates
that win.

Long-decode rows (``results[mode]["long_decode"]``): decode-heavy traffic at
``--long-max-len`` through BOTH paged-attention routes — the fused
block-walk kernel vs the XLA gather oracle — on the identical trace, plus
the modeled decode HBM-bytes-per-token of each route and the int8 pool's
context-per-byte ratio. check_serving_gate.py bounds the fused route's TPOT
and the int8 capacity from these rows.

Multi-device row: unless ``--no-multi-device``, the bench serves every mode
again in the same process, tensor-parallel on a (n/2, 2) data x model mesh
over ``jax.devices()``, and merges the result in as
``results[mode]["continuous_tp2"]`` — same workload trace, token-for-token
the same outputs, so the row isolates the sharding overhead/benefit. With
one device the row is skipped and the bench says so (on CPU, set
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` to get eight). No
child process is started: a chip belongs to the process that imported JAX.
"""
from __future__ import annotations

import argparse
import json
import time
from typing import Dict, List, Optional, Tuple

import jax
import numpy as np

from repro.configs import get_smoke
from repro.models import build_model
from repro.nn.module import unbox
from repro.obs import MetricsRegistry
from repro.serve.engine import Request, ServeEngine
from repro.serve.metrics import _percentile
from repro.serve.scheduler import replay_arrivals

MODES = ("dense", "bika", "bnn", "qnn8")

# bump when row keys / semantics change (v2: tap tpot percentiles, per-row
# metrics-registry snapshots, top-level schema_version stamp)
SCHEMA_VERSION = 2


def make_workload(rng: np.random.RandomState, n: int, vocab: int, *,
                  arrival_rate: float, plen_range: Tuple[int, int],
                  ntok_range: Tuple[int, int]) -> List[Tuple[float, Request]]:
    arrivals = np.cumsum(rng.exponential(1.0 / arrival_rate, n))
    out = []
    for i in range(n):
        plen = int(rng.randint(plen_range[0], plen_range[1] + 1))
        ntok = int(rng.randint(ntok_range[0], ntok_range[1] + 1))
        prompt = rng.randint(0, vocab, plen).astype(np.int32)
        out.append((float(arrivals[i]), Request(rid=i, prompt=prompt, max_new_tokens=ntok)))
    return out


def make_shared_prefix_workload(
    rng: np.random.RandomState, n: int, vocab: int, *, arrival_rate: float,
    sys_len: int, suffix_range: Tuple[int, int], ntok_range: Tuple[int, int],
) -> List[Tuple[float, Request]]:
    """Chat-style traffic: every request = one shared system prompt + a
    short unique suffix. The paged engine's prefix cache serves ``sys_len``
    tokens of every admission after the first from cached blocks; the dense
    engines recompute them per request."""
    arrivals = np.cumsum(rng.exponential(1.0 / arrival_rate, n))
    sys_prompt = rng.randint(0, vocab, sys_len).astype(np.int32)
    out = []
    for i in range(n):
        slen = int(rng.randint(suffix_range[0], suffix_range[1] + 1))
        ntok = int(rng.randint(ntok_range[0], ntok_range[1] + 1))
        prompt = np.concatenate([sys_prompt, rng.randint(0, vocab, slen).astype(np.int32)])
        out.append((float(arrivals[i]), Request(rid=i, prompt=prompt, max_new_tokens=ntok)))
    return out


class _Tap:
    """Per-request streaming tap: stamps first/last token wall times."""

    def __init__(self):
        self.t_submit: Dict[int, float] = {}
        self.t_first: Dict[int, float] = {}
        self.t_last: Dict[int, float] = {}
        self.n_tok: Dict[int, int] = {}

    def attach(self, req: Request) -> None:
        rid = req.rid

        def on_token(tok: int, _rid=rid) -> None:
            now = time.monotonic()
            self.t_first.setdefault(_rid, now)
            self.t_last[_rid] = now
            self.n_tok[_rid] = self.n_tok.get(_rid, 0) + 1

        req.on_token = on_token

    def summary(self, makespan: float) -> Dict:
        ttfts = sorted(self.t_first[r] - self.t_submit[r] for r in self.t_first)
        tpots = sorted(
            (self.t_last[r] - self.t_first[r]) / (self.n_tok[r] - 1)
            for r in self.t_first if self.n_tok.get(r, 0) > 1
        )
        total = sum(self.n_tok.values())
        return {
            "completed_requests": len(self.t_last),
            "completed_tokens": total,
            "makespan_s": makespan,
            "goodput_tok_s": total / makespan if makespan > 0 else 0.0,
            "ttft_mean_s": float(np.mean(ttfts)) if ttfts else None,
            "ttft_p50_s": _percentile(ttfts, 0.50) if ttfts else None,
            "ttft_p95_s": _percentile(ttfts, 0.95) if ttfts else None,
            "tpot_mean_s": float(np.mean(tpots)) if tpots else None,
            "tpot_p50_s": _percentile(tpots, 0.50) if tpots else None,
            "tpot_p95_s": _percentile(tpots, 0.95) if tpots else None,
        }


def _warmup(eng: ServeEngine, vocab: int) -> None:
    """One throwaway request to pre-compile prefill+decode, so the timed run
    compares scheduling, not cold-start XLA compiles."""
    eng.submit(Request(rid=-1, prompt=np.arange(1, 4, dtype=np.int32) % vocab,
                       max_new_tokens=2))
    eng.run()


def run_static(api, params, arch, workload, *, batch_size: int, max_len: int,
               warmup: bool) -> Dict:
    eng = ServeEngine(api, params, arch, batch_size=batch_size, max_len=max_len,
                      engine="static")
    if warmup:
        _warmup(eng, arch.vocab)
    tap = _Tap()
    pending = [(t, r) for t, r in workload]
    shapes = set()
    t0 = time.monotonic()
    while pending or eng.queue:
        now = time.monotonic() - t0
        while pending and pending[0][0] <= now:
            t_arr, req = pending.pop(0)
            tap.t_submit[req.rid] = t0 + t_arr
            tap.attach(req)
            eng.submit(req)
        if eng.queue:
            group, eng.queue = eng.queue[:batch_size], eng.queue[batch_size:]
            shapes.add((len(group), max(len(r.prompt) for r in group)))
            eng.step_batch(group)
        elif pending:
            time.sleep(max(0.0, pending[0][0] - now))
    makespan = time.monotonic() - t0
    out = tap.summary(makespan)
    out["distinct_prefill_shapes"] = len(shapes)
    return out


def run_continuous(api, params, arch, workload, *, n_slots: int, max_len: int,
                   warmup: bool, mesh=None, engine: str = "continuous",
                   block_size: int = 8, chunk: int = 16, from_train=None,
                   spec_draft=None, spec_k: int = 1) -> Dict:
    # per-row registry: the run's labelled histograms/counters + serve_run_*
    # gauges ride along in the row as a JSON snapshot (schema_version 2)
    registry = MetricsRegistry()
    kw = dict(max_len=max_len, engine=engine, n_slots=n_slots,
              kv_block_size=block_size, prefill_chunk=chunk, mesh=mesh,
              registry=registry)
    if from_train is not None:
        # speculative rows convert the SAME trained tree into target and
        # draft serve forms (serve/spec.py) — so both engines of an A/B see
        # identical target weights
        eng = ServeEngine.from_trained(from_train, arch, spec_draft=spec_draft,
                                       spec_k=spec_k, **kw)
    else:
        eng = ServeEngine(api, params, arch, **kw)
    sched = eng.scheduler
    if warmup:
        _warmup(eng, arch.vocab)
        # fresh metrics window: reset_metrics snapshots the prefill-compile
        # counter, so the timed report below counts only its own misses
        sched.reset_metrics()
    tap = _Tap()

    def submit(req, t_abs):
        tap.t_submit[req.rid] = t_abs
        tap.attach(req)
        eng.submit(req)

    _, makespan = replay_arrivals(sched, workload, submit=submit)
    out = tap.summary(makespan)
    out["slot_occupancy"] = sched.metrics.slot_occupancy
    out["prefill_compiles"] = sched.metrics.prefill_compiles
    out["decode_steps"] = sched.metrics.decode_steps
    if engine == "paged":
        out["prefix_hit_rate"] = sched.metrics.prefix_hit_rate
        out["prefix_hit_tokens"] = sched.metrics.prefix_hit_tokens
        out["prefill_chunks"] = sched.metrics.prefill_chunks
        out["blocks_in_use_peak"] = sched.metrics.blocks_in_use_peak
        out["admission_deferrals"] = sched.metrics.admission_deferrals
        out["prefix_evictions"] = sched.metrics.prefix_evictions
        out["kv_pool_bytes"] = sched.metrics.kv_pool_bytes
        out["kv_bytes_per_token"] = sched.metrics.kv_bytes_per_token
        out["kv_bytes_in_use_peak"] = sched.metrics.kv_bytes_in_use_peak
        out["decode_hbm_bytes_per_token"] = sched.metrics.decode_hbm_bytes_per_token
    # scheduler-clock latency aggregates + registry state for this row
    # (tap figures above stay the cross-engine comparison source of truth)
    sm = sched.metrics.summary()
    out["sched_tpot_p50_s"] = sm["tpot_p50_s"]
    out["sched_tpot_p95_s"] = sm["tpot_p95_s"]
    out["sched_queue_wait_mean_s"] = sm["queue_wait_mean_s"]
    out["sched_prefill_mean_s"] = sm["prefill_mean_s"]
    if spec_draft is not None and spec_k > 1:
        out["spec_rounds"] = sm["spec_rounds"]
        out["spec_accept_rate"] = sm["spec_accept_rate"]
        out["spec_tokens_per_round"] = sm["spec_tokens_per_round"]
    out["registry"] = registry.snapshot()
    return out


def run_speculative(args) -> Dict:
    """Speculative decoding A/B (DESIGN.md §10): a dense target served
    target-only vs speculating with a registry-native quantized draft of its
    OWN trained weights, on an identical decode-heavy paged trace. The wall
    clock ratio is emulator-relative on CPU (interpret-mode kernels distort
    absolute time — PR-6 precedent); the accept rate and emitted tokens per
    round are the host-stable mechanism figures the CI gate leans on."""
    arch = get_smoke(args.arch, compute_mode="dense", remat=False)
    tparams = unbox(build_model(arch, phase="train").init(jax.random.PRNGKey(0)))
    mk = lambda: make_workload(
        np.random.RandomState(args.seed + 3), max(8, args.requests // 2),
        arch.vocab, arrival_rate=args.arrival_rate, plen_range=(3, 8),
        ntok_range=(16, 24),
    )
    common = dict(n_slots=args.n_slots, max_len=args.max_len,
                  warmup=not args.no_warmup, engine="paged",
                  block_size=args.kv_block_size, chunk=args.prefill_chunk,
                  from_train=tparams)
    base = run_continuous(None, None, arch, mk(), **common)
    spec = run_continuous(None, None, arch, mk(), spec_draft=args.spec_draft,
                          spec_k=args.spec_k, **common)
    ratio = (base["tpot_mean_s"] / spec["tpot_mean_s"]
             if spec["tpot_mean_s"] else None)
    out = {"target_mode": "dense", "draft": args.spec_draft,
           "spec_k": args.spec_k, "baseline": base, "speculative": spec,
           "tpot_ratio_base_over_spec": ratio,
           "accept_rate": spec["spec_accept_rate"],
           "tokens_per_round": spec["spec_tokens_per_round"]}
    print(f"[speculative] dense <- {args.spec_draft} k={args.spec_k}: tpot "
          f"{base['tpot_mean_s']:.4f}s -> {spec['tpot_mean_s']:.4f}s "
          f"({ratio:.2f}x) | accept {out['accept_rate']:.2f} | "
          f"{out['tokens_per_round']:.2f} tok/round")
    return out


def run_long_decode(mode: str, args) -> Dict:
    """Decode-heavy traffic at large max_len through BOTH paged attention
    routes on the identical trace: short prompts, long token budgets, so
    nearly all time is decode ticks over deep KV windows — the regime the
    fused block-walk kernel targets (the gather route re-materializes each
    row's full window per tick). Also reports the int8 pool's
    context-per-byte win vs the fp32 pool."""
    arch0 = get_smoke(args.arch, compute_mode=mode, remat=False)
    if mode == "bika":
        arch0 = arch0.replace(pack_signs=True)
    ml = args.long_max_len
    n_req = max(4, args.requests // 8)
    mk = lambda: make_workload(
        np.random.RandomState(args.seed + 2), n_req, arch0.vocab,
        arrival_rate=args.arrival_rate, plen_range=(3, 8),
        ntok_range=(ml // 4, ml // 2),
    )
    out: Dict = {"max_len": ml, "n_requests": n_req}
    params = None
    for route in ("fused", "gather"):
        arch = arch0.replace(paged_attn_route=route)
        api = build_model(arch, phase="serve")
        if params is None:
            params = unbox(api.init(jax.random.PRNGKey(0)))
        out[route] = run_continuous(
            api, params, arch, mk(), n_slots=args.n_slots, max_len=ml,
            warmup=not args.no_warmup, engine="paged",
            block_size=args.kv_block_size, chunk=args.prefill_chunk)
    f, g = out["fused"]["tpot_mean_s"], out["gather"]["tpot_mean_s"]
    out["tpot_ratio_gather_over_fused"] = (g / f) if f else None
    f50, g50 = out["fused"]["tpot_p50_s"], out["gather"]["tpot_p50_s"]
    # median-based ratio: one straggler tick can't skew the route A/B
    out["tpot_p50_ratio_gather_over_fused"] = (g50 / f50) if f50 else None
    out["hbm_ratio_gather_over_fused"] = (
        out["gather"]["decode_hbm_bytes_per_token"]
        / out["fused"]["decode_hbm_bytes_per_token"]
        if out["fused"]["decode_hbm_bytes_per_token"] else None)
    # int8 pool capacity: bytes per logical token, fp32 vs int8 pool
    api = build_model(arch0, phase="serve")
    bpt = {}
    for quant in (False, True):
        eng = ServeEngine(api, params, arch0, max_len=ml, engine="paged",
                          n_slots=args.n_slots, kv_block_size=args.kv_block_size,
                          prefill_chunk=args.prefill_chunk, quantized_kv=quant)
        bpt[quant] = eng.scheduler.kv.bytes_per_token
    out["kv_bytes_per_token_fp32"] = bpt[False]
    out["kv_bytes_per_token_int8"] = bpt[True]
    out["int8_context_per_byte_ratio"] = bpt[False] / bpt[True]
    print(f"[{mode}] long-decode max_len={ml}: tpot fused "
          f"{f:.4f}s vs gather {g:.4f}s "
          f"({out['tpot_ratio_gather_over_fused']:.2f}x) | modeled HBM/token "
          f"{out['fused']['decode_hbm_bytes_per_token']:.0f} vs "
          f"{out['gather']['decode_hbm_bytes_per_token']:.0f} B | int8 capacity "
          f"{out['int8_context_per_byte_ratio']:.2f}x")
    return out


def bench_mode(mode: str, args, mesh=None) -> Dict:
    arch = get_smoke(args.arch, compute_mode=mode, remat=False)
    if mode == "bika":
        arch = arch.replace(pack_signs=True)
    api = build_model(arch, phase="serve")
    params = unbox(api.init(jax.random.PRNGKey(0)))
    mk = lambda: make_workload(  # identical trace for both engines
        np.random.RandomState(args.seed), args.requests, arch.vocab,
        arrival_rate=args.arrival_rate,
        plen_range=(args.min_prompt, args.max_prompt),
        ntok_range=(args.min_new, args.max_new),
    )
    paged_kw = dict(block_size=args.kv_block_size, chunk=args.prefill_chunk)
    if mesh is not None:
        # multi-device child run: only the scheduler engines ride the mesh
        cont = run_continuous(api, params, arch, mk(), n_slots=args.n_slots,
                              max_len=args.max_len, warmup=not args.no_warmup,
                              mesh=mesh)
        paged = run_continuous(api, params, arch, mk(), n_slots=args.n_slots,
                               max_len=args.max_len, warmup=not args.no_warmup,
                               mesh=mesh, engine="paged", **paged_kw)
        print(f"[{mode}] continuous tp={mesh.shape['model']}: "
              f"{cont['goodput_tok_s']:.1f} tok/s | paged "
              f"{paged['goodput_tok_s']:.1f} tok/s | occupancy "
              f"{cont['slot_occupancy']:.2f}")
        return {"continuous": cont, "continuous_paged": paged}
    static = run_static(api, params, arch, mk(), batch_size=args.batch_size,
                        max_len=args.max_len, warmup=not args.no_warmup)
    cont = run_continuous(api, params, arch, mk(), n_slots=args.n_slots,
                          max_len=args.max_len, warmup=not args.no_warmup)
    paged = run_continuous(api, params, arch, mk(), n_slots=args.n_slots,
                           max_len=args.max_len, warmup=not args.no_warmup,
                           engine="paged", **paged_kw)
    ratio = (cont["goodput_tok_s"] / static["goodput_tok_s"]
             if static["goodput_tok_s"] else None)
    paged_ratio = (paged["goodput_tok_s"] / cont["goodput_tok_s"]
                   if cont["goodput_tok_s"] else None)
    # shared-system-prompt workload: where prefix caching actually pays.
    # Identical trace through the dense continuous and paged engines; the
    # paged engine serves the system prompt from cached blocks after the
    # first admission (TTFT drops by ~the shared prefill work).
    mk_shared = lambda: make_shared_prefix_workload(
        np.random.RandomState(args.seed + 1), args.requests, arch.vocab,
        arrival_rate=args.arrival_rate, sys_len=args.sys_prompt,
        suffix_range=(2, 8), ntok_range=(args.min_new, args.max_new),
    )
    sp_cont = run_continuous(api, params, arch, mk_shared(), n_slots=args.n_slots,
                             max_len=args.max_len, warmup=not args.no_warmup)
    sp_paged = run_continuous(api, params, arch, mk_shared(), n_slots=args.n_slots,
                              max_len=args.max_len, warmup=not args.no_warmup,
                              engine="paged", **paged_kw)
    ttft_gain = (sp_cont["ttft_mean_s"] / sp_paged["ttft_mean_s"]
                 if sp_paged.get("ttft_mean_s") else None)
    shared = {
        "sys_prompt_len": args.sys_prompt,
        "continuous": sp_cont,
        "paged": sp_paged,
        "ttft_improvement": ttft_gain,
    }
    print(f"[{mode}] static {static['goodput_tok_s']:.1f} tok/s | continuous "
          f"{cont['goodput_tok_s']:.1f} tok/s | paged "
          f"{paged['goodput_tok_s']:.1f} tok/s | ratio {ratio:.2f}x | "
          f"occupancy {cont['slot_occupancy']:.2f} | prefill compiles "
          f"{cont['prefill_compiles']} vs {static['distinct_prefill_shapes']} shapes "
          f"vs {paged['prefill_compiles']} (paged)")
    print(f"[{mode}] shared-prefix: paged hit rate "
          f"{sp_paged['prefix_hit_rate']:.2f} | ttft {sp_cont['ttft_mean_s']:.4f}s "
          f"-> {sp_paged['ttft_mean_s']:.4f}s ({ttft_gain:.2f}x)")
    long_decode = run_long_decode(mode, args)
    return {"static": static, "continuous": cont, "continuous_paged": paged,
            "goodput_ratio": ratio, "paged_goodput_ratio": paged_ratio,
            "shared_prefix": shared, "long_decode": long_decode}


def multi_device_row(args) -> Optional[Dict]:
    """Every mode's continuous + paged rows served tensor-parallel, in this
    process, on a (n/2, 2) data x model mesh over ``jax.devices()``. Returns
    None (and says why) when fewer than two devices, or an odd count, are
    visible; a failing row raises."""
    n = len(jax.devices())
    if n < 2 or n % 2:
        print(f"[multi-device] skipped: {n} device(s) visible; the tp=2 row "
              "needs an even count >= 2 (on CPU: XLA_FLAGS="
              "--xla_force_host_platform_device_count=8)")
        return None
    from repro.launch.serve import build_serve_mesh

    mesh = build_serve_mesh(2, "")
    print(f"[multi-device] mesh {dict(mesh.shape)}")
    return {m: bench_mode(m, args, mesh=mesh) for m in args.modes.split(",")}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--modes", default="dense,bika,bnn,qnn8")
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--arrival-rate", type=float, default=16.0)
    ap.add_argument("--batch-size", type=int, default=4)
    ap.add_argument("--n-slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--min-prompt", type=int, default=3)
    ap.add_argument("--max-prompt", type=int, default=24)
    ap.add_argument("--min-new", type=int, default=2)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--kv-block-size", type=int, default=8,
                    help="paged engine: tokens per KV block")
    ap.add_argument("--prefill-chunk", type=int, default=16,
                    help="paged engine: chunked-prefill chunk length")
    ap.add_argument("--sys-prompt", type=int, default=24,
                    help="shared-prefix workload: system prompt length")
    ap.add_argument("--long-max-len", type=int, default=256,
                    help="long-decode workload: paged max_len (decode-heavy "
                         "fused-vs-gather TPOT A/B)")
    ap.add_argument("--spec-draft", default="qnn8",
                    choices=("dense", "bnn", "qnn8", "small"),
                    help="speculative A/B row: draft preset for the dense "
                         "target (serve/spec.py)")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="speculative A/B row: verify window width "
                         "(0 disables the row)")
    ap.add_argument("--no-warmup", action="store_true")
    ap.add_argument("--tp", type=int, default=0,
                    help="run the continuous engine tensor-parallel on a "
                         "(n_dev/tp, tp) data x model mesh (0 = off)")
    ap.add_argument("--no-multi-device", action="store_true",
                    help="skip the in-process tp=2 row over the local devices")
    ap.add_argument("--out", default="BENCH_serving.json")
    ap.add_argument("--smoke", action="store_true",
                    help="capped run for CI: bika only, 8 requests, no "
                         "multi-device row (CI runs its own 8-dev smoke)")
    args = ap.parse_args(argv)
    if args.smoke:
        args.modes, args.requests, args.max_new = "bika", 8, 12
        args.no_multi_device = True

    mesh = None
    if args.tp > 0:
        from repro.launch.serve import build_serve_mesh

        mesh = build_serve_mesh(args.tp, "")
        print(f"[serving_bench] mesh {dict(mesh.shape)}")

    results = {m: bench_mode(m, args, mesh=mesh) for m in args.modes.split(",")}
    speculative = None
    if mesh is None and args.spec_k > 1:
        speculative = run_speculative(args)
    multi = None
    if mesh is None and not args.no_multi_device:
        multi = multi_device_row(args)
        if multi is not None:
            for m, row in multi.items():
                if m in results:
                    results[m]["continuous_tp2"] = row["continuous"]
                    base = results[m]["continuous"]["goodput_tok_s"]
                    tp2 = row["continuous"]["goodput_tok_s"]
                    results[m]["tp2_goodput_ratio"] = tp2 / base if base else None
                    if "continuous_paged" in row:
                        results[m]["continuous_paged_tp2"] = row["continuous_paged"]
    payload = {
        "bench": "serving",
        "schema_version": SCHEMA_VERSION,
        "arch": args.arch,
        "workload": {
            "requests": args.requests,
            "arrival_rate_req_s": args.arrival_rate,
            "prompt_len": [args.min_prompt, args.max_prompt],
            "max_new_tokens": [args.min_new, args.max_new],
            "seed": args.seed,
        },
        "engines": {"static": {"batch_size": args.batch_size},
                    "continuous": {"n_slots": args.n_slots},
                    "continuous_paged": {"n_slots": args.n_slots,
                                         "kv_block_size": args.kv_block_size,
                                         "prefill_chunk": args.prefill_chunk,
                                         "sys_prompt_len": args.sys_prompt}},
        "max_len": args.max_len,
        "speculative": speculative,
        "tp": args.tp or None,
        "multi_device": (
            {"devices": len(jax.devices()),
             "mesh": {"data": len(jax.devices()) // 2, "model": 2},
             "row": "continuous_tp2"} if multi is not None else None
        ),
        "results": results,
    }
    with open(args.out, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
    print(f"wrote {args.out}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
