"""Serving launcher CLI: bring up a hardware-form (serve-phase) model and
drain a synthetic request stream through either engine.

    PYTHONPATH=src python -m repro.launch.serve --arch smollm-360m --smoke \
        --requests 8 --new-tokens 16 --engine continuous

``--arrival-rate R`` (req/s, continuous engine) replays a Poisson arrival
process instead of submitting everything up front: the launcher ticks the
slot scheduler and admits each request when its arrival time elapses —
the same open-loop load shape as benchmarks/serving_bench.py.

Observability (DESIGN.md §8, all opt-in):

- ``--trace-out trace.jsonl`` records the run's request-lifecycle spans,
  the serve loop's ``serve.*`` spans and paged-path events to JSONL (plus
  ``trace.perfetto.json`` next to it, loadable at ui.perfetto.dev), and
  prints the ``serve.*`` spans' self times; validate/report with
  ``benchmarks/trace_report.py``.
- ``--metrics-out metrics.prom`` dumps the metrics registry in Prometheus
  text format (``.json`` suffix -> JSON snapshot).
- ``--profile-dir DIR`` wraps the drain in ``jax.profiler.trace``; the
  ``serve.*`` spans appear on its host plane.

``--tp N`` serves tensor-parallel on a (n_devices/N, N) data x model mesh
built from the local devices (``--mesh-shape d,m`` pins an explicit shape):
params go out under ``param_shardings``, the KV pool shards kv_heads over
the model axis, and outputs stay token-for-token identical to 1-device
serving (DESIGN.md §5). CPU smoke: prefix with
``XLA_FLAGS=--xla_force_host_platform_device_count=8``.
"""
from __future__ import annotations

import argparse

import jax
import numpy as np

from repro.configs import ARCH_NAMES, get_config, get_smoke
from repro.launch.cache import use_persistent_compile_cache
from repro.models import build_model
from repro.nn.module import param_bytes, unbox
from repro.obs import MetricsRegistry, Tracer, profile_trace, self_times, set_tracer
from repro.serve.engine import Request, ServeEngine
from repro.serve.scheduler import replay_arrivals


def build_serve_mesh(tp: int, mesh_shape: str):
    """Mesh from the CLI flags (None when both are unset): ``--mesh-shape
    d,m`` wins; otherwise ``--tp N`` uses every local device as (n//N, N)."""
    from repro.distributed.meshes import make_mesh

    if mesh_shape:
        shape = tuple(int(v) for v in mesh_shape.split(","))
        if len(shape) != 2:
            raise SystemExit(f"--mesh-shape wants 'data,model', got {mesh_shape!r}")
        return make_mesh(shape, ("data", "model"))
    if tp > 0:
        n = len(jax.devices())
        if n % tp:
            raise SystemExit(f"--tp {tp} does not divide the {n} local devices")
        return make_mesh((n // tp, tp), ("data", "model"))
    return None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m", choices=ARCH_NAMES)
    ap.add_argument("--mode", default="bika", choices=("dense", "bika", "bnn", "qnn8"))
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--batch-size", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--quantized-kv", action="store_true")
    ap.add_argument("--engine", default="auto",
                    choices=("auto", "static", "continuous", "paged"))
    ap.add_argument("--n-slots", type=int, default=0,
                    help="continuous decode slots (0 -> batch-size)")
    ap.add_argument("--kv-block-size", type=int, default=16,
                    help="paged engine: tokens per KV block")
    ap.add_argument("--kv-n-blocks", type=int, default=0,
                    help="paged engine: physical pool blocks "
                         "(0 -> n_slots * max_len / block_size)")
    ap.add_argument("--prefix-cache", dest="prefix_cache", action="store_true",
                    default=True, help="paged engine: shared-prefix block reuse "
                                       "(default on)")
    ap.add_argument("--no-prefix-cache", dest="prefix_cache", action="store_false")
    ap.add_argument("--prefill-chunk", type=int, default=32,
                    help="paged engine: chunked-prefill chunk length")
    ap.add_argument("--paged-attn-route", default="fused",
                    choices=("fused", "gather"),
                    help="paged attention: fused block-table kernel (default) "
                         "or the XLA gather oracle")
    ap.add_argument("--spec-draft", default="",
                    choices=("", "dense", "bika", "bnn", "qnn8", "small"),
                    help="speculative decoding: draft preset built from the "
                         "SAME trained weights (registry backend or 'small' "
                         "= half-depth dense). Empty = off. Greedy outputs "
                         "stay token-for-token identical to target-only")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="speculative window: draft proposes k-1 tokens, the "
                         "target verifies all k in one step (k=1 degenerates "
                         "to normal decode)")
    ap.add_argument("--arrival-rate", type=float, default=0.0,
                    help="Poisson arrival rate in req/s (0 = submit all up front)")
    ap.add_argument("--tp", type=int, default=0,
                    help="tensor-parallel size: serve on a (n_dev/tp, tp) "
                         "data x model mesh (0 = single device)")
    ap.add_argument("--mesh-shape", default="",
                    help="explicit 'data,model' mesh shape (overrides --tp)")
    ap.add_argument("--trace-out", default="",
                    help="write request-lifecycle trace JSONL here (also "
                         "writes <stem>.perfetto.json for ui.perfetto.dev)")
    ap.add_argument("--trace-capacity", type=int, default=65536,
                    help="trace ring-buffer capacity in records")
    ap.add_argument("--metrics-out", default="",
                    help="dump the metrics registry: Prometheus text, or a "
                         "JSON snapshot when the path ends in .json")
    ap.add_argument("--profile-dir", default="",
                    help="wrap the drain in jax.profiler.trace writing here")
    args = ap.parse_args(argv)
    use_persistent_compile_cache()
    mesh = build_serve_mesh(args.tp, args.mesh_shape)

    tracer = Tracer(capacity=args.trace_capacity) if args.trace_out else None
    if tracer is not None:
        set_tracer(tracer)  # autotune + other global-hook sites report here
    registry = MetricsRegistry() if (args.metrics_out or args.trace_out) else None

    getter = get_smoke if args.smoke else get_config
    arch = getter(args.arch, compute_mode=args.mode, remat=False)
    if args.mode == "bika":
        arch = arch.replace(pack_signs=True)
    if args.paged_attn_route != arch.paged_attn_route:
        arch = arch.replace(paged_attn_route=args.paged_attn_route)
    eng_kw = dict(batch_size=args.batch_size,
                  max_len=args.max_len, quantized_kv=args.quantized_kv,
                  engine=args.engine, n_slots=args.n_slots or None,
                  kv_block_size=args.kv_block_size,
                  kv_n_blocks=args.kv_n_blocks or None,
                  prefix_cache=args.prefix_cache,
                  prefill_chunk=args.prefill_chunk, mesh=mesh,
                  tracer=tracer, registry=registry)
    if args.spec_draft:
        # speculative decoding needs the trained float tree so the SAME
        # weights can be converted through both the target backend and the
        # cheaper draft backend (serve/spec.py)
        tparams = unbox(build_model(arch, phase="train").init(jax.random.PRNGKey(0)))
        print(f"[serve] {arch.name} mode={args.mode} "
              f"params={param_bytes(tparams):,} B "
              f"spec: draft={args.spec_draft} k={args.spec_k}")
        eng = ServeEngine.from_trained(tparams, arch, spec_draft=args.spec_draft,
                                       spec_k=args.spec_k, **eng_kw)
    else:
        api = build_model(arch, phase="serve")
        params = unbox(api.init(jax.random.PRNGKey(0)))
        print(f"[serve] {arch.name} mode={args.mode} "
              f"params={param_bytes(params):,} B")
        eng = ServeEngine(api, params, arch, **eng_kw)
    mesh_note = (f" mesh={dict(mesh.shape)}" if mesh is not None else "")
    print(f"[serve] engine={eng.engine}{mesh_note}")
    rng = np.random.RandomState(0)
    extra = None
    if arch.family == "encdec":
        # sized to the engine's packed batch ceiling; the engine trims it to
        # each packed group (incl. the final partial batch)
        extra = {"frames": 0.1 * jax.random.normal(
            jax.random.PRNGKey(1),
            (args.batch_size, 16, arch.d_model))}
    reqs = []
    for i in range(args.requests):
        plen = int(rng.randint(3, 12))
        reqs.append(Request(rid=i, prompt=rng.randint(0, arch.vocab, plen)
                            .astype(np.int32), max_new_tokens=args.new_tokens))

    if args.arrival_rate > 0 and eng.scheduler is None:
        print("[serve] WARNING: --arrival-rate needs a slot-scheduler engine "
              f"(continuous/paged); engine={eng.engine} drains closed-loop instead")
    with profile_trace(args.profile_dir):
        if args.arrival_rate > 0 and eng.scheduler is not None:
            arrivals = np.cumsum(rng.exponential(1.0 / args.arrival_rate, len(reqs)))
            done, _ = replay_arrivals(eng.scheduler, list(zip(arrivals, reqs)))
        else:
            for r in reqs:
                eng.submit(r)
            done = eng.run(extra_batch=extra)
    for r in sorted(done, key=lambda q: q.rid)[:4]:
        print(f"  req {r.rid}: {list(r.output)[:10]}...")
    print(f"[serve] completed {len(done)} requests")
    if eng.metrics is not None:
        m = eng.metrics.summary()
        print(f"[serve] goodput={m['goodput_tok_s']:.1f} tok/s "
              f"occupancy={m['slot_occupancy']:.2f} "
              f"prefill compiles={m['prefill_compiles']}")
        if eng.engine == "paged":
            print(f"[serve] prefix hit rate={m['prefix_hit_rate']:.2f} "
                  f"blocks peak={m['blocks_in_use_peak']} "
                  f"chunks={m['prefill_chunks']} "
                  f"deferrals={m['admission_deferrals']}")
        print(f"[serve] kv pool={m['kv_pool_bytes']:,} B "
              f"({m['kv_bytes_per_token']:.0f} B/token) "
              f"in-use peak={m['kv_bytes_in_use_peak']:,} B "
              f"decode HBM/token={m['decode_hbm_bytes_per_token']:.0f} B")
        if m.get("spec_rounds"):
            print(f"[serve] spec: rounds={m['spec_rounds']} "
                  f"accept rate={m['spec_accept_rate']:.2f} "
                  f"tokens/round={m['spec_tokens_per_round']:.2f}")
    if tracer is not None:
        own = self_times(tracer.records)
        total = sum(own.values())
        if total > 0:
            split = " ".join(f"{k}={v * 1e3:.1f}ms({v / total:.0%})"
                             for k, v in sorted(own.items(), key=lambda kv: -kv[1]))
            print(f"[serve] profile (serve.* span self times): {split}")
        summary = (eng.metrics.summary() if eng.metrics is not None else None)
        requests = ([r.metrics.to_dict() for r in done
                     if r.metrics is not None] or None)
        tracer.write_jsonl(args.trace_out, summary=summary, requests=requests)
        stem = args.trace_out[:-6] if args.trace_out.endswith(".jsonl") \
            else args.trace_out
        tracer.write_perfetto(stem + ".perfetto.json")
        print(f"[serve] trace: {len(tracer)} records "
              f"({tracer.dropped} dropped) -> {args.trace_out}")
        set_tracer(None)
    if registry is not None and args.metrics_out:
        if args.metrics_out.endswith(".json"):
            registry.write_json(args.metrics_out)
        else:
            registry.write_prometheus(args.metrics_out)
        print(f"[serve] metrics -> {args.metrics_out}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
