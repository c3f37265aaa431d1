import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 " + os.environ.get("XLA_FLAGS", "")
)

"""Multi-pod dry-run: lower + compile every (architecture x input-shape x
mesh) cell and record memory/cost/collective analyses (EXPERIMENTS.md
§Dry-run; benchmarks/roofline.py derives the three roofline terms from the
JSON artifacts this writes).

The two lines above run before ANY other import — jax pins the host device
count at first init. Everything below is ordinary code.

  PYTHONPATH=src python -m repro.launch.dryrun --mesh single --arch all --shape all
  PYTHONPATH=src python -m repro.launch.dryrun --mesh multi  --cells smollm-360m:train_4k

Per cell:
  * params/opt/caches are jax.eval_shape'd (ShapeDtypeStructs — nothing is
    allocated; full-size grok-1 fits in zero bytes of host RAM);
  * the step function (train_step / prefill / serve_step) is jit'd with
    explicit NamedShardings from the FSDP+TP rule table and .lower().compile()d
    against the 256- or 512-device mesh;
  * compiled.memory_analysis() proves the per-device footprint fits,
    compiled.cost_analysis() gives FLOPs/bytes, and the collective mix is
    parsed out of compiled.as_text().
"""
import argparse
import json
import re
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from repro.configs import ARCH_NAMES, SHAPES, applicable_shapes, get_config
from repro.data.lm import LMDataConfig, lm_batch_specs
from repro.distributed.constraints import mesh_context
from repro.distributed.sharding import (
    FSDP_RULES,
    ShardingRules,
    batch_sharding,
    param_shardings,
    zero1_shardings,
)
from repro.analysis.hlo_audit import analyze_hlo
from repro.launch.mesh import make_production_mesh
from repro.models import build_model
from repro.nn.module import unbox
from repro.optim.adamw import OptimizerSpec, make_optimizer
from repro.train.steps import make_train_step

__all__ = ["run_cell", "parse_collectives", "main"]

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"\b([a-z]+[0-9]+(?:e[0-9]+m[0-9]+(?:fn)?)?|pred)\[([0-9,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def parse_collectives(hlo: str) -> Dict[str, Dict[str, float]]:
    """Sum operand bytes of every collective in the (post-SPMD, per-device)
    HLO. Returns {op: {count, operand_bytes, result_bytes}} + 'total'."""
    out: Dict[str, Dict[str, float]] = {
        k: {"count": 0, "operand_bytes": 0.0, "result_bytes": 0.0} for k in _COLLECTIVES
    }
    for line in hlo.splitlines():
        stripped = line.strip()
        m = re.match(r"(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(.*)$", stripped)
        if not m:
            continue
        rhs = m.group(1)
        opm = re.search(r"\b(all-reduce|all-gather|reduce-scatter|all-to-all|"
                        r"collective-permute)(?:-start|-done)?\(", rhs)
        if not opm:
            continue
        if "-done(" in rhs:  # -done carries no payload; counted at -start
            continue
        op = opm.group(1)
        shapes = _SHAPE_RE.findall(rhs)
        if not shapes:
            continue
        # first shape(s) before the op name = result type; the rest = operands
        prefix = rhs[: opm.start()]
        result_shapes = _SHAPE_RE.findall(prefix)
        operand_shapes = shapes[len(result_shapes):]
        rb = sum(_shape_bytes(d, s) for d, s in result_shapes)
        ob = sum(_shape_bytes(d, s) for d, s in operand_shapes)
        out[op]["count"] += 1
        out[op]["operand_bytes"] += ob
        out[op]["result_bytes"] += rb
    out["total"] = {
        "count": sum(v["count"] for v in out.values()),
        "operand_bytes": sum(v["operand_bytes"] for v in out.values()),
        "result_bytes": sum(v["result_bytes"] for v in out.values()),
    }
    return out


def _mem_dict(mem) -> Dict[str, float]:
    keys = (
        "argument_size_in_bytes",
        "output_size_in_bytes",
        "temp_size_in_bytes",
        "alias_size_in_bytes",
        "generated_code_size_in_bytes",
    )
    d = {}
    for k in keys:
        v = getattr(mem, k, None)
        if v is not None:
            d[k] = float(v)
    if not d:
        d["repr"] = str(mem)
    return d


def _auto_microbatches(cfg, shape, mesh) -> int:
    """Bound live activation memory: aim <= ~8k tokens per device per
    microbatch (the scan-over-layers carry stash is L x tokens x d_model)."""
    dp = 1
    for a in ("pod", "data"):
        dp *= mesh.shape.get(a, 1)
    tokens_per_dev = shape.seq_len * shape.global_batch // dp
    mb = max(1, tokens_per_dev // 8192)
    bp = shape.global_batch // dp  # per-device batch rows
    while bp % mb != 0 and mb > 1:  # microbatches must divide the batch
        mb -= 1
    return mb


def build_cell(arch: str, shape_name: str, mesh, *, mode: str = "bika",
               rules: Optional[ShardingRules] = None, microbatches: Optional[int] = None,
               remat: bool = True, extra_cfg: Optional[Dict] = None,
               shard_grads: bool = False, quantized_kv: bool = False):
    """Returns (jitted_fn, example_args, meta) for one cell — not yet lowered."""
    shape = SHAPES[shape_name]
    rules = rules or ShardingRules(FSDP_RULES)
    over = dict(
        compute_mode=mode,
        compute_dtype="bfloat16",
        param_dtype="float32",
        remat=remat,
        pack_signs=(mode == "bika"),
    )
    over.update(extra_cfg or {})
    cfg = get_config(arch, **over)
    meta: Dict[str, Any] = {"arch": arch, "shape": shape_name, "mode": mode,
                            "kind": shape.kind}

    if shape.kind == "train":
        api = build_model(cfg, phase="train")
        boxed = jax.eval_shape(api.init, jax.random.PRNGKey(0))
        p_sh = param_shardings(mesh, boxed, rules)
        params_s = unbox(boxed)
        opt_init, opt_update = make_optimizer(OptimizerSpec())
        opt_s = jax.eval_shape(opt_init, params_s)
        z1 = zero1_shardings(mesh, boxed, rules)
        rep = NamedSharding(mesh, PartitionSpec())
        o_sh = {k: (z1 if isinstance(v, dict) else rep) for k, v in opt_s.items()}
        mb = microbatches or _auto_microbatches(cfg, shape, mesh)
        meta["microbatches"] = mb
        step = make_train_step(api, opt_update, microbatches=mb,
                               grad_shardings=z1 if shard_grads else None)
        dcfg = LMDataConfig(
            vocab=cfg.vocab, seq_len=shape.seq_len, global_batch=shape.global_batch,
            frames_dim=cfg.d_model if cfg.family == "encdec" else 0,
        )
        batch_s = lm_batch_specs(dcfg)
        b_sh = jax.tree_util.tree_map(
            lambda l: batch_sharding(mesh, len(l.shape), 0, rules), batch_s
        )
        fn = jax.jit(step, in_shardings=(p_sh, o_sh, b_sh),
                     donate_argnums=(0, 1))
        args = (params_s, opt_s, batch_s)

    elif shape.kind == "prefill":
        api = build_model(cfg, phase="serve")
        boxed = jax.eval_shape(api.init, jax.random.PRNGKey(0))
        p_sh = param_shardings(mesh, boxed, rules)
        params_s = unbox(boxed)
        batch_s = {"tokens": jax.ShapeDtypeStruct(
            (shape.global_batch, shape.seq_len), jnp.int32)}
        if cfg.family == "encdec":
            batch_s["frames"] = jax.ShapeDtypeStruct(
                (shape.global_batch, shape.seq_len, cfg.d_model), jnp.float32)
        b_sh = jax.tree_util.tree_map(
            lambda l: batch_sharding(mesh, len(l.shape), 0, rules), batch_s
        )
        fn = jax.jit(
            lambda p, b: api.prefill(p, b, max_len=shape.seq_len),
            in_shardings=(p_sh, b_sh),
        )
        args = (params_s, batch_s)

    elif shape.kind == "decode":
        api = build_model(cfg, phase="serve")
        boxed = jax.eval_shape(api.init, jax.random.PRNGKey(0))
        p_sh = param_shardings(mesh, boxed, rules)
        params_s = unbox(boxed)
        b = shape.global_batch
        cache_kwargs = {}
        if cfg.family == "encdec":
            cache_kwargs["encoder_len"] = min(shape.seq_len, 32768)
        if quantized_kv and cfg.family in ("lm", "encdec", "hybrid"):
            cache_kwargs["quantized"] = True
        cache_s = jax.eval_shape(
            lambda: api.init_cache(b, shape.seq_len, **cache_kwargs)
        )
        tok_s = jax.ShapeDtypeStruct((b, 1), jnp.int32)
        pos_s = jax.ShapeDtypeStruct((), jnp.int32)
        dp = 1
        for ax in ("pod", "data"):
            dp *= mesh.shape.get(ax, 1)
        if b % dp == 0:
            tok_sh = batch_sharding(mesh, 2, 0, rules)
        else:  # long_500k: batch 1 is replicated; SP shards the cache instead
            tok_sh = NamedSharding(mesh, PartitionSpec())
        # cache/position shardings: GSPMD propagation chooses (heads/batch
        # shard flows in from the projections); donate the cache.
        fn = jax.jit(
            api.decode_step,
            in_shardings=(p_sh, tok_sh, None, None),
            donate_argnums=(2,),
        )
        args = (params_s, tok_s, cache_s, pos_s)
    else:
        raise ValueError(shape.kind)

    return fn, args, meta


def run_cell(arch: str, shape_name: str, mesh, mesh_name: str, *, mode: str = "bika",
             out_dir: Optional[str] = None, save_hlo: bool = False, **kw) -> Dict[str, Any]:
    t0 = time.time()
    rec: Dict[str, Any] = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                           "mode": mode, "status": "ok"}
    try:
        fn, args, meta = build_cell(arch, shape_name, mesh, mode=mode, **kw)
        rec.update(meta)
        with mesh_context(mesh):
            lowered = fn.lower(*args)
            t_lower = time.time()
            compiled = lowered.compile()
            t_compile = time.time()
        rec["lower_s"] = round(t_lower - t0, 2)
        rec["compile_s"] = round(t_compile - t_lower, 2)
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0]
        rec["cost"] = {k: float(v) for k, v in cost.items()
                       if isinstance(v, (int, float)) and np.isfinite(float(v))}
        rec["memory"] = _mem_dict(compiled.memory_analysis())
        hlo = compiled.as_text()
        rec["collectives"] = parse_collectives(hlo)
        # trip-count-aware static model (cost_analysis counts while bodies
        # once; see analysis/hlo_audit.py) — the roofline reads `static`.
        static = analyze_hlo(hlo, mesh.size)
        rec["static"] = {
            "flops": static["flops"],
            "bytes": static["bytes"],
            "collectives": static["collectives"],
            "trip_counts": static["trip_counts"],
        }
        rec["hlo_bytes"] = len(hlo)
        rec["n_devices"] = mesh.size
        if save_hlo or os.environ.get("DRYRUN_SAVE_HLO"):
            os.makedirs(out_dir or ".", exist_ok=True)
            with open(os.path.join(out_dir or ".",
                                   f"{arch}__{shape_name}__{mode}.hlo.txt"), "w") as f:
                f.write(hlo)
    except Exception as e:  # a failing cell is a bug — record it loudly
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    rec["total_s"] = round(time.time() - t0, 2)
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        path = os.path.join(out_dir, f"{arch}__{shape_name}__{mode}.json")
        with open(path, "w") as f:
            json.dump(rec, f, indent=1, sort_keys=True)
    return rec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="multi-pod dry-run")
    ap.add_argument("--mesh", choices=("single", "multi"), default="single")
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mode", default="bika", choices=("bika", "dense", "bnn", "qnn8"))
    ap.add_argument("--cells", default=None,
                    help="comma list of arch:shape pairs (overrides --arch/--shape)")
    ap.add_argument("--out", default=None)
    ap.add_argument("--microbatches", type=int, default=None)
    ap.add_argument("--impl", default=None,
                    choices=("fused", "cvjp", "cvjp_tiled", "pallas"),
                    help="bika contraction implementation override")
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args(argv)

    multi = args.mesh == "multi"
    mesh = make_production_mesh(multi_pod=multi)
    mesh_name = "pod2x16x16" if multi else "pod16x16"
    out_dir = args.out or f"results/dryrun/{mesh_name}"

    cells = []
    if args.cells:
        for c in args.cells.split(","):
            a, s = c.split(":")
            cells.append((a, s))
    else:
        archs = ARCH_NAMES if args.arch == "all" else (args.arch,)
        for a in archs:
            cfg = get_config(a)
            shapes = applicable_shapes(cfg) if args.shape == "all" else (args.shape,)
            for s in shapes:
                cells.append((a, s))

    failures = 0
    for a, s in cells:
        path = os.path.join(out_dir, f"{a}__{s}__{args.mode}.json")
        if args.skip_existing and os.path.exists(path):
            with open(path) as f:
                old = json.load(f)
            if old.get("status") == "ok":
                print(f"[skip] {a}:{s} (cached ok)")
                continue
        extra = {"bika_impl": args.impl} if args.impl else None
        rec = run_cell(a, s, mesh, mesh_name, mode=args.mode, out_dir=out_dir,
                       microbatches=args.microbatches, extra_cfg=extra,
                       save_hlo=args.save_hlo)
        if rec["status"] == "ok":
            flops = rec["cost"].get("flops", float("nan"))
            coll = rec["collectives"]["total"]["operand_bytes"]
            print(f"[ok]   {a}:{s} lower {rec['lower_s']}s compile {rec['compile_s']}s "
                  f"flops/dev {flops:.3e} coll/dev {coll:.3e}B")
        else:
            failures += 1
            print(f"[FAIL] {a}:{s} {rec['error']}")
    print(f"done: {len(cells) - failures}/{len(cells)} cells ok on {mesh_name}")
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
