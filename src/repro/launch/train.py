"""Training launcher CLI.

    PYTHONPATH=src python -m repro.launch.train --arch smollm-360m \
        --mode bika --steps 100 --seq-len 512 --batch 32 \
        --ckpt /tmp/ck --mesh auto [--smoke] [--fsdp]

``--mesh auto`` uses every local device (data x model = N x 1); ``--mesh
prod`` builds the (16,16) production mesh (requires 256 devices — i.e. a real
pod or XLA_FLAGS-forced host devices); ``--smoke`` swaps in the reduced
config for CPU-scale runs.
"""
from __future__ import annotations

import argparse

import jax
import numpy as np
from jax.sharding import Mesh

from repro.configs import ARCH_NAMES, get_config, get_smoke
from repro.distributed.sharding import FSDP_RULES, LOGICAL_RULES, ShardingRules
from repro.launch.cache import use_persistent_compile_cache
from repro.optim.adamw import OptimizerSpec
from repro.train.trainer import TrainConfig, Trainer


def _mesh(kind: str) -> Mesh:
    if kind == "prod":
        from .mesh import make_production_mesh

        return make_production_mesh()
    devs = jax.devices()
    return Mesh(np.asarray(devs).reshape(len(devs), 1), ("data", "model"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m", choices=ARCH_NAMES)
    ap.add_argument("--mode", default="bika", choices=("dense", "bika", "bnn", "qnn8"))
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=512)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--mesh", default="auto", choices=("auto", "prod"))
    ap.add_argument("--fsdp", action="store_true")
    args = ap.parse_args(argv)
    use_persistent_compile_cache()

    getter = get_smoke if args.smoke else get_config
    arch = getter(args.arch, compute_mode=args.mode)
    cfg = TrainConfig(
        arch=arch, seq_len=args.seq_len, global_batch=args.batch,
        microbatches=args.microbatches, steps=args.steps, ckpt_dir=args.ckpt,
        log_every=max(args.steps // 20, 1),
    )
    rules = ShardingRules(FSDP_RULES if args.fsdp else LOGICAL_RULES)
    trainer = Trainer(cfg, mesh=_mesh(args.mesh), rules=rules,
                      opt=OptimizerSpec(peak_lr=args.lr, total_steps=args.steps))
    _, _, log = trainer.run()
    for m in log:
        print(f"step {m['step']:>6}  loss {m['loss']:.4f}  acc {m['accuracy']:.3f}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
