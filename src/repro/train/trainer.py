"""Trainer: mesh-aware end-to-end training with checkpoint/restart.

The supervisor pattern (``run_with_restarts``) is the single-process analogue
of a cluster job controller: the Trainer may die at any step (we inject
``SimulatedFailure`` in tests), and the supervisor re-creates it; the new
Trainer restores the latest checkpoint + step counter and the step-indexed
data pipeline regenerates the exact next batch — restart is bitwise
reproducible (tested). On a real cluster the same code path handles
preemption and node failure; elastic restore (checkpoint.manager) covers
coming back up on a *different* mesh shape.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from repro.checkpoint import CheckpointManager, latest_step
from repro.data.lm import LMDataConfig, lm_batch
from repro.distributed.constraints import mesh_context
from repro.distributed.sharding import (
    ShardingRules,
    batch_sharding,
    param_shardings,
    zero1_shardings,
)
from repro.models import build_model
from repro.models.base import ArchConfig
from repro.nn.module import unbox
from repro.optim.adamw import OptimizerSpec, make_optimizer
from .steps import make_train_step

__all__ = ["TrainConfig", "Trainer", "SimulatedFailure", "run_with_restarts"]


class SimulatedFailure(RuntimeError):
    """Raised by the failure-injection hook to emulate preemption/crash."""


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    arch: ArchConfig
    seq_len: int = 128
    global_batch: int = 8
    microbatches: int = 1
    steps: int = 50
    ckpt_dir: Optional[str] = None
    ckpt_every: int = 25
    keep: int = 3
    aux_weight: float = 0.01
    z_loss: float = 1e-4
    data_seed: int = 0
    log_every: int = 10
    async_ckpt: bool = False


class Trainer:
    def __init__(
        self,
        cfg: TrainConfig,
        mesh: Optional[Mesh] = None,
        rules: ShardingRules = ShardingRules(),
        opt: Optional[OptimizerSpec] = None,
        fail_at_step: Optional[int] = None,
    ):
        # Sharding-invariant RNG. The legacy threefry lowering generates
        # different bits depending on the output sharding, so the
        # jit(out_shardings=...)-generated params/batches below diverge
        # between mesh shapes — 1-device vs N-device training would differ
        # from step 0 (observed ~0.03 in first-step loss). Set before any
        # trace so the elastic-restore and DPxTP-equivalence guarantees
        # hold regardless of mesh shape.
        jax.config.update("jax_threefry_partitionable", True)
        self.cfg = cfg
        self.rules = rules
        self.fail_at_step = fail_at_step
        if mesh is None:
            mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
        self.mesh = mesh
        arch = cfg.arch
        self.api = build_model(arch, phase="train")
        self.opt_spec = opt or OptimizerSpec(total_steps=cfg.steps)
        opt_init, opt_update = make_optimizer(self.opt_spec)

        self.data_cfg = LMDataConfig(
            vocab=arch.vocab,
            seq_len=cfg.seq_len,
            global_batch=cfg.global_batch,
            seed=cfg.data_seed,
            frames_dim=arch.d_model if arch.family == "encdec" else 0,
        )

        boxed = jax.eval_shape(self.api.init, jax.random.PRNGKey(0))
        self.param_sh = param_shardings(mesh, boxed, rules)
        self.param_sh_plain = self.param_sh  # already a plain (unboxed-aligned) tree
        opt_struct = jax.eval_shape(opt_init, unbox(boxed))
        self.opt_sh = self._opt_shardings(boxed, opt_struct)

        self.jit_init = jax.jit(
            lambda k: unbox(self.api.init(k)), out_shardings=self.param_sh_plain
        )
        step_fn = make_train_step(
            self.api,
            opt_update,
            aux_weight=cfg.aux_weight,
            z_loss=cfg.z_loss,
            microbatches=cfg.microbatches,
        )
        self.jit_step = jax.jit(
            step_fn,
            donate_argnums=(0, 1),
            out_shardings=(self.param_sh_plain, self.opt_sh, None),
        )
        self.jit_opt_init = jax.jit(opt_init, out_shardings=self.opt_sh)
        self._batch_fn = jax.jit(
            lambda step: lm_batch(self.data_cfg, step),
            out_shardings=self._batch_shardings(),
        )
        self.ckpt = (
            CheckpointManager(cfg.ckpt_dir, keep=cfg.keep) if cfg.ckpt_dir else None
        )
        self.metrics_log: List[Dict[str, float]] = []

    def _batch_shardings(self):
        specs = jax.eval_shape(lambda s: lm_batch(self.data_cfg, s), jnp.zeros((), jnp.int32))
        return jax.tree_util.tree_map(
            lambda l: batch_sharding(self.mesh, len(l.shape), 0, self.rules), specs
        )

    def _opt_shardings(self, boxed_params, opt_struct):
        z1_plain = zero1_shardings(self.mesh, boxed_params, self.rules)
        rep = NamedSharding(self.mesh, PartitionSpec())
        return {
            k: (z1_plain if isinstance(v, dict) else rep)
            for k, v in opt_struct.items()
        }

    # -- lifecycle ---------------------------------------------------------

    def init_or_restore(self, key=None):
        key = key if key is not None else jax.random.PRNGKey(42)
        start = 0
        with mesh_context(self.mesh):
            params = self.jit_init(key)
            opt_state = self.jit_opt_init(params)
        if self.ckpt and latest_step(self.cfg.ckpt_dir) is not None:
            state, manifest = self.ckpt.restore_latest(
                {"params": params, "opt": opt_state},
                shardings={"params": self.param_sh_plain, "opt": self.opt_sh},
            )
            params, opt_state = state["params"], state["opt"]
            start = int(manifest["step"])
        return params, opt_state, start

    def run(self, params=None, opt_state=None, start_step: Optional[int] = None):
        if params is None:
            params, opt_state, start_step = self.init_or_restore()
        t0 = time.time()
        with mesh_context(self.mesh):
            for step in range(start_step, self.cfg.steps):
                if self.fail_at_step is not None and step == self.fail_at_step:
                    raise SimulatedFailure(f"injected failure at step {step}")
                batch = self._batch_fn(jnp.asarray(step, jnp.int32))
                params, opt_state, metrics = self.jit_step(params, opt_state, batch)
                if (step + 1) % self.cfg.log_every == 0 or step == start_step:
                    m = {k: float(v) for k, v in metrics.items()}
                    m["step"] = step
                    # repro: noqa-RPA005 -- float(v) above blocked on the
                    # step's metrics, so the wall clock is already synced
                    m["wall_s"] = time.time() - t0
                    self.metrics_log.append(m)
                if self.ckpt and (step + 1) % self.cfg.ckpt_every == 0:
                    self.ckpt.save(
                        step + 1,
                        {"params": params, "opt": opt_state},
                        blocking=not self.cfg.async_ckpt,
                    )
        if self.ckpt:
            self.ckpt.save(self.cfg.steps, {"params": params, "opt": opt_state})
            self.ckpt.wait()
        return params, opt_state, self.metrics_log


def run_with_restarts(
    make_trainer: Callable[[], Trainer],
    *,
    max_restarts: int = 5,
):
    """Cluster-supervisor analogue: restart the trainer until it completes."""
    attempts = 0
    while True:
        trainer = make_trainer()
        try:
            return trainer.run() + (attempts,)
        except SimulatedFailure:
            attempts += 1
            if attempts > max_restarts:
                raise
