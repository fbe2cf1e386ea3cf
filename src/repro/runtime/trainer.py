"""Trainer: the end-to-end training loop wiring every streaming layer together.

Streams in play per step:
  L1  host batch prefetch (PrefetchIterator, depth = stream count),
  L1' async checkpoint D2H,
  L3  grad-accumulation microbatch streaming inside train_step,
plus fault tolerance: supervised steps with retry, auto-resume from the
latest checkpoint, straggler logging, elastic re-mesh on restore.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.checkpointer import Checkpointer
from repro.data.pipeline import PrefetchIterator, SyntheticLM
from repro.launch import sharding, steps as steps_lib
from repro.models import transformer as T
from repro.models.transformer import ModelConfig
from repro.optim import adamw
from repro.runtime.fault_tolerance import StepSupervisor


@dataclasses.dataclass
class TrainConfig:
    global_batch: int = 8
    seq_len: int = 128
    steps: int = 100
    accum: int = 1
    prefetch_depth: int = 2
    checkpoint_dir: str | None = None
    checkpoint_every: int = 50
    log_every: int = 10
    seed: int = 0
    lr: float = 3e-4
    warmup: int = 20


class Trainer:
    def __init__(
        self,
        cfg: ModelConfig,
        tcfg: TrainConfig,
        *,
        mesh: jax.sharding.Mesh | None = None,
        log: Callable[[str], None] = print,
    ):
        self.cfg = cfg
        self.tcfg = tcfg
        self.mesh = mesh
        self.log = log
        self.supervisor = StepSupervisor()
        self.ckpt = (
            Checkpointer(tcfg.checkpoint_dir) if tcfg.checkpoint_dir else None)

        from repro.optim import schedule as sched
        self.opt_cfg = adamw.AdamWConfig(
            lr=tcfg.lr,
            schedule=sched.warmup_cosine(tcfg.warmup, tcfg.steps))
        self._step_fn = steps_lib.make_train_step(
            cfg, self.opt_cfg, accum=tcfg.accum)

    # -- state ----------------------------------------------------------------

    def init_state(self, key) -> tuple[Any, Any]:
        params = T.init_params(self.cfg, key)
        opt_state = adamw.init_state(params, self.opt_cfg.moment_dtype)
        if self.mesh is not None:
            pshape = jax.eval_shape(lambda: params)
            pspecs = sharding.param_specs(pshape, self.mesh)
            params = jax.device_put(params, sharding.to_named(pspecs, self.mesh))
            ospecs = sharding.opt_state_specs(pspecs)
            opt_state = jax.device_put(
                opt_state, sharding.to_named(ospecs, self.mesh))
        return params, opt_state

    def _jit_step(self):
        if self.mesh is None:
            return jax.jit(self._step_fn, donate_argnums=(0, 1))
        pshape = jax.eval_shape(
            lambda k: T.init_params(self.cfg, k), jax.random.PRNGKey(0))
        pspecs = sharding.param_specs(pshape, self.mesh)
        ospecs = sharding.opt_state_specs(pspecs)
        return jax.jit(
            self._step_fn,
            in_shardings=(sharding.to_named(pspecs, self.mesh),
                          sharding.to_named(ospecs, self.mesh), None),
            donate_argnums=(0, 1),
        )

    def _source(self, start_step: int) -> PrefetchIterator:
        extra = {}
        if self.cfg.is_encoder_decoder:
            extra["enc_inputs"] = (
                (self.cfg.encoder_seq, self.cfg.d_model), np.float32)
        if self.cfg.prefix_len:
            extra["prefix_embeds"] = (
                (self.cfg.prefix_len, self.cfg.d_model), np.float32)
        src = SyntheticLM(
            self.cfg.vocab_size, global_batch=self.tcfg.global_batch,
            seq_len=self.tcfg.seq_len, seed=self.tcfg.seed, extra=extra)
        return PrefetchIterator(
            iter(src), depth=self.tcfg.prefetch_depth, start_step=start_step)

    # -- loop -------------------------------------------------------------------

    def train(self) -> dict[str, Any]:
        """Run (or resume) the training loop. Returns final metrics + history."""
        start_step = 0
        params = opt_state = None
        if self.ckpt is not None and self.ckpt.latest_step() is not None:
            (params, opt_state), meta = self._restore()
            start_step = meta["step"] + 1
            self.log(f"[trainer] resumed from step {meta['step']}")
        if params is None:
            params, opt_state = self.init_state(jax.random.PRNGKey(self.tcfg.seed))

        step_fn = self._jit_step()
        data = self._source(start_step)
        losses: list[float] = []
        ctx = (jax.set_mesh(self.mesh) if self.mesh is not None
               else contextlib.nullcontext())
        t_start = time.perf_counter()
        with ctx:
            for step in range(start_step, self.tcfg.steps):
                batch = next(data)

                def run(batch=batch):
                    nonlocal params, opt_state
                    params, opt_state, metrics = step_fn(params, opt_state, batch)
                    jax.block_until_ready(metrics["loss"])
                    return metrics

                metrics = self.supervisor.run_step(step, run)
                losses.append(float(metrics["loss"]))
                if step % self.tcfg.log_every == 0 or step == self.tcfg.steps - 1:
                    self.log(
                        f"[trainer] step {step:5d} loss {losses[-1]:.4f} "
                        f"gnorm {float(metrics['grad_norm']):.3f} "
                        f"lr {float(metrics['lr']):.2e}")
                if (self.ckpt is not None and self.tcfg.checkpoint_every
                        and (step + 1) % self.tcfg.checkpoint_every == 0):
                    self.ckpt.save(step, {"params": params, "opt": opt_state})
        data.close()
        if self.ckpt is not None:
            self.ckpt.save(self.tcfg.steps - 1,
                           {"params": params, "opt": opt_state}, blocking=True)
        wall = time.perf_counter() - t_start
        return {
            "losses": losses,
            "final_loss": losses[-1] if losses else None,
            "params": params,
            "wall_s": wall,
            "supervisor": self.supervisor.straggler_report(),
        }

    def _restore(self):
        tree, meta = self.ckpt.restore()
        params, opt_state = tree["params"], tree["opt"]
        if self.mesh is not None:  # elastic re-mesh path
            pshape = jax.eval_shape(lambda: params)
            pspecs = sharding.param_specs(pshape, self.mesh)
            params = jax.device_put(params, sharding.to_named(pspecs, self.mesh))
            ospecs = sharding.opt_state_specs(pspecs)
            opt_state = jax.device_put(
                opt_state, sharding.to_named(ospecs, self.mesh))
        return (params, opt_state), meta
