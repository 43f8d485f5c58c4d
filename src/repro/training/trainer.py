"""Trainer: the host-side loop the framework deploys.

Wires together the sharded train step, the deterministic data pipeline,
checkpointing (periodic + async), telemetry hooks, preemption handling, and
elastic restart (resume the latest checkpoint onto whatever mesh exists).
Runs unchanged from 1 CPU device (examples/tests) to the production mesh.
"""
from __future__ import annotations

import dataclasses
import os
import time
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from ..checkpoint import CheckpointManager
from ..data import DataConfig, SyntheticLM
from ..models.config import ModelConfig
from ..models.params import abstract_params, init_params, partition_specs
from ..models.sharding import make_rules, sharding_context
from ..models.transformer import model_pspecs
from .optimizer import AdamWConfig
from .train_step import TrainConfig, init_train_state, make_train_step


@dataclasses.dataclass
class TrainerConfig:
    total_steps: int = 100
    ckpt_every: int = 0
    ckpt_dir: str = "/tmp/repro_ckpt"
    ckpt_keep: int = 3
    ckpt_async: bool = True
    log_every: int = 10
    seed: int = 0
    preemption_file: Optional[str] = None    # touch this file to request stop
    straggler_threshold: float = 2.0         # x median step time


class Trainer:
    def __init__(
        self,
        cfg: ModelConfig,
        tc: TrainConfig,
        trainer_cfg: TrainerConfig,
        mesh: Optional[Mesh] = None,
        fsdp: bool = True,
    ):
        self.cfg = cfg
        self.tc = tc
        self.c = trainer_cfg
        self.mesh = mesh
        self.rules = make_rules(mesh, fsdp=fsdp) if mesh is not None else None
        self.ckpt = (
            CheckpointManager(self.c.ckpt_dir, keep=self.c.ckpt_keep)
            if self.c.ckpt_every
            else None
        )
        self.metrics_log: List[Dict[str, float]] = []
        self.step_times: List[float] = []
        self.hooks: List[Callable[[int, Dict[str, float]], None]] = []

        pspecs = model_pspecs(cfg)
        if mesh is not None:
            specs = partition_specs(pspecs, self.rules)
            ns = lambda s: NamedSharding(mesh, s)
            self.param_shardings = jax.tree_util.tree_map(
                ns, specs, is_leaf=lambda x: isinstance(x, P)
            )
            opt_sh = {"m": self.param_shardings, "v": self.param_shardings}
            if cfg.param_dtype != "float32":
                opt_sh["master"] = self.param_shardings
            self.state_shardings = {
                "params": self.param_shardings,
                "opt": opt_sh,
                "step": ns(P()),
            }
        else:
            self.param_shardings = None
            self.state_shardings = None

        step_fn = make_train_step(cfg, tc)
        if mesh is not None:
            self._step = jax.jit(
                step_fn,
                in_shardings=(self.state_shardings, None),
                donate_argnums=(0,),
            )
        else:
            self._step = jax.jit(step_fn, donate_argnums=(0,))

    # -- state ------------------------------------------------------------------

    def init_state(self) -> Dict[str, Any]:
        """Fresh state, created where it lives: on a mesh each device draws
        only its own shard, so no device ever holds the whole model."""

        def init():
            params = init_params(jax.random.PRNGKey(self.c.seed), model_pspecs(self.cfg))
            return init_train_state(self.cfg, params)

        return jax.jit(init, out_shardings=self.state_shardings)()

    def restore_or_init(self) -> Dict[str, Any]:
        state = self.init_state()
        if self.ckpt and self.ckpt.latest_step() is not None:
            state, _ = self.ckpt.restore(state, shardings=self.state_shardings)
        return state

    # -- loop -------------------------------------------------------------------

    def run(self, state: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
        cfg, c = self.cfg, self.c
        state = state if state is not None else self.restore_or_init()
        start = int(jax.device_get(state["step"]))
        data = SyntheticLM(
            DataConfig(
                vocab_size=cfg.vocab_size,
                seq_len=256 if cfg.frontend == "none" else 64,
                global_batch=8,
                seed=c.seed,
                with_embeds=cfg.frontend != "none",
                d_model=cfg.d_model,
            )
        )

        ctx = (
            sharding_context(self.mesh, self.rules)
            if self.mesh is not None
            else _nullcontext()
        )
        with ctx:
            for step in range(start, c.total_steps):
                if c.preemption_file and os.path.exists(c.preemption_file):
                    # graceful preemption: checkpoint + stop
                    if self.ckpt:
                        self.ckpt.wait()
                        self.ckpt.save(step, state, extras={"preempted": True})
                    break
                batch = {k: jnp.asarray(v) for k, v in data.batch_at(step).items()}
                t0 = time.time()
                state, metrics = self._step(state, batch)
                metrics = {k: float(jax.device_get(v)) for k, v in metrics.items()}
                dt = time.time() - t0
                self.step_times.append(dt)
                metrics["step_time_s"] = dt
                self.metrics_log.append(metrics)
                for h in self.hooks:
                    h(step, metrics)
                if (
                    len(self.step_times) > 4
                    and dt > self.c.straggler_threshold * float(np.median(self.step_times))
                ):
                    metrics["straggler_flag"] = 1.0
                if c.log_every and step % c.log_every == 0:
                    print(
                        f"step {step:5d} loss {metrics['loss']:.4f} "
                        f"gnorm {metrics['grad_norm']:.3f} {dt*1e3:.0f}ms",
                        flush=True,
                    )
                if self.ckpt and c.ckpt_every and (step + 1) % c.ckpt_every == 0:
                    if c.ckpt_async:
                        self.ckpt.save_async(step + 1, state)
                    else:
                        self.ckpt.save(step + 1, state)
        if self.ckpt:
            self.ckpt.wait()
        return state


class _nullcontext:
    def __enter__(self):
        return None

    def __exit__(self, *a):
        return False
