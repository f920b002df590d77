"""Training loop with fault tolerance: auto-resume, async checkpoints,
deterministic skip-ahead data, and a step-time straggler watchdog.

The loop is mesh-agnostic: on restart the mesh may change shape (elastic
scaling) because checkpoints store logical arrays (see train/checkpoint.py);
`run_training` re-sharding-constrains everything it loads.
"""
from __future__ import annotations

import logging
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.data.pipeline import DataConfig, DataIterator
from repro.dist import sharding as shard
from repro.launch import steps as steps_mod
from repro.models import lm
from repro.models.config import ModelConfig
from repro.train import checkpoint as ckpt
from repro.train import optim

log = logging.getLogger("repro.train")


@dataclass
class TrainConfig:
    steps: int = 100
    log_every: int = 10
    ckpt_every: int = 50
    ckpt_dir: Optional[str] = None
    ckpt_keep: int = 3
    seed: int = 0
    straggler_factor: float = 3.0   # watchdog: step > factor x median -> warn
    optimizer: optim.AdamWConfig = field(default_factory=optim.AdamWConfig)


@dataclass
class TrainResult:
    final_step: int
    losses: Dict[int, float]
    restored_from: Optional[int]
    straggler_events: int


def _shard_batch(batch: Dict[str, np.ndarray], mesh: Mesh):
    spec = shard.batch_spec(mesh)
    bspec = spec[0] if len(spec) else None

    def put(x):
        ndim = x.ndim
        return jax.device_put(
            x, NamedSharding(mesh, P(*( [bspec] + [None] * (ndim - 1) ))))

    return {k: put(v) for k, v in batch.items()}


def run_training(cfg: ModelConfig, mesh: Mesh, tc: TrainConfig,
                 data_cfg: Optional[DataConfig] = None,
                 hooks: Optional[Dict[str, Callable]] = None) -> TrainResult:
    hooks = hooks or {}
    data_cfg = data_cfg or DataConfig(
        vocab_size=cfg.vocab_size, seq_len=256, global_batch=8,
        frontend=cfg.frontend, frontend_dim=cfg.frontend_dim,
        frontend_tokens=cfg.frontend_tokens, encdec=cfg.is_encdec,
        seed=tc.seed)

    with jax.set_mesh(mesh):
        params_abs = steps_mod.abstract_params(cfg, mesh)
        param_sh = jax.tree.map(lambda a: a.sharding, params_abs)
        key = jax.random.PRNGKey(tc.seed)
        params = jax.jit(
            lambda k: lm.init_params(k, cfg), out_shardings=param_sh)(key)
        opt_state = optim.init(params)

        restored_from = None
        if tc.ckpt_dir:
            last = ckpt.latest_step(tc.ckpt_dir)
            if last is not None:
                _, (params, opt_state), _ = ckpt.load(
                    tc.ckpt_dir, (params, opt_state), step=last)
                restored_from = last
                log.info("resumed from step %d", last)

        start_step = int(jax.device_get(opt_state.step))
        train_step = jax.jit(
            steps_mod.make_train_step(cfg, tc.optimizer),
            donate_argnums=(0, 1))

        it = DataIterator(data_cfg, start_step=start_step)  # skip-ahead
        saver = ckpt.AsyncCheckpointer(tc.ckpt_dir, keep=tc.ckpt_keep) \
            if tc.ckpt_dir else None

        losses: Dict[int, float] = {}
        step_times = []
        straggler_events = 0
        for step in range(start_step, tc.steps):
            batch = _shard_batch(next(it), mesh)
            t0 = time.time()
            params, opt_state, metrics = train_step(params, opt_state, batch)
            if "inject_fault" in hooks:
                hooks["inject_fault"](step)
            loss = float(jax.device_get(metrics["loss"]))
            dt = time.time() - t0
            step_times.append(dt)
            if len(step_times) > 5:
                median = float(np.median(step_times[-50:]))
                if dt > tc.straggler_factor * median:
                    straggler_events += 1
                    log.warning("straggler: step %d took %.3fs (median %.3fs)",
                                step, dt, median)
            if not np.isfinite(loss):
                raise FloatingPointError(f"non-finite loss at step {step}")
            if step % tc.log_every == 0 or step == tc.steps - 1:
                losses[step] = loss
                log.info("step %d loss %.4f (%.2fs)", step, loss, dt)
            if saver and (step + 1) % tc.ckpt_every == 0:
                saver.save(step + 1, (params, opt_state),
                           meta={"arch": cfg.name})
        if saver:
            saver.save(tc.steps, (params, opt_state), meta={"arch": cfg.name})
            saver.wait()
    return TrainResult(tc.steps, losses, restored_from, straggler_events)
