"""Training loop: gradient accumulation, clipping, LR schedule, metrics,
checkpoint/restart, straggler monitoring.

Works at any scale: host mesh on CPU (examples/CI) or the production mesh
(via launch/train.py).  The step function is pjit'd with rule-derived
shardings; fault tolerance comes from CheckpointManager + the supervisor
hooks in fault_tolerance.py.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Iterator

import jax
import jax.numpy as jnp

from repro import obs
from repro.core.optim import AdamW, clip_by_global_norm, cosine_schedule
from repro.runtime import current_session
from repro.training.checkpoint import CheckpointManager
from repro.training.fault_tolerance import StragglerMonitor


@dataclass
class TrainConfig:
    steps: int = 100
    base_lr: float = 3e-4
    warmup: int = 10
    grad_clip: float = 1.0
    accum: int = 1                   # gradient accumulation microsteps
    checkpoint_every: int = 50
    checkpoint_dir: str | None = None
    log_every: int = 10


def make_step_fn(model, optimizer, tcfg: TrainConfig):
    schedule = cosine_schedule(tcfg.base_lr, tcfg.warmup, tcfg.steps)

    def step_fn(params, opt_state, step, batch):
        def loss_of(p, mb):
            loss, metrics = model.loss_fn(p, mb)
            return loss, metrics

        if tcfg.accum > 1:
            def micro(i, carry):
                gsum, lsum = carry
                mb = jax.tree.map(
                    lambda x: x.reshape(tcfg.accum, -1, *x.shape[1:])[i],
                    batch)
                (loss, _), g = jax.value_and_grad(loss_of,
                                                  has_aux=True)(params, mb)
                return (jax.tree.map(jnp.add, gsum, g), lsum + loss)

            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            grads, loss = jax.lax.fori_loop(
                0, tcfg.accum, micro, (zeros, jnp.zeros((), jnp.float32)))
            grads = jax.tree.map(lambda g: g / tcfg.accum, grads)
            loss = loss / tcfg.accum
            metrics = {}
        else:
            (loss, metrics), grads = jax.value_and_grad(
                loss_of, has_aux=True)(params, batch)
        grads, gnorm = clip_by_global_norm(grads, tcfg.grad_clip)
        lr = schedule(step)
        new_p, new_s = optimizer.apply_with_count(params, grads, opt_state,
                                                  lr, step + 1)
        metrics = dict(metrics, loss=loss, grad_norm=gnorm, lr=lr)
        return new_p, new_s, metrics

    return step_fn


def train(model, params, batches: Iterator[Any], tcfg: TrainConfig,
          optimizer=None, jit_kwargs: dict | None = None,
          log_fn: Callable[[str], None] = print):
    """Returns (params, history). Resumes from checkpoint_dir if present.

    Runs under the ambient runtime Session (mesh, backend, kernels …);
    its ``describe()`` snapshot is logged once for provenance so a
    history can always be tied back to the configuration it ran under.
    """
    sess = current_session()
    log_fn(f"[train] session {sess.describe()}")
    optimizer = optimizer or AdamW(lr=tcfg.base_lr)
    opt_state = optimizer.init(params)
    start_step = 0
    ckpt = None
    if tcfg.checkpoint_dir:
        ckpt = CheckpointManager(tcfg.checkpoint_dir)
        if ckpt.latest_step() is not None:
            (params, opt_state), extra = ckpt.restore((params, opt_state))
            start_step = int(extra.get("step", ckpt.latest_step()))
            log_fn(f"[train] resumed from step {start_step}")

    step_fn = jax.jit(make_step_fn(model, optimizer, tcfg),
                      donate_argnums=(0, 1), **(jit_kwargs or {}))
    monitor = StragglerMonitor()
    history: list[dict] = []
    step = start_step
    for batch in batches:
        if step >= tcfg.steps:
            break
        t0 = obs.now()
        params, opt_state, metrics = step_fn(
            params, opt_state, jnp.int32(step), batch)
        loss = float(metrics["loss"])
        dt = obs.now() - t0
        monitor.record(step, dt)
        history.append({"step": step, "loss": loss, "sec": dt})
        if step % tcfg.log_every == 0:
            log_fn(f"[train] step {step:5d} loss {loss:8.4f} "
                   f"({dt*1e3:6.1f} ms)")
        step += 1
        if ckpt and (step % tcfg.checkpoint_every == 0
                     or monitor.should_checkpoint_now()):
            ckpt.save_async(step, (params, opt_state), {"step": step})
    if ckpt:
        ckpt.save(step, (params, opt_state), {"step": step})
        ckpt.wait()
    return params, history
