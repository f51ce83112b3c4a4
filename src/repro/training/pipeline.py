"""Pipeline parallelism: GPipe-style microbatch schedule over a ``stage``
mesh axis with collective-permute handoff.

Used when depth exceeds what DP×TP can feed (≥ multi-pod scale); the
40-cell dry-run uses DP×TP×EP(+SP) which is the right fit for ≤512 chips,
so PP ships as a tested, composable feature rather than a default.

Implementation: ``shard_map`` over the ``stage`` axis; each stage holds
its own layer stack (params stacked on a leading stage axis).  The
schedule runs ``n_micro + n_stages - 1`` ticks; on each tick every stage
processes one microbatch and ``ppermute``s activations to its successor.
Bubble fraction = (n_stages - 1) / (n_micro + n_stages - 1).
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_apply(mesh: Mesh, stage_fn: Callable, stage_params,
                   x_micro: jax.Array, *, axis: str = "stage") -> jax.Array:
    """Run microbatches through pipeline stages.

    stage_fn(params, x) -> x : one stage's computation.
    stage_params: pytree with leading [n_stages] axis (sharded over
        ``axis``).
    x_micro: [n_micro, micro_batch, ...] activations.
    Returns [n_micro, micro_batch, ...] outputs (from the last stage).
    """
    n_stages = mesh.shape[axis]
    n_micro = x_micro.shape[0]
    n_ticks = n_micro + n_stages - 1

    def per_stage(params, xs):
        # params: this stage's slice (leading axis 1) ; xs: all microbatches
        params = jax.tree.map(lambda a: a[0], params)
        stage_id = jax.lax.axis_index(axis)
        buf = jnp.zeros_like(xs[0])
        outputs = jnp.zeros_like(xs)

        def tick(carry, t):
            buf, outputs = carry
            # stage 0 ingests microbatch t (when in range)
            mb_idx = jnp.clip(t, 0, n_micro - 1)
            incoming = jnp.where(stage_id == 0, xs[mb_idx], buf)
            active = (t - stage_id >= 0) & (t - stage_id < n_micro)
            y = stage_fn(params, incoming)
            y = jnp.where(active, y, incoming)
            # last stage writes its finished microbatch
            out_idx = jnp.clip(t - (n_stages - 1), 0, n_micro - 1)
            write = active & (stage_id == n_stages - 1)
            outputs = jax.lax.dynamic_update_index_in_dim(
                outputs, jnp.where(write, y, outputs[out_idx]), out_idx, 0)
            # hand off to the next stage
            perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
            buf = jax.lax.ppermute(y, axis, perm)
            return (buf, outputs), None

        (buf, outputs), _ = jax.lax.scan(tick, (buf, outputs),
                                         jnp.arange(n_ticks))
        # only the last stage holds real outputs; broadcast them
        outputs = jax.lax.psum(
            jnp.where(stage_id == n_stages - 1, outputs, 0.0), axis)
        return outputs

    spec_p = jax.tree.map(lambda _: P(axis), stage_params)
    return jax.shard_map(
        per_stage, mesh=mesh,
        in_specs=(spec_p, P()), out_specs=P(),
        check_vma=False,
    )(stage_params, x_micro)


def bubble_fraction(n_stages: int, n_micro: int) -> float:
    return (n_stages - 1) / (n_micro + n_stages - 1)
