"""DistributedInterface (paper §4.1.3, A.4.1, Listing 5).

The open API for distributed-computation primitives.  Backends:

* :class:`EmulatedBackend` — in-process world for tests/CI;
* :class:`ShardMapBackend` — ``jax.lax`` collectives bound to a named mesh
  axis, for use *inside* ``shard_map``-traced training steps (explicit SPMD);
* the implicit GSPMD path (pjit shardings) lives in ``repro.launch`` and
  needs no instance of this interface — XLA inserts the collectives.

Unlike NCCL-style APIs, calls here are traceable JAX ops, so "async"
becomes overlap in the XLA schedule: ``allReduce(..., async_op=True)``
returns a handle whose ``.wait()`` is a scheduling barrier, letting
callers express compute/comm overlap (used by the bucketed gradient
synchronizer with compression in ``grad_sync.py``).
"""

from __future__ import annotations

import abc
from dataclasses import dataclass
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp


@dataclass
class Work:
    """Handle for an asynchronous collective (paper: ``async`` flag)."""

    _result: Any
    _finalize: Callable[[Any], Any] | None = None

    def wait(self) -> Any:
        out = self._result
        if self._finalize is not None:
            out = self._finalize(out)
            self._finalize = None
            self._result = out
        return out


class DistributedInterface(abc.ABC):
    """Paper Listing 5, adapted: tensors in/out, sync or async."""

    # -- metadata --------------------------------------------------------
    @abc.abstractmethod
    def getWorldRank(self) -> Any: ...  # noqa: N802 - paper-faithful names

    @abc.abstractmethod
    def getWorldSize(self) -> int: ...

    # -- collectives -----------------------------------------------------
    @abc.abstractmethod
    def allReduce(self, x, scale: float = 1.0, async_op: bool = False): ...

    def allReduceMultiple(self, xs: Sequence[Any], scale: float = 1.0,
                          async_op: bool = False):
        outs = [self.allReduce(x, scale, async_op) for x in xs]
        return outs

    @abc.abstractmethod
    def allGather(self, x, axis: int = 0): ...

    @abc.abstractmethod
    def reduceScatter(self, x, axis: int = 0): ...

    @abc.abstractmethod
    def allToAll(self, x, split_axis: int, concat_axis: int): ...

    @abc.abstractmethod
    def broadcast(self, x, root: int = 0): ...

    @abc.abstractmethod
    def permute(self, x, perm: Sequence[tuple[int, int]]): ...

    # -- synchronization ---------------------------------------------------
    def syncDistributed(self) -> None:  # noqa: N802
        """Flush pending async work (no-op where XLA schedules)."""

    def barrier(self) -> None:
        """Rendezvous; on a traced backend this is a data dependency."""


class EmulatedBackend(DistributedInterface):
    """Single-process world of size 1 (loopback) — CI/rendezvous default."""

    def __init__(self, rank: int = 0, world: int = 1):
        self._rank, self._world = rank, world

    def getWorldRank(self):
        return self._rank

    def getWorldSize(self):
        return self._world

    def allReduce(self, x, scale: float = 1.0, async_op: bool = False):
        out = x * scale * self._world if scale != 1.0 else x
        return Work(out) if async_op else out

    def allGather(self, x, axis: int = 0):
        return jnp.concatenate([x] * self._world, axis=axis)

    def reduceScatter(self, x, axis: int = 0):
        n = x.shape[axis] // self._world
        idx = [slice(None)] * x.ndim
        idx[axis] = slice(self._rank * n, (self._rank + 1) * n)
        return (x * self._world)[tuple(idx)]

    def allToAll(self, x, split_axis: int, concat_axis: int):
        return x

    def broadcast(self, x, root: int = 0):
        return x

    def permute(self, x, perm):
        return x


class ShardMapBackend(DistributedInterface):
    """jax.lax collectives over a named mesh axis (inside shard_map)."""

    def __init__(self, axis_name: str = "data"):
        self.axis_name = axis_name

    def getWorldRank(self):
        return jax.lax.axis_index(self.axis_name)

    def getWorldSize(self):
        return jax.lax.axis_size(self.axis_name)

    def allReduce(self, x, scale: float = 1.0, async_op: bool = False):
        def run(v):
            out = jax.lax.psum(v, self.axis_name)
            return out * scale if scale != 1.0 else out

        if async_op:
            # Defer the collective: XLA's latency-hiding scheduler overlaps
            # it with compute issued before .wait().
            return Work(x, run)
        return run(x)

    def allGather(self, x, axis: int = 0):
        return jax.lax.all_gather(x, self.axis_name, axis=axis, tiled=True)

    def reduceScatter(self, x, axis: int = 0):
        return jax.lax.psum_scatter(x, self.axis_name, scatter_dimension=axis,
                                    tiled=True)

    def allToAll(self, x, split_axis: int, concat_axis: int):
        return jax.lax.all_to_all(x, self.axis_name, split_axis=split_axis,
                                  concat_axis=concat_axis, tiled=True)

    def broadcast(self, x, root: int = 0):
        src = jax.lax.axis_index(self.axis_name) == root
        return jax.lax.psum(jnp.where(src, x, jnp.zeros_like(x)),
                            self.axis_name)

    def permute(self, x, perm):
        return jax.lax.ppermute(x, self.axis_name, perm)


_ACTIVE: DistributedInterface | None = None


def init_distributed(backend: DistributedInterface | str = "emulated",
                     **kw) -> DistributedInterface:
    """Rendezvous entry point (paper: 'specialized rendezvous schemes')."""
    global _ACTIVE
    if isinstance(backend, str):
        backend = {"emulated": EmulatedBackend,
                   "shard_map": ShardMapBackend}[backend](**kw)
    _ACTIVE = backend
    return backend


def get_distributed() -> DistributedInterface:
    global _ACTIVE
    if _ACTIVE is None:
        _ACTIVE = EmulatedBackend()
    return _ACTIVE
