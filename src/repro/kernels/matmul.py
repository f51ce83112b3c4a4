"""Tiled MXU matmul Pallas kernel.

The TPU-native adaptation of "swap the source of truth for a primitive op"
(paper §5.2.4): the :class:`PallasBackend` routes *every* ``matmul`` in the
framework through this kernel.

Tiling: (bm, bk) x (bk, bn) VMEM tiles; the MXU wants multiples of 128 on
the contracting/output dims, the VPU lane layout wants minor dim = 128.
Accumulation is fp32 in a VMEM scratch accumulator across the K grid axis
(the grid revisits the same output tile along k), cast to the output dtype
on the last K step.  Default tiles (128, 128, 128) use
3 * 128 * 128 * 4 B ≈ 192 KiB of VMEM — far under the ~16 MiB budget, so
callers can raise bm/bn for better MXU utilization on large shapes.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: VMEM budget a planned epilogue kernel may occupy (leaves headroom
#: under the ~16 MiB per-core budget).
_EPILOGUE_VMEM_LIMIT = 14 << 20


def _matmul_kernel(x_ref, y_ref, o_ref, acc_ref, *, n_k: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        x_ref[...], y_ref[...], preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def matmul(x: jax.Array, y: jax.Array, *, bm: int = 128, bn: int = 128,
           bk: int = 128, interpret: bool = False) -> jax.Array:
    """2-D tiled matmul: (M, K) @ (K, N) -> (M, N)."""
    m, k = x.shape
    k2, n = y.shape
    assert k == k2, (x.shape, y.shape)
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (
        f"shape ({m},{k})x({k},{n}) not divisible by tiles ({bm},{bn},{bk})")
    n_k = k // bk
    grid = (m // bm, n // bn, n_k)
    out_dtype = jnp.promote_types(x.dtype, y.dtype)
    return pl.pallas_call(
        functools.partial(_matmul_kernel, n_k=n_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(x, y)


# -- fused epilogues ---------------------------------------------------------


def plan_epilogue(*, m: int, k: int, n: int,
                  reductions: Sequence[tuple[Any, bool, int]],
                  extra_shapes: Sequence[tuple[int, ...]],
                  dtypes: Sequence[Any], on_tpu: bool,
                  vmem_limit: int = _EPILOGUE_VMEM_LIMIT
                  ) -> tuple[int, int, int] | None:
    """Validate an epilogue cluster against the fused kernel's contract
    and choose (bm, bn, bk) tiles; None means "don't claim".

    The epilogue body runs on one (bm, bn) output tile, so:

    * reductions must be keepdims over the last axis — and force
      ``bn == n`` (each tile must hold complete rows); ``axis=None``
      additionally forces ``bm == m`` (the whole matrix in one tile);
    * every extra operand must broadcast against a row/column tile:
      rank ≤ 2 with dims in {1, m} × {1, n} (rank-1 maps to columns);
    * the working set must fit VMEM; on TPU, shapes must be MXU/lane
      aligned and dtypes supported.
    """
    bm = 128 if m % 128 == 0 else m
    bn = 128 if n % 128 == 0 else n
    bk = 128 if k % 128 == 0 else k
    if reductions:
        bn = n
    for axis, keepdims, rank in reductions:
        if not keepdims or rank < 1:
            return None
        if axis is None:
            bm = m
        elif not isinstance(axis, int) or axis % rank != rank - 1:
            return None
    for s in extra_shapes:
        if len(s) == 0 or len(s) > 2:
            return None
        s2 = (1,) * (2 - len(s)) + tuple(s)
        if len(s) == 1 and s2[1] not in (1, n):
            return None
        if s2[0] not in (1, m) or s2[1] not in (1, n):
            return None
    vmem = 4 * (bm * bk + bk * bn + 2 * bm * bn)
    for s in extra_shapes:
        s2 = (1,) * (2 - len(s)) + tuple(s)
        vmem += 4 * ((bm if s2[0] == m else 1) * (bn if s2[1] == n else 1))
    if vmem > vmem_limit:
        return None
    if on_tpu:
        if m % 8 or n % 128 or k % 128:
            return None
        if any(jnp.dtype(d) not in (jnp.float32, jnp.bfloat16)
               for d in dtypes):
            return None
    return bm, bn, bk


def _bcast_spec(s: tuple[int, int], m: int, n: int, bm: int, bn: int
                ) -> pl.BlockSpec:
    """BlockSpec for a 2-D epilogue operand: tiled along the dims it shares
    with the (m, n) output, pinned to block 0 along broadcast dims."""
    rtile, ctile = s[0] == m, s[1] == n
    blk = (bm if rtile else 1, bn if ctile else 1)

    def imap(i, j, kk, _r=rtile, _c=ctile):
        return (i if _r else 0, j if _c else 0)

    return pl.BlockSpec(blk, imap)


def _epilogue_kernel(*refs, body: Callable, n_extra: int, n_k: int,
                     mm_dtype: Any):
    x_ref, y_ref = refs[0], refs[1]
    extra_refs = refs[2:2 + n_extra]
    o_ref = refs[2 + n_extra]
    acc_ref = refs[3 + n_extra]
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        x_ref[...], y_ref[...], preferred_element_type=jnp.float32)

    @pl.when(kk == n_k - 1)
    def _store():
        z = acc_ref[...].astype(mm_dtype)
        (out,) = body(z, *[r[...] for r in extra_refs])
        o_ref[...] = out.astype(o_ref.dtype)


def matmul_epilogue(body: Callable, *, m: int, k: int, n: int,
                    extra_shapes: Sequence[tuple[int, ...]],
                    out_dtype: Any, mm_dtype: Any, bm: int, bn: int,
                    bk: int, interpret: bool = False) -> Callable:
    """Tiled matmul with a synthesized epilogue fused at the store step.

    ``body(z, *extras)`` is the cluster's epilogue
    (:func:`repro.kernels.cluster.make_body` over the post-matmul
    members): it receives the (bm, bn) accumulator tile cast to the
    matmul's output dtype plus each extra operand's matching tile, and
    returns the single output tile.  Tiling must come from
    :func:`plan_epilogue` — it guarantees the per-tile replay is exact
    (reductions row-complete, operands broadcastable).
    """
    n_k = k // bk
    grid = (m // bm, n // bn, n_k)
    # rank-1 operands map to columns and travel as (1, len) rows: a rank-1
    # block must cover the whole array or a whole dtype-dependent tile on
    # TPU, while a (1, bn) block is legal for any dtype
    shapes2 = [(1,) * (2 - len(s)) + tuple(s) for s in extra_shapes]
    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
        pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
    ]
    for s2 in shapes2:
        in_specs.append(_bcast_spec(s2, m, n, bm, bn))
    call = pl.pallas_call(
        functools.partial(_epilogue_kernel, body=body,
                          n_extra=len(extra_shapes), n_k=n_k,
                          mm_dtype=mm_dtype),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )

    def run(x, y, *extras):
        return call(x, y, *[e.reshape(s2) for e, s2 in zip(extras, shapes2)])

    return jax.jit(run)
