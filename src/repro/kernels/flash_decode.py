"""Flash-decoding Pallas kernel: one query token vs a blocked KV cache.

The decode-side hot spot is *memory-bound* (stream the whole cache per
token), so the kernel's job is maximal HBM utilization: KV arrives in
(bk, d) VMEM tiles, partial (m, l, acc) statistics accumulate in scratch
across the k-grid axis, and the validity mask (cache length / ring
occupancy) streams alongside the cache — matching the shard-level math
in repro/serving/decode_attention.py (this kernel is the per-shard body;
the psum/pmax combine stays at the shard_map level).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0 ** 30


def _decode_kernel(q_ref, k_ref, v_ref, valid_ref, o_ref, m_scr, l_scr,
                   acc_scr, *, scale: float, n_kb: int):
    kb = pl.program_id(1)

    @pl.when(kb == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0]                                    # [1, d] row
    k = k_ref[0]                                    # [bk, d]
    valid = valid_ref[...].reshape(1, -1) != 0      # [1, bk]
    scores = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale  # [1, bk]
    scores = jnp.where(valid, scores, NEG_INF)
    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, scores.max(-1, keepdims=True))
    corr = jnp.exp(m_prev - m_new)
    p = jnp.where(valid, jnp.exp(scores - m_new), 0.0)
    l_scr[...] = l_scr[...] * corr + p.sum(-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
        p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(kb == n_kb - 1)
    def _store():
        o_ref[0] = (acc_scr[...]
                    / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def _verify_kernel(q_ref, k_ref, v_ref, valid_ref, o_ref, m_scr, l_scr,
                   acc_scr, *, scale: float, n_kb: int):
    kb = pl.program_id(1)

    @pl.when(kb == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0]                                    # [t, d]
    k = k_ref[0]                                    # [bk, d]
    valid = valid_ref[0]                            # [t, bk]
    scores = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale  # [t, bk]
    scores = jnp.where(valid, scores, NEG_INF)
    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, scores.max(-1, keepdims=True))
    corr = jnp.exp(m_prev - m_new)
    p = jnp.where(valid, jnp.exp(scores - m_new), 0.0)
    l_scr[...] = l_scr[...] * corr + p.sum(-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
        p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(kb == n_kb - 1)
    def _store():
        o_ref[0] = (acc_scr[...]
                    / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "bk", "interpret"))
def flash_verify(q, k, v, valid, *, scale: float | None = None,
                 bk: int = 512, interpret: bool = False):
    """Wide-verify flash decoding for speculative decoding: ``t`` query
    tokens per row against the same blocked KV cache.

    q: [N, T, D]; k, v: [N, S, D]; valid: [N, T, S] bool per row *and*
    per query position (causal within the verified span: query ``t``
    may see cache positions ``<= pos + t``) -> [N, T, D].

    ``flash_decode`` is the T=1 special case; the (m, l, acc) online-
    softmax statistics simply gain a leading T axis and the whole span
    shares each streamed KV tile.
    """
    n, t, d = q.shape
    s = k.shape[1]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    bk = min(bk, s)
    assert s % bk == 0, (s, bk)
    n_kb = s // bk
    grid = (n, n_kb)
    out = pl.pallas_call(
        functools.partial(_verify_kernel, scale=scale, n_kb=n_kb),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, t, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, bk, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, bk, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, t, bk), lambda i, j: (i, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, t, d), lambda i, j: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, t, d), v.dtype),
        scratch_shapes=[
            pltpu.VMEM((t, 1), jnp.float32),
            pltpu.VMEM((t, 1), jnp.float32),
            pltpu.VMEM((t, d), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, valid)
    return out


@functools.partial(jax.jit, static_argnames=("scale", "bk", "interpret"))
def flash_decode(q, k, v, valid, *, scale: float | None = None,
                 bk: int = 512, interpret: bool = False):
    """q: [N, D]; k, v: [N, S, D]; valid: [S] bool shared across rows, or
    [N, S] per-row (paged/continuous-batching caches where every slot
    sits at its own depth) -> [N, D].

    The mask streams as int32 with a unit dimension in front of ``S``, so
    its block's last two dims are (full 1, ``bk``): Mosaic's tiling rule
    for the sublane dimension, and bool's own VMEM layout, rule out a
    ``(1, bk)`` block of an ``[N, S]`` bool array.
    """
    n, d = q.shape
    s = k.shape[1]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    bk = min(bk, s)
    assert s % bk == 0, (s, bk)
    n_kb = s // bk
    grid = (n, n_kb)
    if valid.ndim == 2:
        valid = valid.astype(jnp.int32)[:, None, :]          # [N, 1, S]
        valid_spec = pl.BlockSpec((1, 1, bk), lambda i, j: (i, 0, j))
    else:
        valid = valid.astype(jnp.int32)[None, :]             # [1, S]
        valid_spec = pl.BlockSpec((1, bk), lambda i, j: (0, j))
    out = pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale, n_kb=n_kb),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, bk, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, bk, d), lambda i, j: (i, j, 0)),
            valid_spec,
        ],
        out_specs=pl.BlockSpec((1, 1, d), lambda i, j: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, 1, d), v.dtype),
        scratch_shapes=[
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, d), jnp.float32),
        ],
        interpret=interpret,
    )(q[:, None, :], k, v, valid)
    return out[:, 0, :]
