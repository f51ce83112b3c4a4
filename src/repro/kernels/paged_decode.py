"""Paged decode attention: one query token per slot against a block pool,
read in place through the block table.

The serving engine keeps each global-attention layer's K and V as a
pool of fixed-size blocks (``[P, KV, D]`` with ``P = num_blocks *
block_size``) and maps every slot's logical positions onto it through a
``[B, max_blocks]`` block table.  Gathering the whole logical view to
attend over it reads every slot's ``max_blocks`` pages whatever its
depth; this kernel walks only the ``ceil(len / block_size)`` live pages
of each slot.

Layout: on the TPU the pool is tiled one position (``KV`` rows of ``D``
lanes) at a time, so the view ``[num_blocks, block_size * KV, D]`` is a
bitcast and one DMA brings a page of ``block_size * KV`` rows (row ``r``
is position ``r // KV``, KV head ``r % KV``).  Pages move in groups of
``pps``, double-buffered across groups *and* across slots: while group
``t`` is attended, group ``t + 1`` (the next group of this slot, or the
first of the next slot) is in flight.  Pages past a slot's last live
page issue no DMA.  Mosaic has no strided bf16 load to pick one head's
rows out of a page, so all ``H`` query rows attend all rows of the group
and a mask keeps each query head to its own KV head's rows; the
online-softmax statistics ``(m, l, acc)`` sit in f32 scratch as in
``flash_decode``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0 ** 30

#: positions one page group brings in: 16 pages of 16 at serving widths
STEP_POSITIONS = 256
#: VMEM the double-buffered K and V page groups may take together
BUFFER_BYTES = 8 << 20


def unfit_page_layout(block_size: int, kv: int, d: int,
                      dtype) -> str | None:
    """Why the TPU cannot read a ``[P, kv, d]`` pool of ``dtype`` in
    place, in pages of ``block_size`` positions, or None when it can.

    The chip tiles the pool per position, ``(min(kv, 8), 128)`` rows by
    lanes, so the page view ``[num_blocks, block_size * kv, d]`` is a
    bitcast only when ``d`` is one lane tile or each position's rows are
    whole 8-row tiles; otherwise the view is a copy of the whole pool.
    A page must also be whole sublane tiles of ``dtype``, or each DMA
    fills part of a tile and the page group's reshape is a relayout.
    """
    if d % 128:
        return f"head dim {d} is not whole 128-lane tiles"
    if d != 128 and kv % 8:
        return (f"a [P, {kv}, {d}] pool is tiled per position: the page "
                f"view [num_blocks, {block_size * kv}, {d}] is a copy of "
                "the pool, not a bitcast")
    sublane = 32 // jnp.dtype(dtype).itemsize
    if (block_size * kv) % sublane:
        return (f"a page of {block_size * kv} rows is not whole "
                f"({sublane}, 128) tiles of {jnp.dtype(dtype).name}")
    return None


def pages_per_step(block_size: int, row_bytes: int) -> int:
    """Pages fetched per step: about ``STEP_POSITIONS`` positions, fewer
    when four page groups (K and V, double-buffered) would pass
    ``BUFFER_BYTES``."""
    pps = max(1, STEP_POSITIONS // block_size)
    while pps > 1 and 4 * pps * block_size * row_bytes > BUFFER_BYTES:
        pps //= 2
    return pps


def _kernel(len_ref, tbl_ref, q_ref, k_hbm, v_hbm, o_ref, k_buf, v_buf,
            sems, m_scr, l_scr, acc_scr, *, block_size: int, kvh: int,
            pps: int, max_blocks: int, scale: float, cap: float):
    n_slots, h, _d = q_ref.shape
    g = h // kvh
    rows = pps * block_size * kvh          # K/V rows of one page group

    def n_pages(b):
        return (len_ref[b] + block_size - 1) // block_size

    def n_steps(b):
        return (n_pages(b) + pps - 1) // pps

    def group(b, step, buf, start: bool):
        """Start (or wait for) the DMAs of one slot's page group: its
        live pages only."""
        first = step * pps

        def page(i, carry):
            blk = tbl_ref[b * max_blocks + first + i]
            for src, dst, sem in ((k_hbm, k_buf, sems.at[0, buf]),
                                  (v_hbm, v_buf, sems.at[1, buf])):
                cp = pltpu.make_async_copy(src.at[blk], dst.at[buf, i], sem)
                if start:
                    cp.start()
                else:
                    cp.wait()
            return carry

        jax.lax.fori_loop(0, jnp.clip(n_pages(b) - first, 0, pps), page, 0)

    # Row r of a page group is position r // kvh, KV head r % kvh: query
    # row i (KV head i // g) may only see the rows of its own head.
    own_head = (jax.lax.broadcasted_iota(jnp.int32, (h, rows), 1) % kvh
                == jax.lax.broadcasted_iota(jnp.int32, (h, rows), 0) // g)

    def attend(b, step, buf):
        first, length = step * pps * block_size, len_ref[b]
        live = (first + jax.lax.broadcasted_iota(jnp.int32, (h, rows), 1)
                // kvh) < length
        valid = own_head & live                                   # [H, R]
        row_ok = (first + jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
                  // kvh) < length                                 # [R, 1]
        # attention runs in q's dtype, as plain_cache_attention's does
        k = k_buf[buf].reshape(rows, -1).astype(q_ref.dtype)
        # pages past the last live one were never fetched: whatever the
        # buffer holds there must not reach the output (0 * NaN)
        v = v_buf[buf].reshape(rows, -1).astype(q_ref.dtype)
        v = jnp.where(row_ok, v, jnp.zeros((), v.dtype))
        s = jax.lax.dot_general(
            q_ref[b], k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale           # [H, R]
        if cap > 0:
            s = jnp.tanh(s / cap) * cap
        s = jnp.where(valid, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        l_scr[...] = l_scr[...] * corr + p.sum(-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    def slot(b, t):
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)
        steps = n_steps(b)

        def step_fn(step, t):
            buf = t % 2
            last = step + 1 >= steps
            nb = jnp.where(last, b + 1, b)
            ns = jnp.where(last, 0, step + 1)

            @pl.when(nb < n_slots)
            def _():
                group(nb, ns, 1 - buf, start=True)

            group(b, step, buf, start=False)
            attend(b, step, buf)
            return t + 1

        t = jax.lax.fori_loop(0, steps, step_fn, t)
        o_ref[b] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
                    ).astype(o_ref.dtype)
        return t

    group(0, 0, 0, start=True)
    jax.lax.fori_loop(0, n_slots, slot, 0)


@functools.partial(jax.jit, static_argnames=("block_size", "scale", "cap",
                                             "pps", "interpret"))
def paged_decode(q, k_pool, v_pool, table, lengths, *, block_size: int,
                 scale: float, cap: float = 0.0, pps: int | None = None,
                 interpret: bool = False):
    """q: [B, H, D]; k_pool, v_pool: [P, KV, D] with ``P = num_blocks *
    block_size``; table: [B, max_blocks] int32 physical block ids;
    lengths: [B] int32 live positions per slot (``pos + 1``, at least 1
    and at most ``max_blocks * block_size``) -> [B, H, D] in q's dtype.

    ``cap`` is the logit softcap (0 = off), applied after ``scale``.
    """
    b, h, d = q.shape
    p, kvh, _ = k_pool.shape
    nb = p // block_size
    page = block_size * kvh
    if pps is None:
        pps = pages_per_step(block_size, kvh * d * k_pool.dtype.itemsize)
    whole = pl.BlockSpec((b, h, d), lambda i, *_: (0, 0, 0))
    out = pl.pallas_call(
        functools.partial(_kernel, block_size=block_size, kvh=kvh, pps=pps,
                          max_blocks=table.shape[1], scale=scale, cap=cap),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(1,),
            in_specs=[whole,
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=whole,
            scratch_shapes=[
                pltpu.VMEM((2, pps, page, d), k_pool.dtype),
                pltpu.VMEM((2, pps, page, d), v_pool.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((h, 1), jnp.float32),
                pltpu.VMEM((h, 1), jnp.float32),
                pltpu.VMEM((h, d), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((b, h, d), q.dtype),
        interpret=interpret,
    )(lengths.astype(jnp.int32), table.reshape(-1).astype(jnp.int32), q,
      k_pool.reshape(nb, page, d), v_pool.reshape(nb, page, d))
    return out
