"""Attention variants: GQA/MQA/MHA (+ sliding window, softcap), and
DeepSeek MLA (multi-head latent attention) with matrix-absorbed decode.

Full-sequence paths (train/prefill) support ``attention_impl="pallas"``
(flash-attention kernel) or ``"ref"`` (masked-softmax oracle, also the
dry-run lowering path).  Decode paths produce *partial* (m, l, o) softmax
statistics so the serving layer can combine across sequence-sharded KV
caches (flash-decoding; see repro/serving/decode_attention.py).
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from .layers import apply_rope, linear, softcap
from .meta import ParamMeta

NEG_INF = -2.0 ** 30  # finite: keeps fully-masked rows NaN-free


def _session_kernels():
    from repro.runtime import current_session

    return current_session().kernels


# ===========================================================================
# masks
# ===========================================================================

def make_mask(q_pos: jax.Array, kv_pos: jax.Array, *, causal: bool = True,
              window: int = 0, prefix_len: int = 0) -> jax.Array:
    """[Sq, Sk] boolean mask. window>0 = sliding window; prefix positions
    (< prefix_len) are bidirectionally visible (PaLI-style prefix-LM)."""
    q = q_pos[:, None]
    k = kv_pos[None, :]
    m = (q >= k) if causal else jnp.ones((q_pos.shape[0], kv_pos.shape[0]),
                                         bool)
    if window > 0:
        m = m & (q - k < window)
    if prefix_len > 0:
        m = m | (k < prefix_len)
    return m


# ===========================================================================
# GQA
# ===========================================================================

def gqa_meta(cfg) -> dict[str, ParamMeta]:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    h, kv = cfg.n_heads, cfg.n_kv_heads
    dt = cfg.param_dtype
    return {
        "wq": ParamMeta((d, h * hd), ("embed", "heads"), dt, "fan_in"),
        "wk": ParamMeta((d, kv * hd), ("embed", "kv_heads"), dt, "fan_in"),
        "wv": ParamMeta((d, kv * hd), ("embed", "kv_heads"), dt, "fan_in"),
        "wo": ParamMeta((h * hd, d), ("heads", "embed"), dt, "fan_in"),
    }


def _qkv(p, x, cfg, positions):
    b, s, _ = x.shape
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    q = linear(x, p["wq"]).reshape(b, s, h, hd)
    k = linear(x, p["wk"]).reshape(b, s, kv, hd)
    v = linear(x, p["wv"]).reshape(b, s, kv, hd)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _sdpa_ref(q, k, v, mask, scale, cap: float = 0.0):
    """Reference grouped attention. q:[B,S,H,D] k/v:[B,S,Kv,D];
    mask: [Sq, Sk] shared, or [B, Sq, Sk] per-batch (chunked prefill at
    per-slot positions)."""
    b, sq, h, d = q.shape
    kvh = k.shape[2]
    g = h // kvh
    q = q.reshape(b, sq, kvh, g, d)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", q, k,
                        preferred_element_type=jnp.float32) * scale
    scores = softcap(scores, cap)
    m = mask[None, None, None] if mask.ndim == 2 else mask[:, None, None]
    scores = jnp.where(m, scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", w.astype(v.dtype), v)
    return out.reshape(b, sq, h, v.shape[-1])  # dv may differ from dk (MLA)


def _sdpa_blockwise(q, k, v, positions, *, causal, window, prefix_len,
                    scale, cap: float = 0.0, q_chunk: int = 1024):
    """Query-chunked attention: never materializes the [S, S] score matrix.

    Peak scores buffer is [B, Kv, G, q_chunk, S] instead of O(S²) — the
    memory-term optimization for long-sequence prefill (§Perf).  Exact
    (full softmax row per chunk; the key axis is never split).
    """
    b, s, h, d = q.shape
    while s % q_chunk != 0:
        q_chunk //= 2
    n = s // q_chunk
    qc = q.reshape(b, n, q_chunk, h, d).transpose(1, 0, 2, 3, 4)
    pc = positions.reshape(n, q_chunk)

    def one_chunk(args):
        qi, pi = args
        mask = make_mask(pi, positions, causal=causal, window=window,
                         prefix_len=prefix_len)
        return _sdpa_ref(qi, k, v, mask, scale, cap)

    out = jax.lax.map(one_chunk, (qc, pc))            # [n, B, qc, H, Dv]
    return out.transpose(1, 0, 2, 3, 4).reshape(b, s, h, v.shape[-1])


def gqa_attention(p, x, cfg, *, positions, window: int = 0,
                  prefix_len: int = 0, causal: bool = True) -> jax.Array:
    """Full-sequence attention (train/prefill)."""
    b, s, _ = x.shape
    q, k, v = _qkv(p, x, cfg, positions)
    scale = 1.0 / math.sqrt(cfg.resolved_head_dim)
    override = _session_kernels().attention
    if override is not None:
        out = override(q, k, v, positions=positions, causal=causal,
                       window=window, prefix_len=prefix_len, scale=scale,
                       cap=cfg.logit_softcap)
    elif cfg.attention_impl == "pallas" and jax.default_backend() == "tpu":
        from repro.kernels import ops as kops

        out = kops.flash_attention(q, k, v, causal=causal, window=window,
                                   scale=scale, softcap=cfg.logit_softcap)
    elif cfg.attention_impl == "blockwise":
        out = _sdpa_blockwise(q, k, v, positions, causal=causal,
                              window=window, prefix_len=prefix_len,
                              scale=scale, cap=cfg.logit_softcap)
    else:
        mask = make_mask(positions, positions, causal=causal, window=window,
                         prefix_len=prefix_len)
        out = _sdpa_ref(q, k, v, mask, scale, cfg.logit_softcap)
    return linear(out.reshape(b, s, -1), p["wo"])


# ===========================================================================
# quantized (fp8) cache storage
# ===========================================================================

FP8_MAX = 448.0  # float8_e4m3fn max normal


def quantize_kv(x, cache_dtype):
    """Quantize a K/V tensor for cache storage.

    fp8 caches store a per-position per-head scale (amax over the head
    dim / FP8_MAX) next to the values, so dequantized reads recover the
    full dynamic range — raw casts crush small-magnitude heads.  Returns
    ``(stored, scale)``; scale is None for non-fp8 cache dtypes.
    """
    if cache_dtype != jnp.float8_e4m3fn:
        return x.astype(cache_dtype), None
    x32 = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(x32), axis=-1)
    scale = jnp.maximum(amax / FP8_MAX, 1e-12)
    return (x32 / scale[..., None]).astype(cache_dtype), scale


def dequantize_kv(stored, scale, out_dtype):
    """Inverse of :func:`quantize_kv`; identity when scale is None."""
    if scale is None:
        return stored
    return (stored.astype(jnp.float32)
            * scale[..., None]).astype(out_dtype)


def gqa_cache_spec(cfg, batch: int, max_seq: int, window: int = 0,
                   paged=None):
    """Cache metas for one layer.  Window layers get per-slot ring
    buffers (always dense — they are already small and fixed-size).
    ``paged=(num_blocks, block_size)`` lays global-attention caches out
    as a shared block pool indexed through a block table; fp8 caches
    additionally carry per-position per-head scale planes.
    """
    kv, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    dt = cfg.resolved_cache_dtype
    fp8 = cfg.cache_dtype == "fp8"
    if paged is not None and window == 0:
        num_blocks, block_size = paged
        p = num_blocks * block_size
        spec = {
            "k": ParamMeta((p, kv, hd), (None, "kv_heads", None), dt,
                           "zeros"),
            "v": ParamMeta((p, kv, hd), (None, "kv_heads", None), dt,
                           "zeros"),
        }
        if fp8:
            spec["k_scale"] = ParamMeta((p, kv), (None, "kv_heads"),
                                        jnp.float32, "zeros")
            spec["v_scale"] = ParamMeta((p, kv), (None, "kv_heads"),
                                        jnp.float32, "zeros")
        return spec
    s = min(window, max_seq) if window > 0 else max_seq
    seq_ax = None if window > 0 else "seq_shard"
    spec = {
        "k": ParamMeta((batch, s, kv, hd),
                       ("batch", seq_ax, "kv_heads", None), dt, "zeros"),
        "v": ParamMeta((batch, s, kv, hd),
                       ("batch", seq_ax, "kv_heads", None), dt, "zeros"),
    }
    if fp8:
        spec["k_scale"] = ParamMeta((batch, s, kv),
                                    ("batch", seq_ax, "kv_heads"),
                                    jnp.float32, "zeros")
        spec["v_scale"] = ParamMeta((batch, s, kv),
                                    ("batch", seq_ax, "kv_heads"),
                                    jnp.float32, "zeros")
    return spec


def gqa_prefill(p, x, cfg, *, positions, window: int = 0, max_seq: int,
                prefix_len: int = 0):
    """Full-seq attention + build the decode cache."""
    b, s, _ = x.shape
    q, k, v = _qkv(p, x, cfg, positions)
    scale = 1.0 / math.sqrt(cfg.resolved_head_dim)
    mask = make_mask(positions, positions, window=window,
                     prefix_len=prefix_len)
    out = _sdpa_ref(q, k, v, mask, scale, cfg.logit_softcap)
    out = linear(out.reshape(b, s, -1), p["wo"])
    cache = _write_prefill_cache(k, v, cfg, window, max_seq)
    return out, cache


def _write_prefill_cache(k, v, cfg, window, max_seq):
    dt = cfg.resolved_cache_dtype
    k, k_scale = quantize_kv(k, dt)
    v, v_scale = quantize_kv(v, dt)
    b, s = k.shape[:2]

    def pack(arr):
        """Lay out one [B, S, ...] tensor as its cache-resident form."""
        trail = ((0, 0),) * (arr.ndim - 2)
        if window > 0:
            w = min(window, max_seq)
            if s >= w:
                # ring-buffer layout: slot i holds position p with
                # p % w == i, matching decode's `slot = pos % w`
                shift = (s - w) % w
                return jnp.roll(arr[:, -w:], shift, axis=1)
            return jnp.pad(arr, ((0, 0), (0, w - s)) + trail)
        return jnp.pad(arr, ((0, 0), (0, max_seq - s)) + trail)

    cache = {"k": pack(k), "v": pack(v)}
    if k_scale is not None:
        cache["k_scale"] = pack(k_scale)
        cache["v_scale"] = pack(v_scale)
    return cache


def _decode_positions(pos, b):
    """Normalize decode position(s): scalar -> (rope positions [1],
    per_slot=False); per-slot [B] array -> ([B, 1], per_slot=True)."""
    pos = jnp.asarray(pos, jnp.int32)
    if pos.ndim == 0:
        return pos, jnp.full((1,), pos, jnp.int32), False
    if pos.ndim != 1 or pos.shape[0] != b:
        raise ValueError(f"pos must be scalar or [batch]; got {pos.shape}")
    return pos, pos[:, None], True


def _batched_cache_update(cache, new, slot):
    """Write ``new`` [B, 1, ...] at a per-batch position ``slot`` [B]."""
    def upd(c, n, s):
        return jax.lax.dynamic_update_slice(c, n, (s,) + (0,) * (c.ndim - 1))

    return jax.vmap(upd)(cache, new, slot)


def _scalar_cache_update(cache, new, slot):
    """Write ``new`` [B, 1, ...] at one shared position ``slot``."""
    return jax.lax.dynamic_update_slice(
        cache, new, (0, slot) + (0,) * (cache.ndim - 2))


# -- paged (block-table) addressing -----------------------------------------
# The block table is duck-typed: anything with ``.table`` ([B, MB] int32
# physical block ids) and ``.block_size`` (static int) works — the real
# class lives in repro/serving/kv_cache.py to keep models import-light.

def _paged_write_index(block_table, pos):
    """Physical pool index for writing position ``pos`` [B] (or [B, T])."""
    bs = block_table.block_size
    blk = jnp.take_along_axis(block_table.table,
                              (pos // bs).reshape(pos.shape[0], -1),
                              axis=1).reshape(pos.shape)
    return blk * bs + pos % bs


def _paged_read_index(block_table):
    """[B, L] physical pool indices for the full logical view
    (L = max_blocks * block_size); unmapped blocks resolve to the
    reserved trash block and must be masked by validity."""
    bs = block_table.block_size
    mb = block_table.table.shape[1]
    l = jnp.arange(mb * bs, dtype=jnp.int32)
    return block_table.table[:, l // bs] * bs + (l % bs)[None, :]


def _paged_gather(cache, block_table, out_dtype):
    """Gather the logical [B, L, ...] K/V view through the block table,
    dequantizing fp8 pools on the way out."""
    idx = _paged_read_index(block_table)
    k = cache["k"][idx]
    v = cache["v"][idx]
    if "k_scale" in cache:
        k = dequantize_kv(k, cache["k_scale"][idx], out_dtype)
        v = dequantize_kv(v, cache["v_scale"][idx], out_dtype)
    return k, v


def _tpu_backend() -> bool:
    return jax.default_backend() == "tpu"


def paged_decode_path(cfg, block_size: int, attend, window: int = 0) -> str:
    """How decode reads one layer's paged pool: ``"paged_kernel"``
    (``kernels/paged_decode.py`` reads each slot's live blocks in place)
    or ``"gather"`` (a gathered logical view).  The kernel serves a
    global-attention layer on the TPU whose pool is unscaled and laid out
    so the chip can read its pages in place (``unfit_page_layout``), with
    no ``attend`` override (an override takes a logical view by
    contract).  ``gqa_decode`` and the serving engine both ask here."""
    if (window or attend is not None or cfg.cache_dtype == "fp8"
            or not _tpu_backend()):
        return "gather"
    from repro.kernels.paged_decode import unfit_page_layout

    if unfit_page_layout(block_size, cfg.n_kv_heads, cfg.resolved_head_dim,
                         cfg.resolved_cache_dtype):
        return "gather"
    return "paged_kernel"


def decode_valid_mask(pos, s_cache, window: int = 0):
    """Causal validity over cache slots: [S] for scalar ``pos``, [B, S]
    for a per-slot position vector.  Once a ring buffer has wrapped
    (``pos + 1 >= s_cache``) every slot holds a live entry."""
    idx = jnp.arange(s_cache)
    if jnp.ndim(pos) == 1:
        idx, pos = idx[None, :], pos[:, None]
    mask = idx <= pos
    if window > 0:
        mask = mask | (pos + 1 >= s_cache)
    return mask


def gqa_decode(p, cache, x, cfg, *, pos, window: int = 0, attend_fn=None,
               block_table=None):
    """One decode step. x: [B, 1, D]; pos: scalar position shared by the
    whole batch, or a [B] int vector of *per-slot* positions (continuous
    batching admits requests mid-flight, so slots decode at different
    depths).

    ``attend_fn(q, k, v, valid)`` lets the serving layer substitute a
    sequence-sharded flash-decoding implementation; when omitted, the
    session's ``kernels.decode_attention`` override applies (ring-buffer
    window caches stay local and always use plain cache attention).

    With ``block_table`` (paged serving), global-attention caches are
    block pools: the new K/V scatters through the table, then attention
    reads each slot's live blocks in place (``paged_decode_path``)
    or a gathered logical view, which keeps the attend interface.
    """
    b = x.shape[0]
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    pos, pos_arr, per_slot = _decode_positions(pos, b)
    q = linear(x, p["wq"]).reshape(b, 1, h, hd)
    k = linear(x, p["wk"]).reshape(b, 1, kv, hd)
    v = linear(x, p["wv"]).reshape(b, 1, kv, hd)
    q = apply_rope(q, pos_arr, cfg.rope_theta)[:, 0]          # [B, H, D]
    k = apply_rope(k, pos_arr, cfg.rope_theta)
    scaled = "k_scale" in cache
    if scaled:
        kq, k_sc = quantize_kv(k, cache["k"].dtype)
        vq, v_sc = quantize_kv(v, cache["v"].dtype)
    else:
        kq, vq = k.astype(cache["k"].dtype), v.astype(cache["v"].dtype)
    scale = 1.0 / math.sqrt(hd)
    attend = attend_fn
    if attend is None and window == 0:
        attend = _session_kernels().decode_attention
    new_cache = dict(cache)
    if block_table is not None and window == 0:
        pos_b = pos if per_slot else jnp.full((b,), pos, jnp.int32)
        phys = _paged_write_index(block_table, pos_b)          # [B]
        new_cache["k"] = cache["k"].at[phys].set(kq[:, 0])
        new_cache["v"] = cache["v"].at[phys].set(vq[:, 0])
        if scaled:
            new_cache["k_scale"] = cache["k_scale"].at[phys].set(k_sc[:, 0])
            new_cache["v_scale"] = cache["v_scale"].at[phys].set(v_sc[:, 0])
        if paged_decode_path(cfg, block_table.block_size,
                             attend) == "paged_kernel":
            from repro.kernels import ops as kops

            # the kernel reads the row written above back from the pool
            out = kops.paged_decode(
                q, new_cache["k"], new_cache["v"], block_table.table,
                pos_b + 1, block_size=block_table.block_size, scale=scale,
                cap=cfg.logit_softcap)
            return linear(out.reshape(b, 1, -1), p["wo"]), new_cache
        k_view, v_view = _paged_gather(new_cache, block_table, x.dtype)
        if scaled:
            # quantization is a storage effect only: the token being
            # decoded attends its own K/V exactly (it never left VMEM)
            k_view = _batched_cache_update(k_view, k.astype(x.dtype), pos_b)
            v_view = _batched_cache_update(v_view, v.astype(x.dtype), pos_b)
        valid = decode_valid_mask(pos_b, k_view.shape[1])
    else:
        s_cache = cache["k"].shape[1]
        slot = jnp.mod(pos, s_cache) if window > 0 else pos
        upd = _batched_cache_update if per_slot else _scalar_cache_update
        new_cache["k"] = upd(cache["k"], kq, slot)
        new_cache["v"] = upd(cache["v"], vq, slot)
        if scaled:
            new_cache["k_scale"] = upd(cache["k_scale"], k_sc, slot)
            new_cache["v_scale"] = upd(cache["v_scale"], v_sc, slot)
            k_view = dequantize_kv(new_cache["k"], new_cache["k_scale"],
                                   x.dtype)
            v_view = dequantize_kv(new_cache["v"], new_cache["v_scale"],
                                   x.dtype)
            k_view = upd(k_view, k.astype(x.dtype), slot)
            v_view = upd(v_view, v.astype(x.dtype), slot)
        else:
            k_view, v_view = new_cache["k"], new_cache["v"]
        valid = decode_valid_mask(pos, s_cache, window)
    out = (attend or plain_cache_attention)(q, k_view, v_view, valid,
                                            scale=scale,
                                            cap=cfg.logit_softcap)
    out = linear(out.reshape(b, 1, -1), p["wo"])
    return out, new_cache


def gqa_prefill_chunk(p, cache, x, cfg, *, positions, count,
                      window: int = 0, block_table=None):
    """Chunked batched prefill: consume a [B, T] chunk of prompt tokens
    in ONE call, writing K/V into the decode cache at per-slot positions
    and attending causally over cache-so-far + chunk.

    x: [B, T, D] chunk activations; positions: [B, T] int32 per-token
    absolute positions; count: [B] number of valid tokens this chunk
    (0 = slot not prefilling — its writes are dropped, its outputs are
    garbage the caller discards).  Replaces O(prompt_len) one-token
    decode calls per admission with O(prompt_len / T) chunk calls.
    """
    b, t, _ = x.shape
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    q = linear(x, p["wq"]).reshape(b, t, h, hd)
    k = linear(x, p["wk"]).reshape(b, t, kvh, hd)
    v = linear(x, p["wv"]).reshape(b, t, kvh, hd)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    scaled = "k_scale" in cache
    if scaled:
        kq, k_sc = quantize_kv(k, cache["k"].dtype)
        vq, v_sc = quantize_kv(v, cache["v"].dtype)
    else:
        kq, vq = k.astype(cache["k"].dtype), v.astype(cache["v"].dtype)
        k_sc = v_sc = None
    tok_valid = jnp.arange(t, dtype=jnp.int32)[None, :] < count[:, None]
    scale = 1.0 / math.sqrt(hd)
    new_cache = dict(cache)
    qpos = positions[:, :, None]                              # [B, T, 1]

    if window > 0:
        # Ring buffers: attend over (ring-as-of-chunk-start ++ chunk),
        # then write only the chunk tail that survives the window —
        # writing first would let late chunk tokens overwrite ring slots
        # early chunk queries still need.
        w = cache["k"].shape[1]
        i = jnp.arange(w, dtype=jnp.int32)[None, :]
        sm1 = positions[:, :1] - 1                            # start - 1
        ring_pos = sm1 - jnp.mod(sm1 - i, w)                  # [B, w]
        ring_ok = ring_pos >= 0
        if scaled:
            ring_k = dequantize_kv(cache["k"], cache["k_scale"], x.dtype)
            ring_v = dequantize_kv(cache["v"], cache["v_scale"], x.dtype)
            chunk_k = dequantize_kv(kq, k_sc, x.dtype)
            chunk_v = dequantize_kv(vq, v_sc, x.dtype)
        else:
            ring_k, ring_v = cache["k"], cache["v"]
            # round-trip chunk K/V through the cache dtype: chunk queries
            # see exactly what later decode steps will read back
            chunk_k, chunk_v = kq.astype(x.dtype), vq.astype(x.dtype)
        kp = positions[:, None, :]                            # [B, 1, T]
        ring_mask = (ring_ok[:, None, :] & (ring_pos[:, None, :] <= qpos)
                     & (qpos - ring_pos[:, None, :] < w))
        in_window = tok_valid[:, None, :] & (qpos - kp < w)
        keys = [ring_k.astype(x.dtype), chunk_k]
        vals = [ring_v.astype(x.dtype), chunk_v]
        if scaled:
            # cross-token reads see storage quantization; self is exact
            masks = [ring_mask, in_window & (kp < qpos),
                     tok_valid[:, None, :] & (kp == qpos)]
            keys.append(k.astype(x.dtype))
            vals.append(v.astype(x.dtype))
        else:
            masks = [ring_mask, in_window & (kp <= qpos)]
        out = _sdpa_ref(q, jnp.concatenate(keys, 1),
                        jnp.concatenate(vals, 1),
                        jnp.concatenate(masks, -1), scale,
                        cfg.logit_softcap)
        end = positions[:, :1] + count[:, None]               # [B, 1]
        keep = tok_valid & (positions >= end - w)
        widx = jnp.where(keep, jnp.mod(positions, w), w)      # w = dropped
        bidx = jnp.broadcast_to(jnp.arange(b)[:, None], (b, t))
        for key, newv in (("k", kq), ("v", vq),
                          ("k_scale", k_sc), ("v_scale", v_sc)):
            if key in cache:
                new_cache[key] = cache[key].at[bidx, widx].set(
                    newv, mode="drop")
        return linear(out.reshape(b, t, -1), p["wo"]), new_cache

    if block_table is not None:
        pool = cache["k"].shape[0]
        phys = _paged_write_index(block_table, positions)     # [B, T]
        phys = jnp.where(tok_valid, phys, pool)               # OOB = dropped
        for key, newv in (("k", kq), ("v", vq),
                          ("k_scale", k_sc), ("v_scale", v_sc)):
            if key in cache:
                new_cache[key] = cache[key].at[phys].set(newv, mode="drop")
        k_view, v_view = _paged_gather(new_cache, block_table, x.dtype)
    else:
        s_cache = cache["k"].shape[1]
        widx = jnp.where(tok_valid, positions, s_cache)       # OOB = dropped
        bidx = jnp.broadcast_to(jnp.arange(b)[:, None], (b, t))
        for key, newv in (("k", kq), ("v", vq),
                          ("k_scale", k_sc), ("v_scale", v_sc)):
            if key in cache:
                new_cache[key] = cache[key].at[bidx, widx].set(
                    newv, mode="drop")
        if scaled:
            k_view = dequantize_kv(new_cache["k"], new_cache["k_scale"],
                                   x.dtype)
            v_view = dequantize_kv(new_cache["v"], new_cache["v_scale"],
                                   x.dtype)
        else:
            k_view, v_view = new_cache["k"], new_cache["v"]
    kv_idx = jnp.arange(k_view.shape[1], dtype=jnp.int32)
    if scaled:
        # cross-token reads see storage quantization; self is exact
        kp = positions[:, None, :]                            # [B, 1, T]
        mask = jnp.concatenate(
            [kv_idx[None, None, :] < qpos,
             tok_valid[:, None, :] & (kp == qpos)], -1)
        k_view = jnp.concatenate([k_view, k.astype(x.dtype)], 1)
        v_view = jnp.concatenate([v_view, v.astype(x.dtype)], 1)
    else:
        mask = kv_idx[None, None, :] <= qpos                  # [B, T, S]
    out = _sdpa_ref(q, k_view, v_view, mask, scale, cfg.logit_softcap)
    return linear(out.reshape(b, t, -1), p["wo"]), new_cache


# ===========================================================================
# cache attention core (shared by GQA decode and MLA absorbed decode)
# ===========================================================================

def partial_cache_attention(q, k, v, valid, *, scale, cap: float = 0.0):
    """Partial softmax stats for flash-decoding combine.

    q: [B, H, Dk]; k: [B, S, Kv, Dk]; v: [B, S, Kv, Dv]; valid: [S] bool
    (shared across the batch) or [B, S] (per-slot decode depths).
    Caches may be stored quantized (fp8) — math upcasts to q's dtype.
    Returns m: [B, Kv, G], l: [B, Kv, G], o: [B, Kv, G, Dv].
    """
    b, h, dk = q.shape
    kvh = k.shape[2]
    g = h // kvh
    qg = q.reshape(b, kvh, g, dk)
    k = k.astype(q.dtype)
    v = v.astype(q.dtype)
    vmask = (valid if valid.ndim == 2 else valid[None])[:, None, None, :]
    scores = jnp.einsum("bkgd,bskd->bkgs", qg, k,
                        preferred_element_type=jnp.float32) * scale
    scores = softcap(scores, cap)
    scores = jnp.where(vmask, scores, NEG_INF)
    m = jnp.max(scores, axis=-1)                                 # [B,Kv,G]
    e = jnp.exp(scores - m[..., None])
    e = jnp.where(vmask, e, 0.0)
    l = jnp.sum(e, axis=-1)
    o = jnp.einsum("bkgs,bskd->bkgd", e.astype(v.dtype), v).astype(
        jnp.float32)
    return m, l, o


def plain_cache_attention(q, k, v, valid, *, scale, cap: float = 0.0):
    """Unsharded decode attention; returns [B, H, Dv] in q's dtype."""
    m, l, o = partial_cache_attention(q, k, v, valid, scale=scale, cap=cap)
    b, kvh, g, dv = o.shape
    out = o / jnp.maximum(l[..., None], 1e-30)
    return out.reshape(b, kvh * g, dv).astype(q.dtype)


# ===========================================================================
# MLA (DeepSeek multi-head latent attention)
# ===========================================================================

def mla_meta(cfg) -> dict[str, ParamMeta]:
    m = cfg.mla
    d, h = cfg.d_model, cfg.n_heads
    dt = cfg.param_dtype
    qk = m.qk_nope_dim + m.qk_rope_dim
    out: dict[str, ParamMeta] = {}
    if m.q_lora_rank:
        out["wq_a"] = ParamMeta((d, m.q_lora_rank), ("embed", None), dt,
                                "fan_in")
        out["q_norm"] = ParamMeta((m.q_lora_rank,), (None,), dt, "ones")
        out["wq_b"] = ParamMeta((m.q_lora_rank, h * qk), (None, "heads"), dt,
                                "fan_in")
    else:
        out["wq"] = ParamMeta((d, h * qk), ("embed", "heads"), dt, "fan_in")
    out["wkv_a"] = ParamMeta((d, m.kv_lora_rank + m.qk_rope_dim),
                             ("embed", None), dt, "fan_in")
    out["kv_norm"] = ParamMeta((m.kv_lora_rank,), (None,), dt, "ones")
    out["wkv_b"] = ParamMeta((m.kv_lora_rank,
                              h * (m.qk_nope_dim + m.v_head_dim)),
                             (None, "heads"), dt, "fan_in")
    out["wo"] = ParamMeta((h * m.v_head_dim, d), ("heads", "embed"), dt,
                          "fan_in")
    return out


def _rms(x, w, eps=1e-6):
    x32 = x.astype(jnp.float32)
    return (x32 * jax.lax.rsqrt((x32 * x32).mean(-1, keepdims=True) + eps)
            * w.astype(jnp.float32)).astype(x.dtype)


def _mla_q(p, x, cfg, positions):
    m = cfg.mla
    b, s, _ = x.shape
    h = cfg.n_heads
    qk = m.qk_nope_dim + m.qk_rope_dim
    if m.q_lora_rank:
        cq = _rms(linear(x, p["wq_a"]), p["q_norm"])
        q = linear(cq, p["wq_b"]).reshape(b, s, h, qk)
    else:
        q = linear(x, p["wq"]).reshape(b, s, h, qk)
    q_nope, q_rope = q[..., : m.qk_nope_dim], q[..., m.qk_nope_dim:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
    return q_nope, q_rope


def _mla_kv_latent(p, x, cfg, positions):
    m = cfg.mla
    kv_a = linear(x, p["wkv_a"])
    c_kv = _rms(kv_a[..., : m.kv_lora_rank], p["kv_norm"])  # [B,S,C]
    k_rope = kv_a[..., m.kv_lora_rank:][:, :, None, :]       # [B,S,1,R]
    k_rope = apply_rope(k_rope, positions, cfg.rope_theta)[:, :, 0]
    return c_kv, k_rope


def mla_attention(p, x, cfg, *, positions, causal: bool = True,
                  **_ignored) -> jax.Array:
    """Expanded (train/prefill) MLA: materialize per-head K/V."""
    m = cfg.mla
    b, s, _ = x.shape
    h = cfg.n_heads
    q_nope, q_rope = _mla_q(p, x, cfg, positions)
    c_kv, k_rope = _mla_kv_latent(p, x, cfg, positions)
    kv = linear(c_kv, p["wkv_b"]).reshape(b, s, h,
                                          m.qk_nope_dim + m.v_head_dim)
    k_nope, v = kv[..., : m.qk_nope_dim], kv[..., m.qk_nope_dim:]
    q = jnp.concatenate([q_nope, q_rope], -1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
                                  (b, s, h, m.qk_rope_dim))], -1)
    scale = 1.0 / math.sqrt(m.qk_nope_dim + m.qk_rope_dim)
    mask = make_mask(positions, positions, causal=causal)
    out = _sdpa_ref(q, k, v, mask, scale)
    return linear(out.reshape(b, s, -1), p["wo"])


def mla_cache_spec(cfg, batch: int, max_seq: int, window: int = 0,
                   paged=None):
    """MLA caches the *latent* (c_kv, k_rope) — the memory win of MLA."""
    if paged is not None:
        raise NotImplementedError(
            "paged KV cache is not implemented for MLA latent caches; "
            "serve MLA models with ServingPolicy(cache='dense')")
    m = cfg.mla
    dt = cfg.resolved_cache_dtype
    return {
        "c_kv": ParamMeta((batch, max_seq, m.kv_lora_rank),
                          ("batch", "seq_shard", None), dt, "zeros"),
        "k_rope": ParamMeta((batch, max_seq, m.qk_rope_dim),
                            ("batch", "seq_shard", None), dt, "zeros"),
    }


def mla_prefill(p, x, cfg, *, positions, max_seq: int, window: int = 0,
                prefix_len: int = 0):
    out = mla_attention(p, x, cfg, positions=positions)
    c_kv, k_rope = _mla_kv_latent(p, x, cfg, positions)
    c_kv = c_kv.astype(cfg.resolved_cache_dtype)
    k_rope = k_rope.astype(cfg.resolved_cache_dtype)
    s = x.shape[1]
    pad = max_seq - s
    cache = {"c_kv": jnp.pad(c_kv, ((0, 0), (0, pad), (0, 0))),
             "k_rope": jnp.pad(k_rope, ((0, 0), (0, pad), (0, 0)))}
    return out, cache


def mla_decode(p, cache, x, cfg, *, pos, window: int = 0, attend_fn=None,
               block_table=None):
    """Absorbed-matmul decode on the latent cache (DeepSeek-V2 appendix).

    Per head: score = q_nopeᵀ·W_uk·c + q_ropeᵀ·k_rope, so W_uk is folded
    into q once per step and attention runs in the compressed space — the
    cache is (kv_lora + rope) wide instead of heads×(nope+v).
    """
    if block_table is not None:
        raise NotImplementedError(
            "paged KV cache is not implemented for MLA decode")
    m = cfg.mla
    b = x.shape[0]
    h = cfg.n_heads
    pos, pos_arr, per_slot = _decode_positions(pos, b)
    q_nope, q_rope = _mla_q(p, x, cfg, pos_arr)
    q_nope, q_rope = q_nope[:, 0], q_rope[:, 0]              # [B,H,*]
    c_kv_new, k_rope_new = _mla_kv_latent(p, x, cfg, pos_arr)
    c_kv_new = c_kv_new.astype(cache["c_kv"].dtype)
    k_rope_new = k_rope_new.astype(cache["k_rope"].dtype)
    if per_slot:
        new_c = _batched_cache_update(cache["c_kv"], c_kv_new, pos)
        new_r = _batched_cache_update(cache["k_rope"], k_rope_new, pos)
    else:
        new_c = jax.lax.dynamic_update_slice(cache["c_kv"], c_kv_new,
                                             (0, pos, 0))
        new_r = jax.lax.dynamic_update_slice(cache["k_rope"], k_rope_new,
                                             (0, pos, 0))
    # absorb W_uk into q
    wkv_b = p["wkv_b"].reshape(m.kv_lora_rank, h, m.qk_nope_dim + m.v_head_dim)
    w_uk = wkv_b[..., : m.qk_nope_dim]                        # [C,H,N]
    w_uv = wkv_b[..., m.qk_nope_dim:]                         # [C,H,V]
    q_eff = jnp.einsum("bhn,chn->bhc", q_nope, w_uk)          # [B,H,C]
    q_cat = jnp.concatenate([q_eff, q_rope], -1)              # [B,H,C+R]
    kv_cat = jnp.concatenate([new_c, new_r], -1)[:, :, None, :]  # [B,S,1,C+R]
    vals = new_c[:, :, None, :]                               # [B,S,1,C]
    s_cache = new_c.shape[1]
    valid = decode_valid_mask(pos, s_cache)
    scale = 1.0 / math.sqrt(m.qk_nope_dim + m.qk_rope_dim)
    attend = (attend_fn or _session_kernels().decode_attention
              or plain_cache_attention)
    o_c = attend(q_cat, kv_cat, vals, valid, scale=scale)     # [B,H,C]
    o = jnp.einsum("bhc,chv->bhv", o_c.astype(jnp.float32),
                   w_uv.astype(jnp.float32)).astype(x.dtype)
    out = linear(o.reshape(b, 1, -1), p["wo"])
    return out, {"c_kv": new_c, "k_rope": new_r}


# ===========================================================================
# cross-attention (encoder-decoder)
# ===========================================================================

def cross_meta(cfg) -> dict[str, ParamMeta]:
    return gqa_meta(cfg)


def cross_attention(p, x, enc_kv, cfg) -> jax.Array:
    """x: [B,Sq,D]; enc_kv: dict with precomputed k,v [B,Sk,Kv,hd]."""
    b, sq, _ = x.shape
    h, hd = cfg.n_heads, cfg.resolved_head_dim
    q = linear(x, p["wq"]).reshape(b, sq, h, hd)
    k, v = enc_kv["k"], enc_kv["v"]
    sk = k.shape[1]
    mask = jnp.ones((sq, sk), bool)
    out = _sdpa_ref(q, k, v, mask, 1.0 / math.sqrt(hd))
    return linear(out.reshape(b, sq, -1), p["wo"])


def cross_kv(p, enc_out, cfg):
    b, sk, _ = enc_out.shape
    kv, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    return {"k": linear(enc_out, p["wk"]).reshape(b, sk, kv, hd),
            "v": linear(enc_out, p["wv"]).reshape(b, sk, kv, hd)}


def cross_decode(p, x, enc_kv, cfg, attend_fn=None):
    b = x.shape[0]
    h, hd = cfg.n_heads, cfg.resolved_head_dim
    q = linear(x, p["wq"]).reshape(b, h, hd)
    k, v = enc_kv["k"], enc_kv["v"]
    valid = jnp.ones((k.shape[1],), bool)
    attend = (attend_fn or _session_kernels().decode_attention
              or plain_cache_attention)
    out = attend(q, k, v, valid, scale=1.0 / math.sqrt(hd))
    return linear(out.reshape(b, 1, -1), p["wo"])
