"""Flash-decoding over sequence-sharded KV caches (SP for serving).

Why: MQA/MLA architectures have too few (or zero materialized) KV heads to
tensor-parallelize the cache over a 16-way model axis, and ``long_500k``
has batch=1 so batch sharding is unavailable too.  The scalable axis is
the cache *sequence*.  Under plain GSPMD, decode attention against a
seq-sharded cache all-gathers the cache (collective-bound); flash-decoding
instead computes partial softmax statistics (m, l, o) per sequence shard
inside ``shard_map`` and merges them with a pmax/psum combine — moving
O(S·d) gather traffic down to O(d) statistics traffic per step.
"""

from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.models.attention import partial_cache_attention


def make_flash_decode_attend(mesh: Mesh, *, seq_axes: Sequence[str],
                             batch_axes: Sequence[str] = ()):
    """Build an ``attend_fn(q, k, v, valid, scale, cap)`` closure.

    q: [B, H, Dk] (replicated over seq_axes);
    k: [B, S, Kv, Dk]; v: [B, S, Kv, Dv] (S sharded over seq_axes);
    valid: [S] bool (sharded like S), or [B, S] when slots decode at
    per-slot positions (continuous batching).

    Suitable as a session-level override:
    ``repro.session(kernels={"decode_attention": attend_fn})``.
    """
    seq_axes = tuple(seq_axes)
    batch_axes = tuple(batch_axes)
    bspec = batch_axes if len(batch_axes) != 1 else batch_axes[0]
    sspec = seq_axes if len(seq_axes) != 1 else seq_axes[0]

    def attend(q, k, v, valid, *, scale, cap: float = 0.0):
        def local(q_l, k_l, v_l, valid_l):
            m, l, o = partial_cache_attention(q_l, k_l, v_l, valid_l,
                                              scale=scale, cap=cap)
            gm = jax.lax.pmax(m, seq_axes)
            corr = jnp.exp(m - gm)
            l_g = jax.lax.psum(l * corr, seq_axes)
            o_g = jax.lax.psum(o * corr[..., None], seq_axes)
            out = o_g / jnp.maximum(l_g[..., None], 1e-30)
            b, kvh, g, dv = out.shape
            return out.reshape(b, kvh * g, dv).astype(q_l.dtype)

        valid_spec = P(bspec, sspec) if valid.ndim == 2 else P(sspec)
        return jax.shard_map(
            local, mesh=mesh,
            in_specs=(P(bspec, None, None),
                      P(bspec, sspec, None, None),
                      P(bspec, sspec, None, None),
                      valid_spec),
            out_specs=P(bspec, None, None),
            check_vma=False,
        )(q, k, v, valid)

    return attend
