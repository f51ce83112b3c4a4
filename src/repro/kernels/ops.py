"""jit'd public wrappers for the Pallas kernels.

Each wrapper auto-selects ``interpret=True`` off-TPU (Python emulation of
the kernel body — the CPU validation mode) and compiles to Mosaic on TPU.
The model substrate calls these via ``attention_impl="pallas"`` /
``PallasBackend``; tests sweep shapes/dtypes against kernels/ref.py.
"""

from __future__ import annotations

import jax

from . import flash_attention as _fa
from . import flash_decode as _fd
from . import matmul as _mm
from . import moe_gmm as _gmm
from . import paged_decode as _pd
from . import rmsnorm as _rms
from . import ssd_chunk as _ssd


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def matmul(x, y, **kw):
    kw.setdefault("interpret", _interpret())
    return _mm.matmul(x, y, **kw)


def rms_norm(x, weight, **kw):
    kw.setdefault("interpret", _interpret())
    return _rms.rms_norm(x, weight, **kw)


def flash_attention(q, k, v, **kw):
    """q: [B,S,H,D] model-layout -> kernel layout [B,H,S,D] with GQA
    expansion handled here."""
    kw.setdefault("interpret", _interpret())
    b, s, h, d = q.shape
    kvh = k.shape[2]
    if kvh != h:
        rep = h // kvh
        k = jax.numpy.repeat(k, rep, axis=2)
        v = jax.numpy.repeat(v, rep, axis=2)
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    out = _fa.flash_attention(qt, kt, vt, **kw)
    return out.transpose(0, 2, 1, 3)


def flash_decode(q, k, v, valid, **kw):
    kw.setdefault("interpret", _interpret())
    return _fd.flash_decode(q, k, v, valid, **kw)


def flash_verify(q, k, v, valid, **kw):
    kw.setdefault("interpret", _interpret())
    return _fd.flash_verify(q, k, v, valid, **kw)


def paged_decode(q, k_pool, v_pool, table, lengths, **kw):
    kw.setdefault("interpret", _interpret())
    return _pd.paged_decode(q, k_pool, v_pool, table, lengths, **kw)


def ssd_chunk(x, dt, A, B, C, **kw):
    kw.setdefault("interpret", _interpret())
    return _ssd.ssd_chunk(x, dt, A, B, C, **kw)


def moe_gmm(h, w, **kw):
    kw.setdefault("interpret", _interpret())
    return _gmm.moe_gmm(h, w, **kw)
