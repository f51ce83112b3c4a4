"""Per-kernel allclose vs kernels/ref.py oracles: shape/dtype sweeps in
interpret mode (CPU emulation of the TPU kernel body)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ops as K
from repro.kernels import ref as R

RNG = np.random.default_rng(7)


def _rand(shape, dtype):
    x = RNG.standard_normal(shape).astype(np.float32)
    return jnp.asarray(x, dtype)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("m,k,n,bm", [(128, 128, 128, 128),
                                      (256, 384, 128, 128),
                                      (512, 128, 256, 128),
                                      (64, 64, 64, 32)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_matmul_sweep(m, k, n, bm, dtype):
    x, y = _rand((m, k), dtype), _rand((k, n), dtype)
    out = K.matmul(x, y, bm=bm, bn=bm, bk=bm)
    np.testing.assert_allclose(
        np.asarray(out, np.float32),
        np.asarray(R.matmul(x, y), np.float32), **_tol(dtype))


@pytest.mark.parametrize("shape", [(8, 128), (4, 16, 256), (2, 3, 5, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_sweep(shape, dtype):
    x = _rand(shape, dtype)
    w = _rand(shape[-1:], jnp.float32)
    np.testing.assert_allclose(
        np.asarray(K.rms_norm(x, w), np.float32),
        np.asarray(R.rms_norm(x, w), np.float32), **_tol(dtype))


@settings(max_examples=8, deadline=None)
@given(n=st.sampled_from([16, 48, 160]), d=st.sampled_from([64, 128]),
       seed=st.integers(0, 10))
def test_rmsnorm_property(n, d, seed):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((n, d)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((d,)), jnp.float32)
    np.testing.assert_allclose(np.asarray(K.rms_norm(x, w)),
                               np.asarray(R.rms_norm(x, w)),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("s,d,causal,window",
                         [(128, 64, True, 0), (256, 64, True, 64),
                          (128, 128, False, 0), (256, 32, True, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(s, d, causal, window, dtype):
    b, h, kv = 2, 4, 2
    q = _rand((b, s, h, d), dtype)
    k = _rand((b, s, kv, d), dtype)
    v = _rand((b, s, kv, d), dtype)
    out = K.flash_attention(q, k, v, causal=causal, window=window,
                            bq=64, bk=64)
    kr = jnp.repeat(k, h // kv, 2).transpose(0, 2, 1, 3)
    vr = jnp.repeat(v, h // kv, 2).transpose(0, 2, 1, 3)
    ref = R.flash_attention(q.transpose(0, 2, 1, 3), kr, vr, causal=causal,
                            window=window).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


def test_flash_attention_softcap():
    b, s, h, d = 1, 64, 2, 32
    q, k, v = (_rand((b, s, h, d), jnp.float32) for _ in range(3))
    out = K.flash_attention(q, k, v, causal=True, softcap=20.0, bq=32, bk=32)
    ref = R.flash_attention(q.transpose(0, 2, 1, 3),
                            k.transpose(0, 2, 1, 3),
                            v.transpose(0, 2, 1, 3), causal=True,
                            softcap=20.0).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("s,d,valid_len,bk", [(256, 64, 100, 64),
                                              (512, 128, 512, 128),
                                              (128, 32, 1, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_decode_sweep(s, d, valid_len, bk, dtype):
    n = 6
    q = _rand((n, d), dtype)
    k = _rand((n, s, d), dtype)
    v = _rand((n, s, d), dtype)
    valid = jnp.arange(s) < valid_len
    out = K.flash_decode(q, k, v, valid, bk=bk)
    ref = R.flash_decode(q, k, v, valid)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


def test_flash_decode_per_row_valid():
    """Paged/continuous-batching path: every row carries its own valid
    mask (slots decode at different depths; gathered block-table views
    have per-slot lengths)."""
    n, s, d, bk = 5, 256, 64, 64
    q = _rand((n, d), jnp.float32)
    k = _rand((n, s, d), jnp.float32)
    v = _rand((n, s, d), jnp.float32)
    lens = jnp.asarray([1, 64, 100, 200, 256])
    valid = jnp.arange(s)[None, :] < lens[:, None]           # [N, S]
    out = K.flash_decode(q, k, v, valid, bk=bk)
    ref = R.flash_decode(q, k, v, valid)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               **_tol(jnp.float32))
    # per-row result must equal the shared-mask result row-by-row
    for i, ln in enumerate([1, 64, 100, 200, 256]):
        shared = K.flash_decode(q[i:i + 1], k[i:i + 1], v[i:i + 1],
                                jnp.arange(s) < ln, bk=bk)
        np.testing.assert_allclose(np.asarray(out[i:i + 1]),
                                   np.asarray(shared), **_tol(jnp.float32))


@pytest.mark.parametrize("s,t,d,bk", [(256, 5, 64, 64), (512, 3, 128, 128),
                                      (128, 1, 32, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_verify_sweep(s, t, d, bk, dtype):
    """Wide-verify: t query positions per row over a shared KV stream,
    per-(row, position) causal/ragged validity."""
    n = 4
    q = _rand((n, t, d), dtype)
    k = _rand((n, s, d), dtype)
    v = _rand((n, s, d), dtype)
    # row i starts at depth start_i; query j attends positions
    # <= start_i + j (the verify span's staircase mask)
    starts = jnp.asarray([1, 40, 100, s - t], jnp.int32)
    valid = (jnp.arange(s)[None, None, :]
             <= starts[:, None, None] + jnp.arange(t)[None, :, None])
    out = K.flash_verify(q, k, v, valid, bk=bk)
    ref = R.flash_verify(q, k, v, valid)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


def test_flash_verify_t1_matches_flash_decode():
    """flash_decode is the T=1 special case of flash_verify."""
    n, s, d = 5, 256, 64
    q = _rand((n, d), jnp.float32)
    k = _rand((n, s, d), jnp.float32)
    v = _rand((n, s, d), jnp.float32)
    lens = jnp.asarray([1, 64, 100, 200, 256])
    valid = jnp.arange(s)[None, :] < lens[:, None]
    wide = K.flash_verify(q[:, None, :], k, v, valid[:, None, :], bk=64)
    narrow = K.flash_decode(q, k, v, valid, bk=64)
    np.testing.assert_allclose(np.asarray(wide[:, 0]), np.asarray(narrow),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("q,p,n", [(32, 16, 24), (64, 32, 16), (16, 64, 128)])
def test_ssd_chunk_sweep(q, p, n):
    b, h, nc = 2, 3, 4
    rng = np.random.default_rng(q)
    x = jnp.asarray(rng.standard_normal((b, h, nc, q, p)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.5, (b, h, nc, q)), jnp.float32)
    A = -jnp.asarray(rng.uniform(0.5, 2.0, (h,)), jnp.float32)
    B = jnp.asarray(rng.standard_normal((b, nc, q, n)), jnp.float32)
    C = jnp.asarray(rng.standard_normal((b, nc, q, n)), jnp.float32)
    y, st_ = K.ssd_chunk(x, dt, A, B, C)
    yr, sr = R.ssd_chunk(x, dt, A, B, C)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), rtol=1e-4,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(st_), np.asarray(sr), rtol=1e-4,
                               atol=1e-5)


def test_ssd_chunked_full_equals_naive_recurrence():
    """The full chunked SSD (models/ssm.py) vs an O(S) step recurrence."""
    from repro.models.ssm import ssd_chunked

    b, s, h, p, n, chunk = 1, 64, 2, 8, 12, 16
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((b, s, h, p)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.5, (b, s, h)), jnp.float32)
    A = -jnp.asarray(rng.uniform(0.5, 2.0, (h,)), jnp.float32)
    B = jnp.asarray(rng.standard_normal((b, s, n)), jnp.float32)
    C = jnp.asarray(rng.standard_normal((b, s, n)), jnp.float32)
    y, hf = ssd_chunked(x, dt, A, B, C, chunk=chunk)

    # naive recurrence
    hstate = np.zeros((b, h, p, n))
    ys = np.zeros((b, s, h, p))
    xn, dtn, Bn, Cn = map(np.asarray, (x, dt, B, C))
    An = np.asarray(A)
    for t in range(s):
        decay = np.exp(dtn[:, t] * An[None])            # [b,h]
        hstate = hstate * decay[..., None, None] + np.einsum(
            "bh,bn,bhp->bhpn", dtn[:, t], Bn[:, t], xn[:, t])
        ys[:, t] = np.einsum("bn,bhpn->bhp", Cn[:, t], hstate)
    np.testing.assert_allclose(np.asarray(y), ys, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(hf), hstate, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("e,c,d,f", [(4, 64, 32, 48), (2, 128, 128, 128),
                                     (8, 32, 64, 16)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_moe_gmm_sweep(e, c, d, f, dtype):
    h = _rand((e, c, d), dtype)
    w = _rand((e, d, f), dtype)
    out = K.moe_gmm(h, w, bc=min(c, 32), bf=min(f, 16), bd=min(d, 16))
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(R.moe_gmm(h, w), np.float32),
                               **_tol(dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_erf_rational_matches_lax_erf(dtype):
    """Mosaic kernel bodies replace ``lax.erf`` with a rational
    approximation; it must agree to f32 rounding over the whole line."""
    from repro.kernels.cluster import _erf_rational

    x = jnp.linspace(-6.0, 6.0, 20001, dtype=jnp.float32).astype(dtype)
    got = _erf_rational(x)
    assert got.dtype == dtype
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(jax.lax.erf(x), np.float32),
                               atol=1e-6 if dtype == jnp.float32 else 8e-3)
