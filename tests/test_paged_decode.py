"""Paged decode attention read in place (``kernels/paged_decode.py``):
the kernel against the gathered-view reference in interpret mode, the
engine's greedy tokens on the kernel path against the gather path, and
the inputs that keep the gather path."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro.configs.base import get_config
from repro.kernels import ops as K
from repro.models import attention as attn
from repro.models import build_model
from repro.runtime import ServingPolicy
from repro.serving import BlockTable, Request, ServeEngine

NUM_BLOCKS = 600


def _table(lengths, block_size, max_blocks, rng, shared=None,
           num_blocks=NUM_BLOCKS):
    """Distinct random physical blocks per slot (0 = trash, never
    allocated); ``shared=(a, b, n)`` maps slot b's first n blocks onto
    slot a's, as prefix sharing does."""
    table = np.zeros((len(lengths), max_blocks), np.int32)
    free = rng.permutation(np.arange(1, num_blocks))
    for b, n in enumerate(lengths):
        if n is None:
            continue                              # idle: trash row
        k = -(-n // block_size)
        table[b, :k], free = free[:k], free[k:]
    if shared is not None:
        a, b, n = shared
        table[b, :n] = table[a, :n]
    return table


#: name -> (q heads, kv heads, head dim, block size, max blocks, lengths,
#: softcap, dtype, shared, pages per step); a length of None is an idle
#: slot whose row maps only the trash block (it decodes position 0)
KERNEL_CASES = {
    "ragged_1_boundary_full": (36, 4, 128, 16, 256, [1, 16, 4096, 700],
                               0.0, jnp.bfloat16, None, None),
    "idle_slot_trash_row": (36, 4, 128, 16, 256, [None, 300, 1], 0.0,
                            jnp.bfloat16, None, None),
    "shared_prefix_blocks": (36, 4, 128, 16, 256, [520, 777, 64], 0.0,
                             jnp.bfloat16, (0, 1, 20), None),
    "softcap": (36, 4, 128, 16, 256, [33, 1000], 30.0, jnp.bfloat16, None,
                None),
    "groups_across_slots": (36, 4, 128, 16, 64, [1, 17, 95, 1024, 64],
                            0.0, jnp.float32, None, 2),
    "tiny": (2, 1, 16, 4, 8, [1, 4, 9, 32], 5.0, jnp.float32, None, 3),
}


@pytest.mark.parametrize("case", KERNEL_CASES, ids=list(KERNEL_CASES))
def test_paged_decode_matches_gathered_view(case):
    h, kvh, d, bs, mb, lengths, cap, dtype, shared, pps = KERNEL_CASES[case]
    rng = np.random.default_rng(14)
    b = len(lengths)
    table = _table(lengths, bs, mb, rng, shared)
    lens = np.array([1 if n is None else n for n in lengths], np.int32)
    pool = (NUM_BLOCKS * bs, kvh, d)
    q, kp, vp = (jnp.asarray(rng.standard_normal(s), dtype)
                 for s in ((b, h, d), pool, pool))
    scale = 1.0 / np.sqrt(d)
    out = K.paged_decode(q, kp, vp, jnp.asarray(table), jnp.asarray(lens),
                         block_size=bs, scale=scale, cap=cap, pps=pps)
    k_view, v_view = attn._paged_gather({"k": kp, "v": vp},
                                        BlockTable(jnp.asarray(table), bs),
                                        dtype)
    ref = attn.plain_cache_attention(
        q, k_view, v_view,
        attn.decode_valid_mask(jnp.asarray(lens - 1), k_view.shape[1]),
        scale=scale, cap=cap)
    assert out.shape == ref.shape and out.dtype == ref.dtype
    tol = dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **tol)


def _tiny_decoder(**kw):
    kw = dict(n_kv_heads=2, head_dim=128) | kw
    cfg = get_config("codeqwen1.5-7b", reduced=True, n_layers=2, d_model=128,
                     n_heads=4, d_ff=128, vocab_size=64, **kw)
    model = build_model(cfg)
    return model, model.init(jax.random.PRNGKey(0))


PROMPTS = [[3, 1, 4, 1, 5], [9, 2], [5, 3, 5, 8, 9, 7, 2], [2, 7, 1, 8, 2, 8]]


def _serve(model, params, slots=3):
    pol = ServingPolicy(cache="paged", block_size=4, prefill_chunk=4)
    eng = ServeEngine(model, params, batch_slots=slots, max_seq=32,
                      policy=pol)
    eng.submit(Request(uid=0, prompt=PROMPTS[0], max_new_tokens=9))
    eng.submit(Request(uid=1, prompt=PROMPTS[1], max_new_tokens=3))
    eng.step()
    eng.step()
    for uid, p in enumerate(PROMPTS[2:], start=2):
        eng.submit(Request(uid=uid, prompt=p, max_new_tokens=7))
    done = {r.uid: r.generated for r in eng.run_until_done()}
    return done, eng


def test_engine_tokens_identical_on_kernel_and_gather_paths(monkeypatch):
    """Greedy tokens on the in-place kernel path (the backend predicate
    patched to the chip's answer, the kernel interpreted) equal those
    on the gather path, under staggered admissions, a finish that leaves
    a slot idle and a slot recycled."""
    model, params = _tiny_decoder()
    gathered, eng_g = _serve(model, params)
    calls = []
    real = K.paged_decode

    def spy(*a, **kw):
        calls.append(kw["block_size"])
        return real(*a, **kw)

    monkeypatch.setattr(K, "paged_decode", spy)
    monkeypatch.setattr(attn, "_tpu_backend", lambda: True)
    in_place, eng_k = _serve(model, params)
    assert eng_g.kv_path == "gather" and eng_k.kv_path == "paged_kernel"
    assert calls and set(calls) == {4}       # the decode program ran it
    assert eng_k.describe()["decode_kv_path"] == "paged_kernel"
    assert set(in_place) == {0, 1, 2, 3}
    assert in_place == gathered


#: case -> config overrides and block size of a case that is not the
#: f32 pool of 2 KV heads of 128 in blocks of 4
LAYOUT_CASES = {
    "small_head_dim": (dict(head_dim=64), 4),
    # 8 rows a page, half a bf16 sublane tile
    "unaligned_page": (dict(n_kv_heads=1, param_dtype=jnp.bfloat16,
                            compute_dtype=jnp.bfloat16), 8),
    # two lane tiles a position: the page view would copy the pool
    "pool_view_copies": (dict(head_dim=256), 4),
}


def _decode_case(case):
    """(cfg, block size, cache, window, attend_fn, session overrides) of
    one layer's decode for each input that selects a path."""
    kw, block_size = LAYOUT_CASES.get(case, ({}, 4))
    kw = dict(n_kv_heads=2, head_dim=128) | kw
    if case == "fp8_pool":
        kw["cache_dtype"] = "fp8"
    cfg = get_config("codeqwen1.5-7b", reduced=True, d_model=128, n_heads=4,
                     **kw)
    window = 8 if case == "window_layer" else 0
    spec = attn.gqa_cache_spec(cfg, 3, 32, window=window,
                               paged=(40, block_size))
    keys = jax.random.split(jax.random.PRNGKey(1), len(spec))
    cache = {n: jax.random.normal(k, m.shape).astype(m.dtype)
             for (n, m), k in zip(sorted(spec.items()), keys)}
    if "k_scale" in cache:
        cache["k_scale"] = jnp.abs(cache["k_scale"]) * 0.01
        cache["v_scale"] = jnp.abs(cache["v_scale"]) * 0.01
    attend_fn = attn.plain_cache_attention if case == "attend_fn" else None
    session = ({"kernels": {"decode_attention": attn.plain_cache_attention}}
               if case == "session_override" else {})
    return cfg, block_size, cache, window, attend_fn, session


#: case -> whether the kernel serves it
PATH_CASES = {"kernel": True, "fp8_pool": False, "attend_fn": False,
              "session_override": False, "window_layer": False,
              "small_head_dim": False, "unaligned_page": False,
              "pool_view_copies": False}


@pytest.mark.parametrize("case", PATH_CASES, ids=list(PATH_CASES))
def test_decode_path_follows_its_inputs(case, monkeypatch):
    """On the chip (predicate patched), only an unscaled paged pool
    whose pages the chip reads in place, with no attend override, takes
    the kernel; fp8 pools, an ``attend_fn``, a session
    ``decode_attention``, window ring buffers, small head dims, pages of
    part of a sublane tile and pools whose page view is a copy keep the
    gather path.  Both paths agree."""
    cfg, bs, cache, window, attend_fn, session = _decode_case(case)
    p = {n: 0.05 * jax.random.normal(jax.random.PRNGKey(i), m.shape)
         for i, (n, m) in enumerate(sorted(attn.gqa_meta(cfg).items()))}
    x = jax.random.normal(jax.random.PRNGKey(2), (3, 1, cfg.d_model))
    pos = jnp.asarray([0, 13, 30], jnp.int32)
    table = BlockTable(jnp.asarray(_table([1, 14, 31], bs, 32 // bs,
                                          np.random.default_rng(3),
                                          num_blocks=40)), bs)
    calls = []

    def spy(*a, **kw):
        calls.append(kw)
        return real(*a, **kw)

    def decode():
        with repro.session(**session):
            return attn.gqa_decode(p, cache, x, cfg, pos=pos, window=window,
                                   attend_fn=attend_fn, block_table=table)

    gathered, g_cache = decode()
    real = K.paged_decode
    monkeypatch.setattr(K, "paged_decode", spy)
    monkeypatch.setattr(attn, "_tpu_backend", lambda: True)
    out, new_cache = decode()
    assert bool(calls) == PATH_CASES[case]
    np.testing.assert_allclose(np.asarray(out), np.asarray(gathered),
                               rtol=2e-5, atol=2e-5)
    for name in cache:
        np.testing.assert_array_equal(np.asarray(new_cache[name]),
                                      np.asarray(g_cache[name]))


@pytest.mark.parametrize("case", ["fp8_pool", "decode_attention", "dense",
                                  "unaligned_page"])
def test_engine_reports_gather_path(case, monkeypatch):
    monkeypatch.setattr(attn, "_tpu_backend", lambda: True)
    kw = {"fp8_pool": {"cache_dtype": "fp8"},
          "unaligned_page": LAYOUT_CASES["unaligned_page"][0]}
    model, params = _tiny_decoder(**kw.get(case, {}))
    kernels = ({"decode_attention": attn.plain_cache_attention}
               if case == "decode_attention" else {})
    pol = ServingPolicy(cache="dense" if case == "dense" else "paged",
                        block_size=8 if case == "unaligned_page" else 4,
                        prefill_chunk=4)
    with repro.session(kernels=kernels):
        eng = ServeEngine(model, params, batch_slots=2, max_seq=32,
                          policy=pol)
    assert eng.kv_path == "gather"
    assert eng.describe()["decode_kv_path"] == "gather"
