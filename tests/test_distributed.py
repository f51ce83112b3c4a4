"""DistributedInterface backends, gradient compression w/ error feedback,
pipeline parallelism, serving engine."""

import json
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.distributed import (EmulatedBackend, GradientSynchronizer,
                                    GradSyncConfig, dequantize_int8,
                                    quantize_int8)


def test_emulated_backend_semantics():
    d = EmulatedBackend()
    x = jnp.arange(4.0)
    np.testing.assert_allclose(np.asarray(d.allReduce(x)), np.asarray(x))
    w = d.allReduce(x, async_op=True)
    np.testing.assert_allclose(np.asarray(w.wait()), np.asarray(x))
    assert d.getWorldSize() == 1


@settings(max_examples=30, deadline=None)
@given(seed=st.integers(0, 100), scale=st.floats(0.01, 100.0))
def test_int8_quantization_error_bound(seed, scale):
    """Property: |x - deq(q(x))| <= scale_step/2 elementwise."""
    x = jax.random.normal(jax.random.PRNGKey(seed), (64,)) * scale
    q, s = quantize_int8(x)
    deq = dequantize_int8(q, s, jnp.float32)
    assert float(jnp.max(jnp.abs(x - deq))) <= float(s) / 2 + 1e-6


def test_error_feedback_preserves_signal():
    """With error feedback, the *accumulated* compressed gradient tracks
    the true accumulated gradient (bias-free compression)."""
    sync = GradientSynchronizer(
        EmulatedBackend(),
        GradSyncConfig(compress="int8", error_feedback=True))
    g = {"w": jnp.asarray([1e-3, 2e-3, -5e-4, 1.0])}  # tiny + large entries
    state = sync.init_state(g)
    acc = np.zeros(4)
    for _ in range(64):
        out, state = sync(g, state, scale=1.0)
        acc += np.asarray(out["w"])
    true_acc = 64 * np.asarray(g["w"])
    np.testing.assert_allclose(acc, true_acc, rtol=0.05, atol=1e-3)


def test_no_compression_is_identity_on_loopback():
    sync = GradientSynchronizer(EmulatedBackend(), GradSyncConfig())
    g = {"a": jnp.arange(3.0), "b": jnp.ones((2, 2))}
    out, _ = sync(g, None, scale=1.0)
    jax.tree.map(lambda x, y: np.testing.assert_allclose(
        np.asarray(x), np.asarray(y)), g, out)


from conftest import REPO_ROOT as _REPO_ROOT, subproc_env as _subproc_env

_SUBPROC_COLLECTIVES = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np, json
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core.distributed import ShardMapBackend

    from repro.launch.mesh import make_mesh
    mesh = make_mesh((8,), ("data",))
    d = ShardMapBackend("data")
    x = jnp.arange(8.0)

    def body(xs):
        local = xs
        return (d.allReduce(local, scale=1.0/8),
                d.allGather(local),
                d.reduceScatter(d.allGather(local)))

    out = jax.shard_map(body, mesh=mesh, in_specs=P("data"),
                        out_specs=(P("data"), P("data"), P("data")),
                        check_vma=False)(x)
    ar, ag, rs = out
    res = {
      "ar": np.asarray(ar).tolist(),
      "rs": np.asarray(rs).tolist(),
    }
    print(json.dumps(res))
""")


def test_shard_map_backend_collectives_8dev():
    r = subprocess.run([sys.executable, "-c", _SUBPROC_COLLECTIVES],
                       capture_output=True, text=True,
                       env=_subproc_env(), timeout=300,
                       cwd=_REPO_ROOT)
    assert r.returncode == 0, r.stderr[-2000:]
    res = json.loads(r.stdout.strip().splitlines()[-1])
    # allReduce(mean): every element = mean(0..7) = 3.5
    np.testing.assert_allclose(res["ar"], [3.5] * 8)
    # reduceScatter(allGather(x)) = 8 * x
    np.testing.assert_allclose(res["rs"], (8 * np.arange(8.0)).tolist())


_SUBPROC_PIPELINE = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, jax.numpy as jnp, numpy as np, json
    from repro.training.pipeline import pipeline_apply

    from repro.launch.mesh import make_mesh
    mesh = make_mesh((4,), ("stage",))
    n_stages, n_micro, mb, d = 4, 8, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(0), n_stages)
    Ws = jnp.stack([jax.random.normal(k, (d, d)) * 0.3 for k in ks])

    def stage_fn(w, x):
        return jnp.tanh(x @ w)

    x = jax.random.normal(jax.random.PRNGKey(1), (n_micro, mb, d))
    out = pipeline_apply(mesh, stage_fn, Ws, x, axis="stage")
    # sequential reference
    ref = x
    for i in range(n_stages):
        ref = jnp.tanh(ref @ Ws[i])
    err = float(jnp.max(jnp.abs(out - ref)))
    print(json.dumps({"err": err}))
""")


def test_pipeline_parallel_equals_sequential_4dev():
    r = subprocess.run([sys.executable, "-c", _SUBPROC_PIPELINE],
                       capture_output=True, text=True,
                       env=_subproc_env(), timeout=300,
                       cwd=_REPO_ROOT)
    assert r.returncode == 0, r.stderr[-2000:]
    err = json.loads(r.stdout.strip().splitlines()[-1])["err"]
    assert err < 1e-5, err


def test_bubble_fraction():
    from repro.training.pipeline import bubble_fraction

    assert bubble_fraction(4, 8) == pytest.approx(3 / 11)
    assert bubble_fraction(1, 8) == 0.0


def test_serve_engine_greedy_matches_manual_decode():
    from repro.configs.base import get_config
    from repro.models import build_model
    from repro.serving.engine import Request, ServeEngine

    cfg = get_config("mamba2-370m", reduced=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prompt = [5, 9, 2]
    engine = ServeEngine(model, params, batch_slots=2, max_seq=32)
    engine.submit(Request(uid=1, prompt=prompt, max_new_tokens=6))
    done = engine.run_until_done()
    assert len(done) == 1 and len(done[0].generated) == 6

    # manual greedy decode (batch of 1 padded to the same slot count)
    cache = model.init_cache(2, 32)
    toks = prompt[:]
    for i, t in enumerate(prompt[:-1]):
        arr = jnp.zeros((2, 1), jnp.int32).at[0, 0].set(t)
        _, cache = model.decode_step(params, cache, arr, jnp.int32(i))
    cur = prompt[-1]
    out = []
    for i in range(6):
        arr = jnp.zeros((2, 1), jnp.int32).at[0, 0].set(cur)
        logits, cache = model.decode_step(params, cache, arr,
                                          jnp.int32(len(prompt) - 1 + i))
        cur = int(jnp.argmax(logits[0]))
        out.append(cur)
    assert out == done[0].generated


def test_serve_engine_multi_request_batching():
    from repro.configs.base import get_config
    from repro.models import build_model
    from repro.serving.engine import Request, ServeEngine

    cfg = get_config("codeqwen1.5-7b", reduced=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    engine = ServeEngine(model, params, batch_slots=2, max_seq=24)
    for uid in range(4):                      # more requests than slots
        engine.submit(Request(uid=uid, prompt=[1 + uid, 2, 3],
                              max_new_tokens=4))
    done = engine.run_until_done()
    assert sorted(r.uid for r in done) == [0, 1, 2, 3]
    assert all(len(r.generated) == 4 for r in done)
