"""Checkpointing (incl. elastic restore), fault tolerance, optimizers,
data pipeline, train loop."""

import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import optim
from repro.training.checkpoint import CheckpointManager
from repro.training.fault_tolerance import (HeartbeatTracker,
                                            StragglerMonitor,
                                            run_with_retries)


# ---------------------------------------------------------------- checkpoint

def _tree(seed=0):
    k = jax.random.PRNGKey(seed)
    return {"layer": {"w": jax.random.normal(k, (8, 4)),
                      "b": jnp.zeros((4,))},
            "stacked": [jnp.arange(6.0), jnp.ones((2, 3), jnp.bfloat16)]}


def test_checkpoint_roundtrip(tmp_path):
    mgr = CheckpointManager(tmp_path)
    tree = _tree()
    mgr.save(7, tree, extra={"step": 7, "note": "hello"})
    restored, extra = mgr.restore(jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree))
    assert extra["note"] == "hello"
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a, np.float32), np.asarray(b, np.float32)), tree,
        restored)


def test_checkpoint_retention_and_latest(tmp_path):
    mgr = CheckpointManager(tmp_path, keep=2)
    for s in (1, 2, 3, 4):
        mgr.save(s, {"x": jnp.full((2,), float(s))})
    assert mgr.all_steps() == [3, 4]
    assert mgr.latest_step() == 4


def test_checkpoint_async_and_shape_mismatch(tmp_path):
    mgr = CheckpointManager(tmp_path)
    mgr.save_async(1, {"x": jnp.ones((4,))})
    mgr.wait()
    with pytest.raises(ValueError, match="shape"):
        mgr.restore({"x": jax.ShapeDtypeStruct((5,), jnp.float32)})


def test_elastic_restore_onto_different_mesh(tmp_path):
    """Save unsharded, restore with explicit shardings (mesh 'resize')."""
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1,), ("data",))
    from jax.sharding import NamedSharding, PartitionSpec as P

    mgr = CheckpointManager(tmp_path)
    tree = {"w": jnp.arange(16.0).reshape(4, 4)}
    mgr.save(1, tree)
    sh = {"w": NamedSharding(mesh, P("data"))}
    restored, _ = mgr.restore(
        {"w": jax.ShapeDtypeStruct((4, 4), jnp.float32)}, shardings=sh)
    assert restored["w"].sharding == sh["w"]
    np.testing.assert_allclose(np.asarray(restored["w"]),
                               np.asarray(tree["w"]))


# ---------------------------------------------------------- fault tolerance

def test_straggler_monitor_flags_outliers():
    mon = StragglerMonitor(window=32, threshold=3.0)
    for i in range(40):
        assert not mon.record(i, 0.1 + 0.001 * (i % 3))
    assert mon.record(40, 1.5)          # 15x median
    assert mon.flagged and mon.flagged[-1][0] == 40


def test_straggler_monitor_degradation_triggers_checkpoint():
    mon = StragglerMonitor(degrade_patience=4)
    for i in range(20):
        mon.record(i, 0.1)
    for i in range(20, 24):
        mon.record(i, 2.0)
    assert mon.should_checkpoint_now()


def test_heartbeat_tracker():
    hb = HeartbeatTracker(world_size=4, timeout=10.0)
    now = 1000.0
    for r in range(4):
        hb.beat(r, now)
    assert hb.dead_ranks(now + 5) == []
    hb.beat(0, now + 20)
    assert hb.dead_ranks(now + 20) == [1, 2, 3]


def test_run_with_retries_recovers_from_injected_failures(tmp_path):
    """Supervisor restores from checkpoint after crashes; progress is
    monotone and final state correct."""
    mgr = CheckpointManager(tmp_path)
    crash_at = {17, 33}

    def save_fn(step, state):
        mgr.save(step, {"x": state}, extra={"step": step})

    def restore_fn():
        like = {"x": jax.ShapeDtypeStruct((), jnp.float32)}
        state, extra = mgr.restore(like)
        return int(extra["step"]), state["x"]

    def step_fn(step, state):
        if step in crash_at:
            crash_at.discard(step)      # fail once per site
            raise RuntimeError(f"injected failure @ {step}")
        return state + 1.0

    save_fn(0, jnp.float32(0.0))
    state, report = run_with_retries(step_fn, jnp.float32(0.0), 50,
                                     save_fn=save_fn,
                                     restore_fn=restore_fn,
                                     checkpoint_every=10)
    assert report["recovered"] == 2
    assert float(state) == 50.0         # every step ran exactly once


# ------------------------------------------------------------------ optim

def test_adam_matches_reference_formula():
    p = jnp.asarray([1.0, -2.0, 3.0])
    g = jnp.asarray([0.1, 0.2, -0.3])
    opt = optim.Adam(lr=0.1, b1=0.9, b2=0.999, eps=1e-8)
    s = opt._init_leaf(p)
    new_p, s = opt._update_leaf(p, g, s, 0.1, 1)
    m = 0.1 * np.asarray(g)
    v = 0.001 * np.asarray(g) ** 2
    mh, vh = m / 0.1, v / 0.001
    expect = np.asarray(p) - 0.1 * mh / (np.sqrt(vh) + 1e-8)
    np.testing.assert_allclose(np.asarray(new_p), expect, rtol=1e-6)


def test_adamw_decoupled_decay():
    p = jnp.ones((3,))
    g = jnp.zeros((3,))
    opt = optim.AdamW(lr=0.1, weight_decay=0.5)
    s = opt._init_leaf(p)
    new_p, _ = opt._update_leaf(p, g, s, 0.1, 1)
    np.testing.assert_allclose(np.asarray(new_p), 1.0 - 0.1 * 0.5,
                               rtol=1e-6)


@pytest.mark.parametrize("state_dtype", [None, jnp.bfloat16])
def test_adam_state_dtype_is_the_same_before_and_after_a_step(state_dtype):
    """A jitted train step compiles once only if the state it returns has
    the dtypes of the state it was given (bf16 params, f32 moments)."""
    p = {"w": jnp.ones((4, 8), jnp.bfloat16)}
    opt = optim.AdamW(lr=0.1, state_dtype=state_dtype)
    s0 = opt.init(p)
    _, s1 = opt.apply_with_count(p, p, s0, 0.1, 1)
    dtypes = [x.dtype for x in jax.tree.leaves(s0)]
    assert dtypes == [x.dtype for x in jax.tree.leaves(s1)]
    assert set(dtypes) == {jnp.dtype(state_dtype or jnp.float32)}


def test_clip_by_global_norm():
    grads = {"a": jnp.full((4,), 3.0), "b": jnp.full((4,), 4.0)}
    clipped, norm = optim.clip_by_global_norm(grads, 1.0)
    np.testing.assert_allclose(float(norm), 10.0, rtol=1e-6)
    total = np.sqrt(sum(float(jnp.sum(g ** 2))
                        for g in jax.tree.leaves(clipped)))
    np.testing.assert_allclose(total, 1.0, rtol=1e-5)


def test_schedules():
    sched = optim.cosine_schedule(1.0, warmup=10, total=110)
    assert float(sched(0)) == 0.0
    np.testing.assert_allclose(float(sched(10)), 1.0, rtol=1e-5)
    assert float(sched(110)) < 0.2
    lin = optim.linear_schedule(1.0, warmup=10, total=110)
    np.testing.assert_allclose(float(lin(60)), 0.5, rtol=1e-5)


def test_adafactor_shrinks_loss():
    k = jax.random.PRNGKey(0)
    w = jax.random.normal(k, (8, 8))
    x = jax.random.normal(jax.random.PRNGKey(1), (16, 8))
    opt = optim.Adafactor(lr=0.1)
    state = opt.init({"w": w})
    params = {"w": w}

    def loss(p):
        return jnp.mean((x @ p["w"]) ** 2)

    l0 = float(loss(params))
    for i in range(20):
        g = jax.grad(loss)(params)
        params, state = opt.apply_with_count(params, g, state, 0.1, i + 1)
    assert float(loss(params)) < l0 * 0.5


# -------------------------------------------------------------------- data

def test_data_pipeline_composition():
    from repro.core.data import (BatchDataset, MapDataset, PrefetchDataset,
                                 ShardDataset, ShuffleDataset, TensorDataset)

    xs = np.arange(100)
    ds = TensorDataset([xs])
    ds = MapDataset(ds, lambda s: (s[0] * 2,))
    shuf = ShuffleDataset(ds, seed=1)
    assert sorted(s[0] for s in shuf) == sorted(2 * xs)
    shard0 = ShardDataset(shuf, 0, 4)
    shard1 = ShardDataset(shuf, 1, 4)
    assert len(shard0) == len(shard1) == 25
    assert not set(s[0] for s in shard0) & set(s[0] for s in shard1)
    batched = BatchDataset(TensorDataset([xs]), 32)
    assert len(batched) == 3
    assert batched[0][0].shape == (32,)
    pre = PrefetchDataset(BatchDataset(TensorDataset([xs]), 10),
                          num_threads=3)
    got = [b[0] for b in pre]
    np.testing.assert_array_equal(np.concatenate(got), xs)


def test_lm_packing_and_tokenizer():
    from repro.core.data import ByteTokenizer, PackedLMDataset

    tok = ByteTokenizer()
    ids = tok.encode("hello")
    assert ids[0] == tok.BOS and ids[-1] == tok.EOS
    assert tok.decode(ids) == "hello"
    ds = PackedLMDataset(["abcdef" * 10, "xyz" * 20], seq_len=16)
    t, l = ds[0]
    assert t.shape == (16,) and l.shape == (16,)
    np.testing.assert_array_equal(t[1:], l[:-1])  # next-token labels


def test_grad_accumulation_equivalence():
    """accum=2 over a 2x batch == accum=1 small-batch average behavior."""
    from repro.configs.base import get_config
    from repro.core.optim import SGD
    from repro.models import build_model
    from repro.training.train_loop import TrainConfig, make_step_fn

    cfg = get_config("mamba2-370m", reduced=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    opt = SGD(lr=0.1)
    tok = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0,
                             cfg.vocab_size)
    batch = {"tokens": tok, "labels": jnp.roll(tok, -1, 1)}

    outs = {}
    for accum in (1, 2):
        tcfg = TrainConfig(steps=5, base_lr=0.1, warmup=0, accum=accum,
                           grad_clip=1e9)
        step = jax.jit(make_step_fn(model, opt, tcfg))
        p, s = params, opt.init(params)
        p, s, m = step(p, s, jnp.int32(1), batch)
        outs[accum] = p
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5),
        outs[1], outs[2])
