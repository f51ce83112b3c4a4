"""Sharding rules engine + cell-plan lowering (single-device mesh) +
multi-device semantics via subprocess (8 fake host devices)."""

import json
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.sharding.rules import ShardingRules, make_rules


def _mesh(shape, axes):
    from repro.launch.mesh import make_mesh
    return make_mesh(shape, axes)


def test_rules_basic_mapping_and_divisibility():
    mesh = _mesh((1, 1), ("data", "model"))
    rules = make_rules("baseline")
    # heads shard over model when divisible
    assert rules.spec((64, 128), ("embed", "heads"), mesh) == P(None, "model")
    # kv_heads=1 cannot shard over model=1? (divisible) -> use bigger mesh
    # via a fake mesh of 4:
    spec = rules.spec((4096, 3), ("embed", "kv_heads"), mesh)
    # size 3 % 1 == 0 on a unit mesh; semantics tested on 8-dev below
    assert spec in (P(None, "model"), P())


def test_rules_no_duplicate_mesh_axes():
    mesh = _mesh((1, 1), ("data", "model"))
    rules = ShardingRules(rules=(("a", "model"), ("b", "model")))
    spec = rules.spec((8, 8), ("a", "b"), mesh)
    parts = [p for p in spec if p is not None]
    assert len(parts) == len(set(parts))
    assert spec == P("model")  # second use dropped


def test_fsdp_rules_shard_embed_over_data():
    mesh = _mesh((1, 1), ("data", "model"))
    rules = make_rules("fsdp")
    assert rules.spec((1024, 512), ("embed", "mlp"), mesh) == \
        P("data", "model")


def test_cellplan_lowers_on_tiny_mesh():
    """The dry-run machinery end-to-end on a 1x1 mesh with reduced cfg."""
    from repro.configs.base import ShapeSpec, get_config
    from repro.launch.steps import BASELINE, CellPlan

    mesh = _mesh((1, 1), ("data", "model"))
    cfg = get_config("codeqwen1.5-7b", reduced=True)
    shape = ShapeSpec("tiny_train", 32, 4, "train")
    plan = CellPlan(cfg, shape, mesh, BASELINE)
    fn, args, in_sh, out_sh, donate = plan.lowerable()
    compiled = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                       donate_argnums=donate).lower(*args).compile()
    assert compiled.cost_analysis()["flops"] > 0

    shape_d = ShapeSpec("tiny_decode", 32, 4, "decode")
    plan_d = CellPlan(cfg, shape_d, mesh, BASELINE)
    fn, args, in_sh, out_sh, donate = plan_d.lowerable()
    compiled = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                       donate_argnums=donate).lower(*args).compile()
    assert compiled is not None


from conftest import REPO_ROOT as _REPO_ROOT, subproc_env as _subproc_env

_SUBPROC_FLASH_DECODE = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np, json
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.serving.decode_attention import make_flash_decode_attend
    from repro.models.attention import plain_cache_attention

    from repro.launch.mesh import make_mesh
    mesh = make_mesh((2, 4), ("data", "model"))
    B, H, KV, S, D = 4, 8, 2, 64, 16
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((B, H, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, S, KV, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, S, KV, D)), jnp.float32)
    valid = jnp.arange(S) < 50
    ref = plain_cache_attention(q, k, v, valid, scale=0.25)
    attend = make_flash_decode_attend(mesh, seq_axes=("model",),
                                      batch_axes=("data",))
    q_s = jax.device_put(q, NamedSharding(mesh, P("data")))
    k_s = jax.device_put(k, NamedSharding(mesh, P("data", "model")))
    v_s = jax.device_put(v, NamedSharding(mesh, P("data", "model")))
    val_s = jax.device_put(valid, NamedSharding(mesh, P("model")))
    out = jax.jit(lambda *a: attend(*a, scale=0.25))(q_s, k_s, v_s, val_s)
    err = float(jnp.max(jnp.abs(out - ref)))
    print(json.dumps({"err": err}))
""")


def test_flash_decode_sharded_matches_plain_8dev():
    """SP flash-decoding == unsharded attention, on a real 2x4 mesh."""
    r = subprocess.run([sys.executable, "-c", _SUBPROC_FLASH_DECODE],
                       capture_output=True, text=True,
                       env=_subproc_env(), timeout=300,
                       cwd=_REPO_ROOT)
    assert r.returncode == 0, r.stderr[-2000:]
    err = json.loads(r.stdout.strip().splitlines()[-1])["err"]
    assert err < 1e-4, err


_SUBPROC_TRAIN_SHARDED = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np, json
    from repro.configs.base import ShapeSpec, get_config
    from repro.launch.steps import BASELINE, CellPlan, Variant
    from repro.models.meta import tree_init
    from repro.sharding.context import active_mesh

    from repro.launch.mesh import make_mesh
    mesh = make_mesh((2, 4), ("data", "model"))
    cfg = get_config("jamba-v0.1-52b", reduced=True)
    shape = ShapeSpec("tiny_train", 32, 4, "train")
    out = {}
    for vname, variant in [("baseline", BASELINE),
                           ("fsdp", Variant(name="fsdp", sharding="fsdp"))]:
        plan = CellPlan(cfg, shape, mesh, variant)
        fn, args, in_sh, out_sh, donate = plan.lowerable()
        params = tree_init(plan.param_metas, jax.random.PRNGKey(0))
        params = jax.device_put(params, plan.param_shardings())
        opt_state = plan.optimizer.init(params)
        tok = jax.random.randint(jax.random.PRNGKey(1), (32, 32), 0,
                                 cfg.vocab_size)
        batch = {"tokens": tok, "labels": jnp.roll(tok, -1, 1)}
        with active_mesh(mesh):
            step = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                           donate_argnums=donate)
            p2, s2, metrics = step(params, opt_state, jnp.int32(0), batch)
        out[vname] = float(metrics["loss"])
    print(json.dumps(out))
""")


def test_sharded_train_step_runs_and_variants_agree_8dev():
    """A real sharded train step on 8 devices; fsdp == baseline loss."""
    r = subprocess.run([sys.executable, "-c", _SUBPROC_TRAIN_SHARDED],
                       capture_output=True, text=True,
                       env=_subproc_env(), timeout=300,
                       cwd=_REPO_ROOT)
    assert r.returncode == 0, r.stderr[-2000:]
    losses = json.loads(r.stdout.strip().splitlines()[-1])
    assert np.isfinite(losses["baseline"])
    np.testing.assert_allclose(losses["baseline"], losses["fsdp"],
                               rtol=1e-4)
