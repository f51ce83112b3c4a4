"""repro.compiler: graph IR capture, pass pipeline, Pallas cluster
lowering, repro.compile numerics (hypothesis), telemetry exactly-once
frees after CSE, and Session provenance."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

import repro
from repro.compiler import (CompilerPolicy, Graph, PassManager, compile_graph,
                            trace)
from repro.core.memory import CachingMemoryManager, telemetry
from repro.core.tensor import ops
from repro.core.tensor.lazy_backend import LazyBackend


def _fresh_lazy():
    return LazyBackend()


# --------------------------------------------------------------------------
# IR capture
# --------------------------------------------------------------------------


def test_trace_captures_pending_subgraph():
    lb = _fresh_lazy()
    with repro.session(backend=lb):
        x = lb._lift(jnp.ones((8, 8)))
        y = ops.tanh(ops.add(ops.mul(x, x), x))
        g, sources = trace([y])
    assert g.validate() == []
    assert len(g.inputs) == 1 and len(g.outputs) == 1
    opset = {g.nodes[u].op for u in g.order}
    assert {"input", "mul", "add", "tanh"} <= opset
    text = g.dump()
    assert "graph(" in text and "tanh" in text and "return" in text
    # round-trip: the IR interpreter reproduces eager numerics
    (out,) = g.eval()
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(jnp.tanh(jnp.ones((8, 8)) * 2)))


def test_cse_merges_duplicate_subexpressions_and_aliases():
    lb = _fresh_lazy()
    with repro.session(backend=lb):
        x = lb._lift(jnp.linspace(0.1, 1.0, 16).reshape(4, 4))
        a1 = ops.exp(ops.mul(x, x))
        a2 = ops.exp(ops.mul(x, x))       # identical subexpression
        out = ops.add(a1, a2)
        g, _ = trace([out])
        n0 = len(g.order)
        report = PassManager.from_policy(CompilerPolicy()).run(g)
    by_name = {s.name: s for s in report}
    assert by_name["cse"].extra["merged"] == 2          # mul and exp dups
    assert len(g.order) == n0 - 2
    assert g.validate() == []
    # aliased outputs still resolve to surviving nodes
    assert all(g.resolve(o) in g.nodes for o in g.outputs)


def test_dce_removes_dead_branch_but_keeps_inputs():
    lb = _fresh_lazy()
    with repro.session(backend=lb):
        x = lb._lift(jnp.ones((4, 4)))
        live = ops.tanh(x)
        dead = ops.exp(ops.mul(x, ops.full_like(x, 2.0)))
        g, _ = trace([live, dead])
    g.outputs = g.outputs[:1]             # drop the dead branch
    stats = PassManager.from_policy(
        CompilerPolicy(pipeline=("dce",))).run(g)
    assert stats[0].extra["removed"] >= 2
    assert g.validate() == []
    assert all(i in g.nodes for i in g.inputs)


# --------------------------------------------------------------------------
# acceptance: the 16-op chain collapses to <= 2 cluster kernels
# --------------------------------------------------------------------------


def _chain(x, n=16):
    for _ in range(n):
        x = ops.mul(ops.add(x, x), ops.full_like(x, 0.5))
        x = ops.tanh(x)
    return x


def test_chain16_collapses_to_two_clusters_numerics_exact():
    x = jnp.linspace(-1.0, 1.0, 256 * 256).reshape(256, 256)
    eager = _chain(x)
    lb = _fresh_lazy()
    with repro.session(backend=lb):
        out = ops.materialize(_chain(x))
        report = lb.last_compile_report
    # legacy lazy path = one dispatch per op (64 compute nodes)
    legacy_lb = _fresh_lazy()
    with repro.session(backend=legacy_lb, compiler=CompilerPolicy.legacy()):
        out_legacy = ops.materialize(_chain(x))
        legacy_dispatches = legacy_lb.last_compile_report["dispatches"]
    assert report["dispatches"] <= 2 < legacy_dispatches
    assert 1 <= report["pallas_kernels"] <= 2
    assert legacy_dispatches >= 48
    np.testing.assert_array_equal(np.asarray(out), np.asarray(eager))
    np.testing.assert_array_equal(np.asarray(out_legacy), np.asarray(eager))


def test_program_cache_hits_on_identical_structure():
    x = jnp.ones((64, 64))
    lb = _fresh_lazy()
    with repro.session(backend=lb):
        ops.materialize(_chain(x, 4))
        assert lb.program_cache_hits == 0
        ops.materialize(_chain(x, 4))
        assert lb.program_cache_hits == 1


def test_cluster_internal_intermediates_recompute_on_demand():
    lb = _fresh_lazy()
    with repro.session(backend=lb):
        x = lb._lift(jnp.full((8, 8), 0.5))
        mid = ops.add(x, x)               # fused into the cluster interior
        out = ops.tanh(ops.mul(mid, mid))
        ops.materialize(out)
        assert out.value is not None
        np.testing.assert_allclose(np.asarray(ops.materialize(mid)),
                                   np.ones((8, 8)), rtol=1e-6)


# --------------------------------------------------------------------------
# satellite: telemetry frees exactly once per surviving node after CSE
# --------------------------------------------------------------------------


def test_telemetry_free_exactly_once_per_surviving_node_after_cse():
    lb = _fresh_lazy()
    with repro.session(backend=lb):
        t = telemetry.start_recording()
        x = lb._lift(jnp.ones((32, 32)))
        # two copies of the same consumer chain: CSE merges them, so the
        # shared producer's free must be emitted once, not per consumer
        a1 = ops.exp(ops.mul(x, x))
        a2 = ops.exp(ops.mul(x, x))
        out = ops.add(ops.tanh(a1), ops.tanh(a2))
        ops.materialize(out)
        trace_rec = telemetry.stop_recording()
    allocs = [e.uid for e in trace_rec.events if e.kind == "alloc"]
    frees = [e.uid for e in trace_rec.events if e.kind == "free"]
    assert len(allocs) == len(set(allocs)), "duplicate alloc uids"
    assert len(frees) == len(set(frees)), \
        "free emitted more than once for a node"
    assert set(frees) <= set(allocs)
    # CSE merged mul+exp+tanh dups: 7 logical -> 4 surviving compute nodes
    assert len(allocs) == 4
    assert len(frees) == 3                # all interior; root not freed
    # replay against the memory-manager interface: event counts must agree
    mgr = CachingMemoryManager(capacity=1 << 24)
    trace_rec.replay(mgr)
    assert mgr.stats.n_allocs == len(allocs)
    assert mgr.stats.live_allocated == 0


def test_telemetry_unchanged_semantics_without_cse_opportunities():
    lb = _fresh_lazy()
    with repro.session(backend=lb):
        t = telemetry.start_recording()
        a = ops.full((32, 32), 1.0)
        b = ops.exp(ops.mul(a, a))
        ops.materialize(b)
        rec = telemetry.stop_recording()
    allocs = [e for e in rec.events if e.kind == "alloc"]
    assert len(allocs) == 3
    assert {e.tag for e in allocs} == {"full", "mul", "exp"}


# --------------------------------------------------------------------------
# repro.compile decorator
# --------------------------------------------------------------------------


def test_compile_decorator_matches_eager_and_caches():
    @repro.compile
    def f(a, b):
        h = ops.mul(ops.add(a, b), ops.full_like(a, 0.25))
        return ops.sum(ops.tanh(h), axis=-1, keepdims=False)

    a = jax.random.normal(jax.random.PRNGKey(0), (8, 16))
    b = jax.random.normal(jax.random.PRNGKey(1), (8, 16))
    want = jnp.sum(jnp.tanh((a + b) * 0.25), axis=-1)
    got = f(a, b)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert f.trace_count == 1
    f(b, a)                                # same signature: cache hit
    assert f.trace_count == 1 and f.cache_size == 1
    f(a[:4], b[:4])                        # new shapes: retrace
    assert f.trace_count == 2 and f.cache_size == 2


def test_compile_policy_override_and_pytree_outputs():
    policy = CompilerPolicy.legacy()

    @repro.compile(policy=policy)
    def f(x):
        y = ops.neg(x)
        return {"pos": x, "neg": y, "both": (ops.add(x, y),)}

    x = jnp.arange(8.0)
    out = f(x)
    np.testing.assert_array_equal(np.asarray(out["neg"]), -np.arange(8.0))
    np.testing.assert_array_equal(np.asarray(out["both"][0]), np.zeros(8))
    assert f.last_executable.n_kernels == 0   # legacy: nothing generated


# --------------------------------------------------------------------------
# hypothesis: random graphs match eager bit-for-bit (f32) / tol (bf16)
# --------------------------------------------------------------------------

_UNARY = ["tanh", "neg", "abs", "sin", "cos"]
_BINARY = ["add", "sub", "mul", "maximum", "minimum"]
_SHAPE = (4, 8)


def _run_program(program, x, contraction_safe=True):
    """Interpret a random program over a value pool; later steps may
    reuse any earlier value (shared subexprs) and only the final value is
    returned (everything else is a dead branch).

    ``contraction_safe`` keeps the program free of ``mul``-feeds-
    ``add/sub`` patterns (tracked through ``neg``): inside a fused
    computation XLA's CPU/TPU backends legally contract those into FMAs,
    which changes the last ulp vs op-at-a-time eager execution.  Bitwise
    equality is only a meaningful guarantee for contraction-free graphs;
    the unrestricted family is covered by the 2-ulp test below.
    """
    pool = [x]
    from_mul = [False]
    for kind, i, j in program:
        ia, ib = i % len(pool), j % len(pool)
        a, b = pool[ia], pool[ib]
        m = False
        if kind < len(_UNARY):
            name = _UNARY[kind]
            v = getattr(ops, name)(a)
            m = name == "neg" and from_mul[ia]
        elif kind < len(_UNARY) + len(_BINARY):
            name = _BINARY[kind - len(_UNARY)]
            if (contraction_safe and name in ("add", "sub")
                    and (from_mul[ia] or from_mul[ib])):
                name = "maximum"
            v = getattr(ops, name)(a, b)
            m = name == "mul"
        elif kind == len(_UNARY) + len(_BINARY):
            r = ops.sum(a, axis=-1, keepdims=True)
            v = ops.broadcast_to(r, _SHAPE)
        else:
            v = ops.where(ops.ge(a, b), a, b)
        pool.append(v)
        from_mul.append(m)
    return pool[-1]


@settings(max_examples=25, deadline=None)
@given(program=st.lists(
    st.tuples(st.integers(0, len(_UNARY) + len(_BINARY) + 1),
              st.integers(0, 11), st.integers(0, 11)),
    min_size=1, max_size=12),
    seed=st.integers(0, 100))
def test_compiled_random_graphs_match_eager_f32_bitwise(program, seed):
    x = jax.random.normal(jax.random.PRNGKey(seed), _SHAPE, jnp.float32)
    eager = _run_program(program, x)
    compiled = repro.compile(lambda v: _run_program(program, v))
    got = compiled(x)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(eager))


@settings(max_examples=15, deadline=None)
@given(program=st.lists(
    st.tuples(st.integers(0, len(_UNARY) + len(_BINARY) + 1),
              st.integers(0, 11), st.integers(0, 11)),
    min_size=1, max_size=12),
    seed=st.integers(0, 100))
def test_compiled_unrestricted_graphs_within_two_ulp_f32(program, seed):
    """Unrestricted graphs: fused FMA contraction may flip the last ulp,
    never more (relative bound ~2 ulps of f32)."""
    x = jax.random.normal(jax.random.PRNGKey(seed), _SHAPE, jnp.float32)
    eager = np.asarray(_run_program(program, x, contraction_safe=False),
                       np.float64)
    compiled = repro.compile(
        lambda v: _run_program(program, v, contraction_safe=False))
    got = np.asarray(compiled(x), np.float64)
    np.testing.assert_allclose(got, eager, rtol=2.4e-7, atol=1e-37)


@settings(max_examples=10, deadline=None)
@given(program=st.lists(
    st.tuples(st.integers(0, len(_UNARY) + len(_BINARY) + 1),
              st.integers(0, 11), st.integers(0, 11)),
    min_size=1, max_size=8),
    seed=st.integers(0, 100))
def test_compiled_random_graphs_match_eager_bf16_tolerance(program, seed):
    x = jax.random.normal(jax.random.PRNGKey(seed), _SHAPE,
                          jnp.float32).astype(jnp.bfloat16)
    eager = np.asarray(_run_program(program, x), np.float32)
    compiled = repro.compile(lambda v: _run_program(program, v))
    got = np.asarray(compiled(x), np.float32)
    np.testing.assert_allclose(got, eager, rtol=2e-2, atol=1e-2)


# --------------------------------------------------------------------------
# policy plumbing + provenance
# --------------------------------------------------------------------------


def test_session_selects_pipeline_and_describe_records_stats():
    lb = _fresh_lazy()
    policy = CompilerPolicy(pipeline=("cse", "fuse"), lowering="jit")
    with repro.session(backend=lb, compiler=policy) as s:
        ops.materialize(_chain(jnp.ones((16, 16)), 4))
        desc = s.describe()
    comp = desc["compiler"]
    assert comp["pipeline"] == ["cse", "fuse"]
    assert comp["lowering"] == "jit"
    run = comp["last_run"]
    assert [p["pass"] for p in run["passes"]] == ["cse", "fuse"]
    assert run["pallas_kernels"] == 0      # jit lowering generates none
    assert all("nodes" in p and "edges" in p for p in run["passes"])
    import json
    json.dumps(desc)                       # provenance stays serializable


def test_session_compiler_dict_override():
    with repro.session(compiler={"pipeline": ("dce",), "lowering": "eager"}) \
            as s:
        assert s.compiler.pipeline == ("dce",)
        assert s.compiler.lowering == "eager"


def test_materialize_many_compiles_jointly():
    lb = _fresh_lazy()
    with repro.session(backend=lb):
        x = lb._lift(jnp.ones((8, 8)))
        shared = ops.tanh(ops.add(x, x))
        o1 = ops.mul(shared, shared)
        o2 = ops.add(shared, x)
        before = lb.materialize_calls
        v1, v2 = ops.materialize((o1, o2))
        assert lb.materialize_calls == before + 1
    np.testing.assert_allclose(np.asarray(v1),
                               np.tanh(2.0) ** 2 * np.ones((8, 8)), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(v2),
                               (np.tanh(2.0) + 1.0) * np.ones((8, 8)),
                               rtol=1e-6)


def test_materialize_namedtuple_preserves_type():
    import collections

    Out = collections.namedtuple("Out", ["a", "b"])
    lb = _fresh_lazy()
    with repro.session(backend=lb):
        x = lb._lift(jnp.ones((4, 4)))
        out = ops.materialize(Out(a=ops.tanh(x), b=ops.neg(x)))
    assert isinstance(out, Out)
    np.testing.assert_allclose(np.asarray(out.a), np.tanh(1.0) * np.ones((4, 4)),
                               rtol=1e-6)


def test_compile_mid_trace_materialized_values_not_cached():
    """Values computed eagerly during the trace (top_k materializes) are
    arg-dependent — replaying them from the cache would pin first-call
    results, so such calls must re-trace every time."""

    @repro.compile
    def f(x):
        v, _ = ops.top_k(x, 2)
        return ops.add(v, v)

    a = jnp.asarray([[1.0, 5.0, 3.0]])
    b = jnp.asarray([[9.0, 2.0, 7.0]])
    np.testing.assert_array_equal(np.asarray(f(a)), [[10.0, 6.0]])
    np.testing.assert_array_equal(np.asarray(f(b)), [[18.0, 14.0]])
    assert f.cache_size == 0 and f.trace_count == 2


def test_compile_array_kwarg_raises_clear_error():
    @repro.compile
    def f(x, scale=None):
        return ops.mul(x, scale)

    with pytest.raises(TypeError, match="positional"):
        f(jnp.ones((2, 2)), scale=jnp.full((2, 2), 3.0))


def test_describe_does_not_leak_other_sessions_pass_stats():
    # both sessions resolve "lazy" to the same registry singleton; B must
    # not report A's legacy-pipeline run as its own provenance
    with repro.session(backend="lazy", compiler=CompilerPolicy.legacy()):
        ops.materialize(_chain(jnp.ones((8, 8)), 2))
    with repro.session(backend="lazy") as s:
        s.backend_instance()               # resolve without materializing
        assert "last_run" not in s.describe()["compiler"]
        ops.materialize(_chain(jnp.ones((8, 8)), 2))
        assert "last_run" in s.describe()["compiler"]


def test_invalid_pass_name_raises():
    with pytest.raises(KeyError):
        PassManager.from_policy(CompilerPolicy(pipeline=("nope",)))


def test_selfcheck_default_pipeline_clean():
    from repro.compiler import selfcheck

    problems = selfcheck.run_corpus(
        pipelines=(("cse", "fold", "dce", "fuse"),))
    assert problems == []


@pytest.mark.parametrize("vmem_limit,block", [(16 << 20, 64), (64 << 10, 16),
                                              (1, None)])
def test_elementwise_cluster_row_block_follows_the_vmem_budget(vmem_limit,
                                                               block):
    """64 x 256 f32 with one input and one output, both double-buffered,
    takes 4 KiB per row: 16 MiB holds all 64 rows, 64 KiB holds 16, and
    one byte none (one whole-array step here, the jit fallback on TPU).
    The grid changes nothing in the values: compiled == eager bitwise."""
    from repro.kernels import cluster

    @repro.compile
    def f(x):
        return ops.tanh(ops.mul(ops.sin(x), x))

    x = jax.random.normal(jax.random.PRNGKey(3), (64, 256), jnp.float32)
    with repro.session(analysis={"vmem_limit_bytes": vmem_limit}):
        got = f(x)
    want = jnp.tanh(jnp.sin(x) * x)
    assert np.array_equal(np.asarray(got), np.asarray(want))
    lb = LazyBackend()
    with repro.session(backend=lb):
        out = f.fn(lb._lift(x))
    g, _ = trace([out])
    PassManager.from_policy(CompilerPolicy(pipeline=("fuse",))).run(g)
    (cl,) = g.clusters
    nodes = [g.nodes[u] for u in cl.node_ids]
    ins = [g.nodes[u] for u in cl.inputs]
    assert cluster.row_block(nodes, ins, 1, vmem_limit) == block
    assert cluster.pallas_supported(nodes, ins, 1, on_tpu=True,
                                    vmem_limit=vmem_limit) == (
        block is not None)
