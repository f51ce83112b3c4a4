"""repro.analysis: the static verification layer.

Four fronts: (1) zero false positives — every selfcheck pipeline
permutation runs with the structured validator between passes and must
stay silent; (2) the mutation corpus — every seeded defect class flagged
by exactly its intended rule; (3) enforcement plumbing — AnalysisPolicy
levels through repro.compile(check=...), the Session, the lazy backend,
and the PassManager; (4) the serving audit over a *real* PagedKVCache.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro.analysis import (AnalysisError, AnalysisPolicy, Diagnostic,
                            DiagnosticReport, Severity, analyze_graph,
                            check_graph, check_kernel_call,
                            check_paged_cache, snapshot_cache)
from repro.analysis.mutations import MUTATIONS, run_mutations
from repro.compiler.passes import PassManager
from repro.compiler.selfcheck import CORPUS, PIPELINES, _build
from repro.core.tensor import ops
from repro.runtime import CompilerPolicy


# -- diagnostics primitives ---------------------------------------------------


def test_diagnostic_report_accounting():
    r = DiagnosticReport()
    r.add("shape.mismatch", Severity.ERROR, "bad", node=3, op="add")
    r.add("vmem.over-budget", Severity.WARNING, "big", cluster=1)
    r.add("tile.unaligned", Severity.INFO, "meh")
    assert r.rules == {"shape.mismatch", "vmem.over-budget", "tile.unaligned"}
    assert len(r.errors) == 1 and len(r.warnings) == 1
    assert r.max_severity() == Severity.ERROR
    assert r.counts() == {"INFO": 1, "WARNING": 1, "ERROR": 1}
    assert [d.rule for d in r.at_least(Severity.WARNING)] == [
        "shape.mismatch", "vmem.over-budget"]
    j = r.to_json()
    assert j["diagnostics"][0]["severity"] == "ERROR"
    assert "%3" in r.diagnostics[0].format()


def test_raise_if_errors_thresholds():
    r = DiagnosticReport()
    r.add("numerics.bf16-accum", Severity.WARNING, "accum")
    r.raise_if_errors(Severity.ERROR)          # warnings pass at default
    with pytest.raises(AnalysisError) as ei:
        r.raise_if_errors(Severity.WARNING, context="strict mode")
    assert "strict mode" in str(ei.value)
    assert ei.value.report.rules == {"numerics.bf16-accum"}


def test_analysis_policy_levels():
    assert AnalysisPolicy().enabled and not AnalysisPolicy().strict
    assert not AnalysisPolicy(level="off").enabled
    assert AnalysisPolicy(level="strict").error_threshold == Severity.WARNING
    assert AnalysisPolicy().error_threshold == Severity.ERROR
    with pytest.raises(ValueError):
        AnalysisPolicy(level="paranoid")


# -- front 1: zero false positives on the clean corpus ------------------------


@pytest.mark.parametrize("level", ["default", "strict"])
@pytest.mark.parametrize("pipeline", PIPELINES,
                         ids=["+".join(p) or "identity" for p in PIPELINES])
def test_clean_corpus_verifies_between_passes(pipeline, level):
    """Every selfcheck graph through every pipeline with the structured
    validator between passes: zero findings at WARNING or above."""
    apol = AnalysisPolicy(level=level)
    for gname in CORPUS:
        graph, _ = _build(gname)
        pm = PassManager.from_policy(CompilerPolicy(pipeline=pipeline))
        pm.run(graph, verify=apol)             # raises on any error
        report = analyze_graph(graph, apol, where=gname)
        loud = report.at_least(Severity.WARNING)
        assert not loud, (
            f"false positive on {gname}/{pipeline}@{level}: "
            + "; ".join(d.format() for d in loud))


def test_validate_delegates_to_structured_checker():
    """Graph.validate() keeps its list[str] contract but is now one view
    of check_graph — same findings, both directions."""
    g, _ = _build("shared_subexpr")
    assert g.validate() == []
    g.outputs = g.outputs + (10 ** 9,)
    legacy = g.validate()
    structured = check_graph(g, AnalysisPolicy(level="strict"))
    assert len(legacy) == len(structured) == 1
    assert structured.rules == {"graph.orphan-output"}
    assert legacy[0] == structured.diagnostics[0].format()


# -- front 2: the mutation corpus --------------------------------------------


@pytest.mark.parametrize("mutation", MUTATIONS, ids=lambda m: m.name)
def test_mutation_caught_by_exactly_its_rule(mutation):
    report = mutation.build()
    found = sorted({d.rule for d in report.at_least(Severity.WARNING)})
    assert mutation.rule in found, (
        f"seeded defect ({mutation.defect}) escaped: {found}")
    assert found == [mutation.rule], (
        f"rule cascade on {mutation.name}: expected exactly "
        f"{mutation.rule}, got {found}")


def test_mutation_runner_summary():
    results = run_mutations()
    assert len(results) == len(MUTATIONS)
    assert all(r["caught"] and r["exact"] for r in results)
    # the acceptance-critical defect classes are all represented
    rules = {r["rule"] for r in results}
    assert {"shape.mismatch", "alias.double-write", "tile.oob",
            "vmem.over-budget", "kv.leak", "kv.double-free"} <= rules


# -- front 3: enforcement plumbing -------------------------------------------


def _corrupting_pass_manager(pipeline=("cse",)):
    """A PassManager whose final pass corrupts a node's recorded shape."""

    class CorruptPass:
        name = "corrupt"

        def run(self, graph):
            for uid in reversed(graph.order):
                n = graph.nodes[uid]
                if n.op not in ("input", "const"):
                    n.shape = tuple(s + 1 for s in n.shape) or (7,)
                    break
            return {}

    pm = PassManager.from_policy(CompilerPolicy(pipeline=pipeline))
    pm.passes.append(CorruptPass())
    return pm


def test_pass_manager_verify_names_the_broken_pass():
    g, _ = _build("chain")
    pm = _corrupting_pass_manager()
    with pytest.raises(AnalysisError) as ei:
        pm.run(g, verify=AnalysisPolicy())
    assert "after pass 'corrupt'" in str(ei.value)
    assert ei.value.report.rules == {"shape.mismatch"}


def test_pass_manager_verify_off_is_silent():
    g, _ = _build("chain")
    _corrupting_pass_manager().run(g, verify=AnalysisPolicy(level="off"))


def test_compile_check_levels():
    def f(x):
        return ops.tanh(ops.add(x, x))

    x = jnp.ones((8, 8))
    strictf = repro.compile(f, check="strict")
    np.testing.assert_allclose(np.asarray(strictf(x)),
                               np.tanh(2 * np.ones((8, 8))), rtol=1e-6)
    assert strictf.last_executable.diagnostics is not None
    assert not strictf.last_executable.diagnostics.at_least(Severity.WARNING)
    with pytest.raises(ValueError):
        repro.compile(f, check="paranoid")


def test_compile_check_strict_promotes_warnings():
    """bf16 accumulation is a WARNING: default compiles, strict raises."""

    def accum(x):
        return ops.sum(ops.mul(x, x), axis=None, keepdims=False)

    x = jnp.ones((32, 32), jnp.bfloat16)
    out = repro.compile(accum, check="default")(x)
    assert jnp.dtype(out.dtype) == jnp.dtype(jnp.bfloat16)
    with pytest.raises(AnalysisError) as ei:
        repro.compile(accum, check="strict")(x)
    assert "numerics.bf16-accum" in ei.value.report.rules


def test_session_analysis_reaches_lazy_backend():
    """The session-scoped AnalysisPolicy governs every materialization;
    the backend exposes the report as provenance."""
    with repro.session(backend="lazy",
                       analysis={"level": "default"}) as s:
        lb = s.backend_instance()
        y = ops.mul(ops.add(jnp.ones((4, 4)), 1.0), 2.0)
        ops.materialize(y)
        assert lb.last_analysis is not None
        assert not lb.last_analysis.at_least(Severity.WARNING)
    with repro.session(backend="lazy", analysis={"level": "off"}) as s:
        lb = s.backend_instance()
        ops.materialize(ops.add(jnp.ones((4,)), 2.0))
        assert lb.last_analysis is None
    assert repro.current_session().analysis.level == "default"


def test_session_describe_includes_analysis():
    with repro.session(analysis={"level": "strict",
                                 "vmem_limit_bytes": 123}) as s:
        d = s.describe()["analysis"]
        assert d == {"level": "strict", "vmem_limit_bytes": 123,
                     "audit_serving": False}


def test_executable_describe_embeds_diagnostic_counts():
    f = repro.compile(lambda x: ops.neg(ops.tanh(x)), check="default")
    f(jnp.ones((4, 4)))
    d = f.last_executable.describe()
    assert d["diagnostics"] == {"INFO": 0, "WARNING": 0, "ERROR": 0}


# -- front 4: the serving audit over a real cache -----------------------------


@pytest.fixture(scope="module")
def paged_cache():
    from repro.configs.base import get_config
    from repro.models import build_model
    from repro.serving.kv_cache import PagedKVCache

    cfg = get_config("codeqwen1.5-7b", reduced=True, n_layers=2, d_model=64,
                     n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=64)
    model = build_model(cfg)
    return PagedKVCache(model, slots=2, max_seq=32, block_size=4)


def test_paged_cache_audit_clean_through_lifecycle(paged_cache):
    kv = paged_cache
    assert len(kv.audit()) == 0
    kv.ensure(0, 10)
    kv.ensure(1, 3)
    assert len(kv.audit()) == 0
    kv.release(0)
    assert len(kv.audit()) == 0
    kv.release(1)
    assert len(kv.audit()) == 0


def test_paged_cache_audit_catches_seeded_leak(paged_cache):
    kv = paged_cache
    kv.ensure(0, 7)
    # seed a leak: drop a held block without telling the allocator
    leaked = kv._blocks[0].pop()
    kv.table[0, len(kv._blocks[0])] = 0
    report = kv.audit()
    assert {d.rule for d in report.errors} == {"kv.leak"}
    kv._blocks[0].append(leaked)               # restore
    kv.table[0, len(kv._blocks[0]) - 1] = leaked[0]
    kv.release(0)
    assert len(kv.audit()) == 0


def test_snapshot_is_a_pure_value(paged_cache):
    kv = paged_cache
    kv.ensure(0, 5)
    snap = snapshot_cache(kv)
    kv.release(0)
    # the snapshot still describes the pre-release state
    assert snap.held[0]
    assert check_paged_cache(snap).max_severity() is None
    j = snap.to_json()
    assert j["manager"] == type(kv.manager).__name__


def test_engine_audit_raises_on_corruption():
    """audit_serving wiring: a corrupted table raises at the next
    release instead of surfacing as cross-request garbage."""
    from repro.configs.base import get_config
    from repro.models import build_model
    from repro.runtime import ServingPolicy
    from repro.serving import Request, ServeEngine

    cfg = get_config("codeqwen1.5-7b", reduced=True, n_layers=2, d_model=64,
                     n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=64)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    with repro.session(analysis={"audit_serving": True}):
        eng = ServeEngine(model, params, batch_slots=2, max_seq=32,
                          policy=ServingPolicy(cache="paged", block_size=4,
                                               prefill_chunk=4))
        eng.submit(Request(uid=0, prompt=[3, 1, 4], max_new_tokens=4))
        eng.submit(Request(uid=1, prompt=[9, 2], max_new_tokens=24))
        eng.step()
        # corrupt the long-running slot's table past its held prefix; the
        # audit fires when the short request's slot is released
        slot1 = next(s for s, r in eng.active.items() if r.uid == 1)
        eng.kv.table[slot1, eng.kv.max_blocks - 1] = 5
        with pytest.raises(AnalysisError) as ei:
            eng.run_until_done()
        assert "kv.table-stale" in ei.value.report.rules


def test_engine_audit_clean_run_at_strict():
    from repro.configs.base import get_config
    from repro.models import build_model
    from repro.runtime import ServingPolicy
    from repro.serving import Request, ServeEngine

    cfg = get_config("codeqwen1.5-7b", reduced=True, n_layers=2, d_model=64,
                     n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=64)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    with repro.session(analysis={"level": "strict"}):
        eng = ServeEngine(model, params, batch_slots=2, max_seq=32,
                          policy=ServingPolicy(cache="paged", block_size=4,
                                               prefill_chunk=4))
        for uid, p in enumerate([[3, 1, 4, 1, 5], [9, 2], [5, 3]]):
            eng.submit(Request(uid=uid, prompt=list(p), max_new_tokens=5))
        done = eng.run_until_done()
    assert sorted(r.uid for r in done) == [0, 1, 2]
    assert eng.kv.blocks_in_use == 0


# -- kernel contracts ---------------------------------------------------------


#: kernel -> a representative aligned launch of its shipped defaults
SHIPPED_LAUNCHES = {
    "flash_attention": dict(b=2, h=4, s=1024, d=64),
    "flash_decode": dict(n=8, s=2048, d=64),
    "flash_verify": dict(n=8, t=5, s=2048, d=64),
    "matmul": dict(m=512, k=512, n=512),
    "rms_norm": dict(n=1024, d=512),
    # starcoder2-7b serving: 36 / 4 heads of 128, blocks of 16, 4096 deep
    "paged_decode": dict(h=36, kv=4, d=128, block_size=16, max_blocks=256,
                         vmem_limit=16 << 20),
}


@pytest.mark.parametrize("kernel", SHIPPED_LAUNCHES)
def test_kernel_contracts_clean_on_shipped_defaults(kernel):
    """The hand-written kernels' own default launches must satisfy their
    declared contracts on representative aligned shapes."""
    report = check_kernel_call(kernel, **SHIPPED_LAUNCHES[kernel])
    assert not report.at_least(Severity.WARNING), (kernel, report.dump())


@pytest.mark.parametrize(
    "params", [dict(d=64), dict(block_size=2), dict(d=256)],
    ids=["head_dim_64", "page_of_8_rows", "page_view_copies_pool"])
def test_paged_decode_contract_refuses_unaligned_pages(params):
    """A pool the TPU cannot read in place (its page view a copy, or a
    page of part of a sublane tile) is an error there, and nothing off
    it (the interpreter takes any shape)."""
    shape = dict(h=8, kv=4, d=128, block_size=16, max_blocks=64) | params
    report = check_kernel_call("paged_decode", **shape)
    assert [d.rule for d in report] == ["tile.unaligned"]
    assert not check_kernel_call("paged_decode", on_tpu=False,
                                 **shape).at_least(Severity.WARNING)


def test_kernel_contract_unknown_kernel():
    with pytest.raises(KeyError):
        check_kernel_call("warp_drive", x=1)


def test_rms_norm_contract_replicates_autoshrink():
    # the launch wrapper shrinks bn until it divides n — so odd row
    # counts are legal and must not be flagged
    report = check_kernel_call("rms_norm", n=1000, d=256)
    assert not report.at_least(Severity.WARNING)


# -- misc ---------------------------------------------------------------------


def test_diagnostic_is_frozen():
    d = Diagnostic("x.y", Severity.INFO, "m")
    with pytest.raises(dataclasses.FrozenInstanceError):
        d.rule = "z"
