"""repro.obs: session gating, span nesting (threads + nested sessions),
bounded retention, metrics, Chrome-trace export/validation, summarize
math, the telemetry bridge, and the instrumented serving/compiler paths.
"""

import json
import threading

import jax
import numpy as np
import pytest

import repro
from repro import obs
from repro.configs.base import get_config
from repro.core.memory import CachingMemoryManager, telemetry
from repro.core.tensor import ops
from repro.models import build_model
from repro.obs import (Tracer, save_trace, to_chrome_trace,
                       validate_chrome_trace)
from repro.obs.metrics import percentile
from repro.obs.summarize import summarize
from repro.runtime import ObservabilityPolicy, ServingPolicy
from repro.serving import Request, ServeEngine


# ---------------------------------------------------------------- gating

def test_obs_off_by_default():
    assert obs.get_tracer() is None
    with repro.session():
        assert obs.get_tracer() is None
    # module-level helpers are no-ops, not errors
    with obs.span("nope") as sp:
        assert sp is None
    obs.instant("nope")


def test_session_obs_coercion_and_provenance():
    with repro.session(obs=True) as sess:
        assert isinstance(sess.obs, ObservabilityPolicy)
        assert sess.obs.enabled
        assert obs.get_tracer() is not None
        desc = sess.describe()["obs"]
        assert desc["enabled"]
    with repro.session(obs={"max_events": 99}) as sess:
        assert sess.obs.enabled and sess.obs.max_events == 99
        assert obs.get_tracer().max_events == 99


def test_derived_sessions_share_tracer_fresh_policy_does_not():
    with repro.session(obs=True):
        outer = obs.get_tracer()
        assert outer is not None
        with repro.session(tag="inner"):       # derived: same policy obj
            assert obs.get_tracer() is outer
        with repro.session(obs=True):          # fresh policy: new tracer
            assert obs.get_tracer() is not outer
        with repro.session(obs=False):         # explicitly off inside
            assert obs.get_tracer() is None
    assert obs.get_tracer() is None


# --------------------------------------------------------------- tracing

def test_span_nesting_and_attrs():
    with repro.session(obs=True):
        t = obs.get_tracer()
        with obs.span("outer", "test", k=1) as a:
            with obs.span("inner", "test") as b:
                pass
            a.attrs["late"] = 2
    assert b.parent == a.sid and a.parent is None
    assert a.attrs == {"k": 1, "late": 2}
    assert [s.name for s in t.spans] == ["inner", "outer"]  # finish order
    assert all(s.end >= s.start for s in t.spans)


def test_spans_do_not_cross_parent_across_threads():
    tracer = Tracer()
    ready = threading.Barrier(2)

    def work(name):
        with tracer.span(f"{name}.outer"):
            ready.wait()
            with tracer.span(f"{name}.inner"):
                pass

    threads = [threading.Thread(target=work, args=(n,), name=n)
               for n in ("a", "b")]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    by_name = {s.name: s for s in tracer.spans}
    assert len(by_name) == 4
    for n in ("a", "b"):
        inner, outer = by_name[f"{n}.inner"], by_name[f"{n}.outer"]
        assert inner.parent == outer.sid
        assert inner.tid == outer.tid
    assert by_name["a.inner"].tid != by_name["b.inner"].tid
    assert set(tracer.thread_names.values()) >= {"a", "b"}


def test_mis_nested_finish_unwinds():
    tracer = Tracer()
    a = tracer.begin("a")
    tracer.begin("b")
    tracer.finish(a)                 # b never finished: unwound with a
    with tracer.span("c") as c:
        pass
    assert c.parent is None          # stack fully unwound


def test_max_events_bound_counts_drops():
    tracer = Tracer(max_events=3)
    for i in range(5):
        tracer.instant(f"e{i}")
    assert len(tracer.instants) == 3
    assert tracer.dropped == 2
    assert tracer.describe()["dropped"] == 2


# --------------------------------------------------------------- metrics

def test_metrics_counters_gauges_histograms():
    tracer = Tracer()
    m = tracer.metrics
    m.counter("c").add()
    m.counter("c").add(2.5)
    g = m.gauge("g")
    g.set(7)
    g.set(9)
    vals = [float(v) for v in np.random.default_rng(0).normal(size=257)]
    h = m.histogram("h")
    for v in vals:
        h.observe(v)
    snap = m.snapshot()
    assert snap["counters"]["c"] == 3.5
    assert snap["gauges"]["g"] == 9.0
    hs = snap["histograms"]["h"]
    assert hs["count"] == len(vals)
    for q in (50, 90, 99):
        assert hs[f"p{q}"] == pytest.approx(
            float(np.percentile(vals, q)), abs=1e-12)
    # gauge sets also landed on a counter track
    assert [s.value for s in tracer.samples] == [7.0, 9.0]


def test_percentile_matches_numpy_linear_interpolation():
    vals = sorted([0.1, 4.0, 2.0, 9.5, 3.3])
    for q in (0, 10, 25, 50, 75, 90, 99, 100):
        assert percentile(vals, q) == pytest.approx(
            float(np.percentile(vals, q)), abs=1e-12)


# ---------------------------------------------------------------- export

def test_export_validates_and_round_trips(tmp_path):
    tracer = Tracer()
    with tracer.span("parent", "t", k=1):
        with tracer.span("child", "t"):
            pass
    tracer.instant("evt", "t", uid=3)
    tracer.metrics.gauge("g").set(5)
    tracer.metrics.counter("n").add()
    path = tmp_path / "trace.json"
    obj = save_trace(tracer, str(path))
    assert validate_chrome_trace(obj) == []
    loaded = json.loads(path.read_text())
    assert validate_chrome_trace(loaded) == []
    phases = {e["ph"] for e in loaded["traceEvents"]}
    assert phases == {"M", "X", "i", "C"}
    x = [e for e in loaded["traceEvents"] if e["ph"] == "X"]
    assert {e["name"] for e in x} == {"parent", "child"}
    child = next(e for e in x if e["name"] == "child")
    parent = next(e for e in x if e["name"] == "parent")
    assert child["args"]["parent_id"] == parent["args"]["span_id"]
    assert loaded["metrics"]["counters"]["n"] == 1.0


def test_validator_catches_malformed_events():
    bad = {"traceEvents": [
        {"ph": "Z", "name": "x", "pid": 1, "tid": 1, "ts": 0},
        {"ph": "X", "name": "x", "pid": 1, "tid": 1, "ts": -5, "dur": 1},
        {"ph": "X", "name": "x", "pid": 1, "tid": 1, "ts": 0},
        {"ph": "i", "name": "x", "pid": 1, "tid": 1, "ts": 0, "s": "q"},
        {"ph": "C", "name": "x", "pid": 1, "tid": 1, "ts": 0,
         "args": {"v": "high"}},
        {"ph": "i", "name": 7, "pid": "p", "tid": 1, "ts": 0},
    ]}
    errs = validate_chrome_trace(bad)
    assert len(errs) >= 6
    assert validate_chrome_trace([]) == ["top-level value is not an object"]
    assert validate_chrome_trace({}) == ["missing or non-array 'traceEvents'"]


# ------------------------------------------------------------- summarize

def test_summarize_synthetic_trace_exact_math():
    """Known timestamps in, exact TTFT / inter-token / self-time out."""
    pid = 1
    ev = []

    def span(name, ts, dur, sid, parent=None):
        args = {"span_id": sid}
        if parent is not None:
            args["parent_id"] = parent
        ev.append({"ph": "X", "name": name, "cat": "t", "ts": ts,
                   "dur": dur, "pid": pid, "tid": 1, "args": args})

    def inst(name, ts, uid):
        ev.append({"ph": "i", "s": "t", "name": name, "cat": "t", "ts": ts,
                   "pid": pid, "tid": 1, "args": {"uid": uid}})

    span("root", 0.0, 100.0, sid=1)
    span("leaf", 10.0, 30.0, sid=2, parent=1)
    span("leaf", 50.0, 20.0, sid=3, parent=1)
    inst("request.submit", 0.0, uid=7)
    inst("request.first_token", 1_000_000.0, uid=7)   # µs -> TTFT 1s
    inst("request.token", 1_000_000.0, uid=7)
    inst("request.token", 1_250_000.0, uid=7)
    inst("request.token", 1_750_000.0, uid=7)
    inst("request.done", 1_750_000.0, uid=7)
    s = summarize({"traceEvents": ev})
    by_name = {a["name"]: a for a in s["spans"]["by_name"]}
    assert by_name["root"]["total_us"] == pytest.approx(100.0)
    assert by_name["root"]["self_us"] == pytest.approx(50.0)  # 100-30-20
    assert by_name["leaf"]["count"] == 2
    r = s["requests"]
    assert r["submitted"] == 1 and r["completed"] == 1
    assert r["ttft_s"]["count"] == 1
    assert r["ttft_s"]["p50"] == pytest.approx(1.0)
    assert r["inter_token_s"]["count"] == 2
    assert r["inter_token_s"]["p50"] == pytest.approx(0.375)  # .25/.5 mid
    assert r["inter_token_s"]["max"] == pytest.approx(0.5)


# ------------------------------------------------- telemetry (satellite)

def test_alloc_trace_timestamps_and_old_format_compat(tmp_path):
    trace = telemetry.start_recording()
    telemetry.record_alloc(1, 4096, tag="matmul")
    telemetry.record_free(1)
    t = telemetry.stop_recording()
    assert all(e.ts > 0 for e in t.events)
    assert t.events[0].ts <= t.events[1].ts
    path = tmp_path / "trace.json"
    t.save(str(path))
    t2 = telemetry.AllocTrace.load(str(path))
    assert [(e.kind, e.uid, e.ts) for e in t2.events] == \
        [(e.kind, e.uid, e.ts) for e in t.events]

    # traces written before the ts field existed still load + replay
    old = [{"kind": "alloc", "uid": 5, "nbytes": 512, "tag": "add"},
           {"kind": "free", "uid": 5, "nbytes": 512, "tag": ""}]
    oldpath = tmp_path / "old.json"
    oldpath.write_text(json.dumps(old))
    t3 = telemetry.AllocTrace.load(str(oldpath))
    assert [e.ts for e in t3.events] == [0.0, 0.0]
    mgr = CachingMemoryManager(capacity=1 << 20)
    t3.replay(mgr)
    assert mgr.stats.n_allocs == 1 and mgr.stats.live_allocated == 0


def test_telemetry_bridges_into_obs_without_recording():
    with repro.session(obs=True):
        tracer = obs.get_tracer()
        telemetry.record_alloc(42, 1024, tag="kv.block")
        telemetry.record_free(42)
    names = [(i.name, i.attrs.get("uid")) for i in tracer.instants]
    assert ("mem.alloc", 42) in names and ("mem.free", 42) in names
    # and no AllocTrace was involved
    assert telemetry.stop_recording() is None


# --------------------------------------------- instrumented stack paths

@pytest.fixture(scope="module")
def tiny():
    cfg = get_config("codeqwen1.5-7b", reduced=True, n_layers=2, d_model=64,
                     n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=64)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return model, params


def test_compiler_spans_and_cache_counters():
    @repro.compile
    def f(x, y):
        return ops.tanh(ops.add(ops.mul(x, y), x))

    a = np.linspace(-1, 1, 64, dtype=np.float32)
    with repro.session(obs=True):
        f(a, a)
        f(a + 1, a - 1)
        tracer = obs.get_tracer()
    names = {s.name for s in tracer.spans}
    assert {"compiler.trace", "compiler.compile", "compiler.lower",
            "compiler.execute"} <= names
    assert any(n.startswith("compiler.pass.") for n in names)
    # pass spans nest under the compile span
    compile_sp = next(s for s in tracer.spans
                      if s.name == "compiler.compile")
    for sp in tracer.spans:
        if sp.name.startswith("compiler.pass."):
            assert sp.parent == compile_sp.sid
    counters = tracer.metrics.snapshot()["counters"]
    assert counters["compiler.program_cache_miss"] == 1.0
    assert counters["compiler.program_cache_hit"] == 1.0


def test_serving_trace_reconstructs_lifecycle(tiny):
    """The obs stream must reproduce the admission/preempt/requeue story
    pinned by test_serving_paged.test_preemption_evicts_requeues_and_
    recomputes — same scenario, now read back from the trace."""
    model, params = tiny
    prompts = [[3, 1, 4, 1, 5, 9], [9, 2, 6, 5, 3, 5]]
    pol = ServingPolicy(cache="paged", block_size=4, num_blocks=7,
                        prefill_chunk=4)

    def run(obs_on):
        with repro.session(obs=obs_on, serving=pol):
            eng = ServeEngine(model, params, batch_slots=2, max_seq=32)
            for u, p in enumerate(prompts):
                eng.submit(Request(uid=u, prompt=list(p),
                                   max_new_tokens=12))
            done = eng.run_until_done()
            return eng, done, obs.get_tracer()

    eng, done, tracer = run(True)
    assert eng.preemptions > 0
    events = [(i.name, i.attrs.get("uid")) for i in tracer.instants
              if i.name.startswith("request.")]

    # per-request ordering: submit -> admit -> first_token; a preempted
    # request is requeued and admitted again before finishing
    for uid in (0, 1):
        seq = [n for n, u in events if u == uid]
        assert seq[0] == "request.submit"
        assert seq.count("request.done") == 1 and seq[-1] == "request.done"
        assert seq.index("request.admit") < seq.index("request.first_token")
        n_pre = seq.count("request.preempt")
        assert seq.count("request.admit") == 1 + n_pre
        if n_pre:
            i_pre = seq.index("request.preempt")
            assert "request.requeue" in seq[i_pre:]
            assert "request.admit" in seq[i_pre:]
    assert sum(n == "request.preempt" for n, _ in events) == eng.preemptions

    # spans + histograms agree with engine counters
    assert sum(s.name == "serve.decode_step" for s in tracer.spans) == \
        eng.decode_calls
    hists = tracer.metrics.snapshot()["histograms"]
    assert hists["serving.ttft_s"]["count"] == len(done)

    # observability does not change decoding
    _, done_off, tracer_off = run(False)
    assert tracer_off is None
    assert {r.uid: r.generated for r in done} == \
        {r.uid: r.generated for r in done_off}

    # and the whole stream exports cleanly
    assert validate_chrome_trace(to_chrome_trace(tracer)) == []


def test_serving_kv_telemetry_uses_negative_uid_namespace(tiny):
    """KV-block alloc events must not collide with LazyTensor uids when a
    recording spans both sources."""
    model, params = tiny
    pol = ServingPolicy(cache="paged", block_size=4, prefill_chunk=4)
    telemetry.start_recording()
    try:
        with repro.session(serving=pol):
            eng = ServeEngine(model, params, batch_slots=1, max_seq=32)
            eng.submit(Request(uid=0, prompt=[3, 1, 4, 1], max_new_tokens=4))
            eng.run_until_done()
    finally:
        trace = telemetry.stop_recording()
    kv_events = [e for e in trace.events if e.tag == "kv.block"]
    assert kv_events and all(e.uid < 0 for e in kv_events)
    mgr = CachingMemoryManager(capacity=1 << 30)
    trace.replay(mgr)
    assert mgr.stats.live_allocated == 0


# ------------------------------------ spans on the profiler's trace too

#: span -> the spans it may sit directly under, and its attributes
SERVE_SPANS = {
    "serve.step": ((None,), {"step", "active", "waiting"}),
    "serve.admit": (("serve.step",), {"admitted"}),
    "serve.admit_request": (("serve.admit",),
                            {"uid", "prompt_tokens", "shared", "queued_ms"}),
    "serve.prefill": (("serve.step",),
                      {"calls", "tokens_needed", "tokens_saved"}),
    "serve.prefill_chunk": (("serve.prefill",), {"tokens", "capacity"}),
    "serve.ensure_capacity": (("serve.step",), set()),
    "serve.decode_step": (("serve.step",),
                          {"active", "keys", "kv_path", "kv_blocks"}),
    "serve.decode_dispatch": (("serve.decode_step",), set()),
    "serve.decode_wait": (("serve.decode_step",), set()),
    "serve.emit": (("serve.step",), {"finished"}),
    "kv.grow": (("serve.admit_request", "serve.ensure_capacity"),
                {"blocks"}),
    "kv.cow_copy": (("serve.admit_request", "serve.ensure_capacity"),
                    {"blocks"}),
}


def _profiled_spans(log_dir):
    """(name, attrs, parent name) of each program span in the profile,
    nested by containment on the thread that recorded it."""
    from jax.profiler import ProfileData

    path, = log_dir.glob("plugins/profile/*/*.xplane.pb")
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            evs = sorted(((int(e.start_ns), int(e.start_ns + e.duration_ns),
                           e.name, dict(e.stats)) for e in line.events
                          if e.name.startswith(("serve.", "kv."))),
                         key=lambda e: (e[0], -e[1]))
            stack = []
            for a, b, name, attrs in evs:
                while stack and stack[-1][1] <= a:
                    stack.pop()
                assert not stack or b <= stack[-1][1], (name, stack[-1][2])
                out.append((name, attrs, stack[-1][2] if stack else None))
                stack.append((a, b, name))
    return out


def _traced_spans(tracer):
    by_sid = {s.sid: s for s in tracer.spans}
    return [(s.name, s.attrs,
             by_sid[s.parent].name if s.parent is not None else None)
            for s in tracer.spans if s.name.startswith(("serve.", "kv."))]


def _check_spans(spans, eng, before, admitted_prompt_tokens):
    """Every span of the table, under its parent, with its attributes,
    and the counts the engine keeps."""
    assert {n for n, _a, _p in spans} == set(SERVE_SPANS)
    for name, attrs, parent in spans:
        parents, keys = SERVE_SPANS[name]
        assert parent in parents, (name, parent)
        assert set(attrs) >= keys, (name, attrs)
        assert all(isinstance(v, (int, float, str)) for v in attrs.values())

    def count(name):
        return sum(n == name for n, _a, _p in spans)

    def total(name, key):
        return sum(a[key] for n, a, _p in spans if n == name)

    assert count("serve.decode_wait") == eng.decode_calls - before[0]
    assert count("serve.prefill_chunk") == eng.prefill_calls - before[1]
    assert total("kv.cow_copy", "blocks") == \
        eng.kv.cow_copies - before[2] > 0
    saved = eng.prefill_tokens_saved - before[3]
    assert saved > 0
    assert total("serve.prefill_chunk", "tokens") == \
        admitted_prompt_tokens - saved
    assert total("serve.prefill", "tokens_saved") == saved


def _serve_shared(model, params, obs_on, log_dir=None):
    """A paged engine with prefix sharing: one request fills the prefix
    cache, then (profiled when ``log_dir`` is given) two requests share
    it, the first diverging inside a shared block (one copy-on-write)."""
    base = [3, 1, 4, 1, 5, 9, 2, 6, 5, 3]
    pol = ServingPolicy(cache="paged", block_size=4, prefill_chunk=8,
                        prefix=True)
    with repro.session(obs=obs_on, serving=pol):
        eng = ServeEngine(model, params, batch_slots=2, max_seq=64)
        tracer = obs.get_tracer()
    eng.submit(Request(uid=0, prompt=list(base), max_new_tokens=3))
    eng.run_until_done()
    if tracer is not None:
        tracer.spans.clear()
    before = (eng.decode_calls, eng.prefill_calls, eng.kv.cow_copies,
              eng.prefill_tokens_saved)
    reqs = [Request(uid=1, prompt=base[:6] + [7, 7, 7], max_new_tokens=4),
            Request(uid=2, prompt=base + [8], max_new_tokens=4)]
    if log_dir is not None:
        jax.profiler.start_trace(str(log_dir))
    try:
        for r in reqs:
            eng.submit(r)
        done = eng.run_until_done()
    finally:
        if log_dir is not None:
            jax.profiler.stop_trace()
    tokens = {r.uid: r.generated for r in done}
    return eng, tracer, before, sum(len(r.prompt) - 1 for r in reqs), tokens


@pytest.fixture(scope="module")
def shared_tokens(tiny):
    return _serve_shared(*tiny, obs_on=False)[-1]


@pytest.mark.parametrize("obs_on", [False, True], ids=["obs_off", "obs_on"])
@pytest.mark.parametrize("profiled", [False, True],
                         ids=["profiler_off", "profiler_on"])
def test_serving_spans_reach_the_profiler(tiny, shared_tokens, tmp_path,
                                          profiled, obs_on):
    """The engine's spans land on the profiler's trace whenever a profile
    is collected, with observability on or off, nested and with their
    attributes, counting what the engine counts; with observability on
    the tracer holds the same spans.  Neither sink changes a token."""
    eng, tracer, before, prompt_tokens, tokens = _serve_shared(
        *tiny, obs_on=obs_on, log_dir=tmp_path if profiled else None)
    assert tokens == shared_tokens
    assert (tracer is not None) == obs_on
    if profiled:
        _check_spans(_profiled_spans(tmp_path), eng, before, prompt_tokens)
    else:
        assert not list(tmp_path.iterdir())
    if obs_on:
        _check_spans(_traced_spans(tracer), eng, before, prompt_tokens)
