"""Putting the device's idle time down to the program's spans
(``bench/lib/spans.py``): the arithmetic on synthetic planes, the guard
on the window's length, and a small trace of the serving engine recorded
on a TPU (``bench/testdata/record_spans.py``)."""

import pytest

from bench.lib import registry, spans, trace

TESTDATA = registry.ROOT / "bench" / "testdata"


class Ev:
    def __init__(self, name, start, dur, **stats):
        self.name, self.start_ns, self.duration_ns = name, start, dur
        self.stats = list(stats.items())


class Line:
    def __init__(self, name, events):
        self.name, self.events = name, events


class Plane:
    def __init__(self, name, lines):
        self.name, self.lines = name, lines


def _synthetic():
    """One engine step of 780 ns in a 1000 ns window; the device runs
    only during [350, 690)."""
    host = Plane("/host:CPU", [Line("python", [
        Ev("bench.window", 0, 1000), Ev("bench.engine_step", 0, 800),
        Ev("serve.step", 10, 780, step=0, active=2, waiting=1),
        Ev("serve.admit", 20, 180, admitted=1),
        Ev("serve.admit_request", 30, 160, uid=7, queued_ms=1.5),
        Ev("kv.cow_copy", 40, 140, blocks=1),
        Ev("serve.decode_step", 300, 400, active=2, keys=40),
        Ev("serve.decode_dispatch", 300, 50),
        Ev("serve.decode_wait", 350, 350),
        Ev("serve.emit", 700, 80, finished=0)])])
    dev = Plane("/device:TPU:0", [
        Line("XLA Modules", [Ev("jit__decode_fn(1)", 350, 340)]),
        Line("XLA Ops", [Ev("fusion", 350, 200), Ev("dot", 550, 140)])])
    return [host, dev]


def test_idle_is_put_down_to_the_innermost_span_and_the_window_guarded(
        monkeypatch):
    att = spans.attribute(_synthetic())
    assert att.window_s == pytest.approx(1000e-9)
    assert att.busy_s == pytest.approx(340e-9)
    ns = {k: v * 1e9 for k, v in att.idle_by_name().items()}
    assert ns == {"host": pytest.approx(220), "serve.step": pytest.approx(120),
                  "serve.admit": pytest.approx(20),
                  "serve.admit_request": pytest.approx(20),
                  "kv.cow_copy": pytest.approx(140),
                  "serve.decode_dispatch": pytest.approx(50),
                  "serve.decode_wait": pytest.approx(10),
                  "serve.emit": pytest.approx(80)}
    assert sum(ns.values()) == pytest.approx(1000 - 340)
    assert att.idle_under("serve.admit") == pytest.approx(180e-9)
    assert spans.admit_idle_share(att) == pytest.approx(18.0)
    # the step lasted 780 ns, 350 of them waiting for the decode
    assert spans.step_host_ms(att) == pytest.approx(430e-6)
    # idle inside bench.engine_step: [0, 350) and [690, 800)
    assert sum(att.idle_in_step.values()) == pytest.approx(460e-9)
    assert spans.step_children_share(att) == pytest.approx(100 * 320 / 460)
    rows = {r[0]: r[1:] for r in spans.table(att)}
    assert rows["serve.step"][:3] == (1, pytest.approx(780e-9),
                                      pytest.approx((780 - 180 - 400 - 80)
                                                    * 1e-9))
    assert att.spans[3].attrs == {"blocks": 1}
    assert att.names(3) == ["kv.cow_copy", "serve.admit_request",
                            "serve.admit", "serve.step"]

    # the metrics read the newest trace only if its window is the run's
    monkeypatch.setattr(spans, "newest_trace", lambda: "newest.xplane.pb")
    monkeypatch.setattr(spans, "load", lambda _path: att)
    red = trace.reduce_planes(_synthetic())
    assert spans.for_ctx({"trace": red}) is att
    red.window_s += 2e-3
    assert spans.for_ctx({"trace": red}) is None
    assert spans.for_ctx({"trace": None}) is None
    monkeypatch.setattr(spans, "newest_trace", lambda: None)
    assert spans.for_ctx({"trace": trace.reduce_planes(_synthetic())}) is None

    # a program without the spans: nothing to read
    bare = spans.attribute([Plane("/host:CPU", [Line("python", [
        Ev("bench.window", 0, 1000)])]), _synthetic()[1]])
    assert spans.step_host_ms(bare) is None
    assert spans.admit_idle_share(bare) is None


def test_spans_of_a_serving_engine_recorded_on_a_tpu(capsys):
    path = TESTDATA / "spans.xplane.pb"
    assert path.stat().st_size < 200_000
    att = spans.load(path)
    red = trace.reduce_file(path)
    assert att.devices == 1
    assert att.window_s == pytest.approx(red.window_s)
    assert att.busy_s == pytest.approx(red.busy_s)
    names = {s.name for s in att.spans}
    assert names >= {"serve.step", "serve.admit", "serve.admit_request",
                     "serve.prefill", "serve.prefill_chunk",
                     "serve.ensure_capacity", "serve.decode_step",
                     "serve.decode_dispatch", "serve.decode_wait",
                     "serve.emit", "kv.grow", "kv.cow_copy"}
    idle = att.idle_by_name()
    assert sum(idle.values()) == pytest.approx(att.window_s - att.busy_s)
    calls, _secs = trace.program_seconds(red, "_decode_fn")
    waits = [s for s in att.spans if s.name == "serve.decode_wait"]
    assert len(waits) == calls
    steps = [s for s in att.spans if s.name == "serve.step"]
    assert 0 < spans.step_host_ms(att) < max(
        (s.end - s.start) * 1e-6 for s in steps)
    assert 0 <= spans.admit_idle_share(att) < 100
    assert 0 <= spans.step_children_share(att) <= 100
    chunks = [s for s in att.spans if s.name == "serve.prefill_chunk"]
    assert all(0 < s.attrs["tokens"] <= s.attrs["capacity"] for s in chunks)
    assert sum(s.attrs["blocks"] for s in att.spans
               if s.name == "kv.cow_copy") >= 1

    assert spans.main([str(path)]) == 0
    out = capsys.readouterr().out
    assert "serve.decode_wait" in out and "admit_idle_share" in out
