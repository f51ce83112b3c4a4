"""Put the device's idle time in a traced run down to the program's own
spans.

The serving engine writes its spans (``serve.*``, ``kv.*``) as
``TraceAnnotation`` host events whenever a profiler session collects,
with their attributes as event stats.  This module reads them from the
run's ``.xplane.pb``, clipped to the ``bench.window`` annotation, and
puts each nanosecond in which no operation ran on the device down to the
innermost program span open at that moment, or to ``host`` where none
is.  Device intervals are aligned to the host clock as
``bench/lib/trace.py`` does it; per-device times are averaged over the
devices traced.  The engine runs on one thread, so
its spans nest.

A per-layer metric reads :func:`for_ctx`; from the command line::

    python3 -m bench.lib.spans <file.xplane.pb>

prints per span name its count, host seconds, self seconds and
device-idle seconds.
"""

from __future__ import annotations

import functools
import pathlib
import statistics
import sys
from collections import defaultdict
from dataclasses import dataclass, field

from bench.lib import registry, trace
from bench.lib.trace import (OPS_LINE, WINDOW, _clip, _device_offset,
                             _device_planes, _dispatches, _union)

PREFIXES = ("serve.", "kv.")
HOST = "host"
ENGINE_STEP = "bench.engine_step"
LONGEST = 12


@dataclass
class HostSpan:
    name: str
    start: int                         # ns, host clock
    end: int
    attrs: dict = field(default_factory=dict)
    parent: int | None = None          # index into Attribution.spans


@dataclass
class Attribution:
    window_s: float
    busy_s: float
    devices: int
    spans: list[HostSpan]              # program spans that meet the window
    #: device-idle seconds by span index (-1: no program span open)
    idle: dict[int, float]
    #: the same, counting only idle time inside ``bench.engine_step``
    idle_in_step: dict[int, float]
    #: the idle intervals (ns) of the first device, longest first, each
    #: cut by the innermost span: [(ns, [(span index, ns), ...]), ...]
    longest: list = field(default_factory=list)

    def names(self, i: int) -> list[str]:
        """The span's name and its ancestors' names, innermost first."""
        out = []
        while i is not None and i >= 0:
            out.append(self.spans[i].name)
            i = self.spans[i].parent
        return out

    def enclosing(self, i: int | None, name: str) -> int | None:
        """Index of the span ``name`` that is span ``i`` or holds it."""
        while i is not None and i >= 0:
            if self.spans[i].name == name:
                return i
            i = self.spans[i].parent
        return None

    def idle_under(self, name: str) -> float:
        """Device-idle seconds while a span ``name`` was open, its
        children's included."""
        return sum(s for i, s in self.idle.items() if name in self.names(i))

    def idle_by_name(self) -> dict[str, float]:
        """Device-idle seconds by the innermost span's name (``host``
        where none was open)."""
        out: dict[str, float] = defaultdict(float)
        for i, s in self.idle.items():
            out[self.spans[i].name if i >= 0 else HOST] += s
        return dict(out)


def _program_spans(planes, lo: int, hi: int) -> list[HostSpan]:
    """Program spans that meet ``[lo, hi)``, in start order, each with
    the index of the innermost span that holds it (a child that outlasts
    its parent is cut at the parent's end)."""
    raw = []
    for plane in planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIXES):
                    a = int(ev.start_ns)
                    b = a + int(ev.duration_ns)
                    if b > lo and a < hi:
                        raw.append(HostSpan(ev.name, a, b, dict(
                            getattr(ev, "stats", ()) or ())))
    raw.sort(key=lambda s: (s.start, -s.end))
    stack: list[int] = []
    for i, s in enumerate(raw):
        while stack and raw[stack[-1]].end <= s.start:
            stack.pop()
        if stack:
            s.parent = stack[-1]
            s.end = min(s.end, raw[stack[-1]].end)
        stack.append(i)
    return raw


def _innermost(spans: list[HostSpan], lo: int,
               hi: int) -> list[tuple[int, int, int]]:
    """``[lo, hi)`` cut into (start, end, span index) pieces, each under
    the innermost span open there (-1: none)."""
    out: list[tuple[int, int, int]] = []
    stack: list[int] = []
    t = lo

    def upto(x: int) -> None:
        nonlocal t
        x = min(max(x, t), hi)
        if x > t:
            out.append((t, x, stack[-1] if stack else -1))
            t = x

    def close() -> None:
        upto(spans[stack[-1]].end)
        stack.pop()

    for i, s in enumerate(spans):
        while stack and spans[stack[-1]].end <= s.start:
            close()
        upto(s.start)
        stack.append(i)
    while stack:
        close()
    upto(hi)
    return out


def _overlaps(xs, ys):
    """Each overlap of two sorted lists of disjoint intervals (``ys``
    entries may carry more fields): (clipped interval, index into
    ``ys``)."""
    j = 0
    for a, b in xs:
        while j < len(ys) and ys[j][1] <= a:
            j += 1
        k = j
        while k < len(ys) and ys[k][0] < b:
            c = _clip(a, b, ys[k][0], ys[k][1])
            if c is not None:
                yield c, k
            k += 1


def _charge(idle: list[tuple[int, int]],
            pieces: list[tuple[int, int, int]]) -> dict[int, int]:
    """Nanoseconds of the ``idle`` intervals under each piece's span."""
    out: dict[int, int] = defaultdict(int)
    for (a, b), k in _overlaps(idle, pieces):
        out[pieces[k][2]] += b - a
    return out


def attribute(planes) -> Attribution | None:
    """The idle attribution of one trace; ``None`` without a window or a
    device plane."""
    planes = list(planes)
    notes = trace._host_annotations(planes)
    windows = [(a, b) for a, b, n in notes if n == WINDOW]
    devices = _device_planes(planes)
    if not windows or not devices:
        return None
    lo, hi = windows[0]
    steps = _union([c for a, b, n in notes if n == ENGINE_STEP
                    for c in [_clip(a, b, lo, hi)] if c is not None])
    spans = _program_spans(planes, lo, hi)
    pieces = _innermost(spans, lo, hi)
    dispatches = _dispatches(planes)
    idle: dict[int, int] = defaultdict(int)
    in_step: dict[int, int] = defaultdict(int)
    busy = 0
    longest = []
    for plane in devices:
        shift = _device_offset(plane, dispatches)
        ops = []
        for line in plane.lines:
            if line.name == OPS_LINE:
                for ev in line.events:
                    a = int(ev.start_ns) + shift
                    c = _clip(a, a + int(ev.duration_ns), lo, hi)
                    if c is not None:
                        ops.append(c)
        merged = _union(ops)
        busy += sum(b - a for a, b in merged)
        edges = [lo] + [t for iv in merged for t in iv] + [hi]
        gaps = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
        for i, ns in _charge(gaps, pieces).items():
            idle[i] += ns
        gaps_in_step = [c for c, _k in _overlaps(gaps, steps)]
        for i, ns in _charge(gaps_in_step, pieces).items():
            in_step[i] += ns
        if not longest:
            longest = [(b - a, sorted(_charge([(a, b)], pieces).items(),
                                      key=lambda kv: -kv[1]))
                       for a, b in sorted(gaps, key=lambda g: g[0] - g[1])
                       [:LONGEST]]
    n = len(devices)
    return Attribution(
        window_s=(hi - lo) * 1e-9, busy_s=busy * 1e-9 / n, devices=n,
        spans=spans,
        idle={i: ns * 1e-9 / n for i, ns in idle.items()},
        idle_in_step={i: ns * 1e-9 / n for i, ns in in_step.items()},
        longest=longest)


@functools.lru_cache(maxsize=4)
def _load(path: str, _mtime: float) -> Attribution | None:
    from jax.profiler import ProfileData

    return attribute(ProfileData.from_file(path).planes)


def load(path: pathlib.Path) -> Attribution | None:
    """:func:`attribute` of a file, parsed once per path and version."""
    path = pathlib.Path(path)
    return _load(str(path), path.stat().st_mtime)


def newest_trace(root: pathlib.Path = registry.ROOT) -> pathlib.Path | None:
    """The newest ``.xplane.pb`` any run of this checkout wrote (one
    process holds the chip, so runs on one machine follow each other)."""
    found = list((root / "bench" / "out").glob(
        "*/trace/plugins/profile/*/*.xplane.pb"))
    return max(found, key=lambda p: p.stat().st_mtime) if found else None


def for_ctx(ctx) -> Attribution | None:
    """The attribution of the run whose reduced trace ``ctx`` holds:
    the newest trace, if its window is that run's to within 1 ms."""
    red = ctx.get("trace")
    if red is None:
        return None
    path = newest_trace()
    if path is None:
        return None
    att = load(path)
    if att is None or abs(att.window_s - red.window_s) > 1e-3:
        return None
    return att


def step_host_ms(att: Attribution) -> float | None:
    """Median over the ``serve.step`` spans that meet the window and hold
    a ``serve.decode_wait`` of the step's length less that wait, in ms:
    the host time of a step that the device cannot overlap."""
    waits: dict[int, int] = defaultdict(int)
    for s in att.spans:
        if s.name == "serve.decode_wait":
            i = att.enclosing(s.parent, "serve.step")
            if i is not None:
                waits[i] += s.end - s.start
    vals = [(att.spans[i].end - att.spans[i].start - w) * 1e-6
            for i, w in waits.items()]
    return statistics.median(vals) if vals else None


def admit_idle_share(att: Attribution) -> float | None:
    """Device-idle time while ``serve.admit`` was open, in % of the
    window; ``None`` where the trace has no such span."""
    if not any(s.name == "serve.admit" for s in att.spans):
        return None
    return 100.0 * att.idle_under("serve.admit") / att.window_s


def table(att: Attribution) -> list[tuple[str, int, float, float, float]]:
    """Per span name: count, host s, self s, device-idle s; ``host``
    last (idle with no span open), longest idle first."""
    count: dict[str, int] = defaultdict(int)
    host: dict[str, float] = defaultdict(float)
    child: dict[str, float] = defaultdict(float)
    for s in att.spans:
        count[s.name] += 1
        host[s.name] += (s.end - s.start) * 1e-9
        if s.parent is not None:
            child[att.spans[s.parent].name] += (s.end - s.start) * 1e-9
    idle = att.idle_by_name()
    rows = [(n, count[n], host[n], host[n] - child[n], idle.get(n, 0.0))
            for n in count]
    rows.sort(key=lambda r: -r[4])
    rows.append((HOST, 0, 0.0, 0.0, idle.get(HOST, 0.0)))
    return rows


def step_children_share(att: Attribution) -> float | None:
    """Share of the device-idle time inside ``bench.engine_step`` put down
    to a span below ``serve.step`` (not to ``serve.step`` itself or to
    no span), in %."""
    total = sum(att.idle_in_step.values())
    if not total:
        return None
    named = sum(s for i, s in att.idle_in_step.items()
                if i >= 0 and att.spans[i].name != "serve.step")
    return 100.0 * named / total


def main(argv: list[str]) -> int:
    if len(argv) != 1:
        print("usage: python3 -m bench.lib.spans <file.xplane.pb>",
              file=sys.stderr)
        return 2
    att = load(pathlib.Path(argv[0]))
    if att is None:
        print("no bench.window or no device plane in the trace",
              file=sys.stderr)
        return 1
    idle_s = att.window_s - att.busy_s
    print(f"window {att.window_s:.6f} s  busy {att.busy_s:.6f} s  "
          f"idle {idle_s:.6f} s  devices {att.devices}")
    print(f"{'span':<24}{'count':>8}{'host_s':>12}{'self_s':>12}"
          f"{'idle_s':>12}")
    for name, n, h, own, idle in table(att):
        print(f"{name:<24}{n:>8}{h:>12.6f}{own:>12.6f}{idle:>12.6f}")
    print("longest idle gaps (first device): s, then s under the spans "
          "that take most of it, and what the step holds")
    for ns, parts in att.longest:
        under: dict[str, int] = defaultdict(int)
        for i, v in parts:
            under[" < ".join(att.names(i)) or HOST] += v
        step = att.enclosing(parts[0][0], "serve.step")
        held = "" if step is None else " | step holds " + ",".join(sorted(
            {s.name for s in att.spans if s.parent == step}))
        print(f"  {ns * 1e-9:.6f}: " + "; ".join(
            f"{k} {v * 1e-9:.6f}" for k, v in sorted(
                under.items(), key=lambda kv: -kv[1])[:3]) + held)
    for label, value in (
            ("idle in bench.engine_step", sum(att.idle_in_step.values())),
            ("of it below serve.step, %", step_children_share(att)),
            ("step_host_ms", step_host_ms(att)),
            ("admit_idle_share, %", admit_idle_share(att))):
        print(f"{label}: {value if value is None else f'{value:.6f}'}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
