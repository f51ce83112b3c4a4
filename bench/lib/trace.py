"""Reduce a JAX profiler trace (``.xplane.pb``) to what the benchmark
reports: the device's busy time in the window, the device time of each
jitted program and of the top operations, and the longest idle gaps,
each named by the host annotation (``bench.*``) that was open at the
time.

The window is the host annotation ``bench.window``.  Device operations
are the events of each device plane's ``XLA Ops`` line, programs those
of its ``XLA Modules`` line.  An operation that holds others (a ``while``
around a scanned layer) is charged only its self time, so the top
operations do not count the same time twice.  Device timestamps run a
little early against the host's (about a millisecond on a TPU v5e); the
offset is taken as the median lag of each program's executions behind
its host dispatches (``PjitFunction(name)``) and added to the device
times.  Times are read in nanoseconds and reported in seconds;
per-device numbers are averaged over the devices traced.
"""

from __future__ import annotations

import pathlib
import re
import statistics
from collections import defaultdict
from dataclasses import dataclass, field

WINDOW = "bench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclass
class Reduced:
    window_s: float
    busy_s: float
    devices: int
    programs: dict = field(default_factory=dict)   # name -> [calls, s]
    top_ops: list = field(default_factory=list)    # [name, s]
    idle_gaps: list = field(default_factory=list)  # [host annotation, s]


def find_xplane(log_dir: pathlib.Path) -> pathlib.Path:
    found = sorted(pathlib.Path(log_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def _union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    out: list[list[int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(a: int, b: int, lo: int, hi: int) -> tuple[int, int] | None:
    a, b = max(a, lo), min(b, hi)
    return (a, b) if b > a else None


def _host_annotations(planes) -> list[tuple[int, int, str]]:
    out = []
    for plane in planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("bench."):
                    out.append((int(ev.start_ns),
                                int(ev.start_ns + ev.duration_ns), ev.name))
    return out


def _dispatches(planes) -> dict[str, list[int]]:
    """Host start times of each jitted function's dispatches, nested
    duplicates (the same call seen twice within 10 us) dropped."""
    out: dict[str, list[int]] = defaultdict(list)
    pat = re.compile(r"^PjitFunction\((.+)\)$")
    for plane in planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for ev in line.events:
                m = pat.match(ev.name)
                if m:
                    out[m.group(1)].append(int(ev.start_ns))
    for name, ts in out.items():
        ts.sort()
        out[name] = [t for i, t in enumerate(ts)
                     if i == 0 or t - ts[i - 1] > 10_000]
    return out


def _device_planes(planes) -> list:
    return [p for p in planes
            if p.name.startswith("/device:") and any(
                line.name == OPS_LINE for line in p.lines)]


def _device_offset(plane, dispatches: dict[str, list[int]]) -> int:
    """Nanoseconds to add to this plane's times: the median of (host
    dispatch - device start) over each program's executions, paired in
    order with its dispatches; 0 when nothing pairs."""
    starts: dict[str, list[int]] = defaultdict(list)
    for line in plane.lines:
        if line.name == MODULES_LINE:
            for ev in line.events:
                starts[ev.name.split("(")[0]].append(int(ev.start_ns))
    lags = []
    for module, ts in starts.items():
        host = dispatches.get(module.removeprefix("jit_"))
        if not host or len(host) != len(ts):
            continue
        lags += [h - d for h, d in zip(host, sorted(ts))]
    return max(0, int(statistics.median(lags))) if lags else 0


def short_op(name: str) -> str:
    """``%fusion.116 = s32[...] fusion(...), kind=kLoop`` -> ``%fusion.116
    fusion``: the result's name and the opcode."""
    m = re.match(r"^(%\S+) = .*?\s([a-z][\w\-]*)\(", name)
    return f"{m.group(1)} {m.group(2)}" if m else name[:80]


def _self_times(events) -> list[tuple[int, int, str, int]]:
    """(start, end, name, self ns) of each event on one line, where an
    event that contains later ones loses their time."""
    evs = sorted(((int(e.start_ns), int(e.start_ns + e.duration_ns),
                   e.name) for e in events), key=lambda x: (x[0], -x[1]))
    out, stack = [], []
    for a, b, name in evs:
        while stack and stack[-1][1] <= a:
            out.append(tuple(stack.pop()))
        if stack:
            stack[-1][3] -= min(b, stack[-1][1]) - a
        stack.append([a, b, name, b - a])
    out.extend(tuple(x) for x in reversed(stack))
    return out


def _name_at(t: int, annotations) -> str:
    """The innermost ``bench.*`` annotation other than the window that
    covers time ``t``, or ``host`` if none does."""
    best = None
    for a, b, name in annotations:
        if name != WINDOW and a <= t < b and (best is None
                                              or b - a < best[1] - best[0]):
            best = (a, b, name)
    return best[2] if best else "host"


def reduce_planes(planes, top: int = 10) -> Reduced:
    planes = list(planes)
    notes = _host_annotations(planes)
    windows = [(a, b) for a, b, n in notes if n == WINDOW]
    if not windows:
        raise ValueError(f"no {WINDOW!r} annotation in the trace")
    lo, hi = windows[0]
    devices = _device_planes(planes)
    if not devices:
        raise ValueError("no device plane with an 'XLA Ops' line")
    busy = 0
    ops: dict[str, float] = defaultdict(float)
    progs: dict[str, list] = defaultdict(lambda: [0, 0.0])
    gaps: list[tuple[int, int]] = []             # (length, start)
    dispatches = _dispatches(planes)
    for plane in devices:
        shift = _device_offset(plane, dispatches)
        intervals = []
        for line in plane.lines:
            if line.name == MODULES_LINE:
                for ev in line.events:
                    a = int(ev.start_ns) + shift
                    c = _clip(a, a + int(ev.duration_ns), lo, hi)
                    if c is not None:
                        progs[ev.name][0] += 1
                        progs[ev.name][1] += (c[1] - c[0]) * 1e-9
            elif line.name == OPS_LINE:
                for a, b, name, self_ns in _self_times(line.events):
                    c = _clip(a + shift, b + shift, lo, hi)
                    if c is None:
                        continue
                    intervals.append(c)
                    share = (c[1] - c[0]) / max(1, b - a)
                    ops[short_op(name)] += self_ns * share * 1e-9
        merged = _union(intervals)
        busy += sum(b - a for a, b in merged)
        edges = [lo] + [t for iv in merged for t in iv] + [hi]
        gaps += [(b - a, a) for a, b in zip(edges[::2], edges[1::2])
                 if b > a]
    n = len(devices)
    gaps.sort(reverse=True)
    return Reduced(
        window_s=(hi - lo) * 1e-9,
        busy_s=busy * 1e-9 / n,
        devices=n,
        programs={k: [v[0] / n, v[1] / n] for k, v in progs.items()},
        top_ops=sorted(([k, v / n] for k, v in ops.items()),
                       key=lambda kv: -kv[1])[:top],
        idle_gaps=[[_name_at(a + d // 2, notes), d * 1e-9]
                   for d, a in gaps[:top]])


def reduce_file(path: pathlib.Path, top: int = 10) -> Reduced:
    from jax.profiler import ProfileData

    return reduce_planes(ProfileData.from_file(str(path)).planes, top)


def program_seconds(reduced: Reduced, needle: str) -> tuple[float, float]:
    """(calls, device seconds) summed over the programs whose name
    contains ``needle`` (``_decode_fn`` matches ``jit__decode_fn``)."""
    calls = secs = 0.0
    for name, (c, s) in reduced.programs.items():
        if needle in name:
            calls += c
            secs += s
    return calls, secs
