"""What every runner shares: the run's settings, the program's
configuration, annotations on the profiler's clock, a count of compiles,
the device's peak memory, and freeing the program's state before the
reference runs."""

from __future__ import annotations

import contextlib
import dataclasses
import pathlib
import sys
import time
from types import ModuleType

from bench.lib import registry

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


def clock() -> float:
    """The harness's clock: the same monotonic clock the program stamps
    its tokens with."""
    return time.perf_counter()


@dataclasses.dataclass
class Run:
    """One run of one cell, as ``bench/run.py`` parsed it."""

    workload: dict
    config: dict
    traffic: dict
    ref: ModuleType
    seed: int
    seconds: float
    trace: bool
    chips: int
    t_process: float
    root: pathlib.Path = registry.ROOT
    #: also read the control (``bench/controls.py``); benchmark runs never do
    control: bool = False

    @property
    def out_dir(self) -> pathlib.Path:
        """Where a run writes what it leaves (traces); git-ignored."""
        return self.root / "bench" / "out" / self.workload["name"]

    def annotate(self, name: str):
        """A host span on the profiler's clock in a traced run; nothing
        otherwise."""
        if not self.trace:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)

    def limits(self) -> dict:
        """The limits of the correctness check: the configuration's,
        with the traffic file's on top."""
        return {**self.config.get("limits", {}),
                **self.traffic.get("limits", {})}


def use_program(root: pathlib.Path) -> None:
    src = root / "src"
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))


def program_config(config: dict, ref: ModuleType):
    """The program's ``ModelConfig`` for a configuration file: the
    program's registered architecture with every size the file states."""
    from repro.configs.base import get_config

    base = get_config(config["program_arch"])
    over = ref.program_overrides(config)
    for k, v in list(over.items()):
        if isinstance(v, dict):
            over[k] = dataclasses.replace(getattr(base, k), **v)
    return base.with_(**over)


class CompileCounter:
    """Counts backend compiles and persistent-cache loads while armed;
    ``close()`` stops listening."""

    def __init__(self):
        import jax

        self.armed = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def close(self) -> None:
        import jax

        self.armed = False
        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)

    def _duration(self, event: str, _secs: float, **_kw) -> None:
        if self.armed and event == BACKEND_COMPILE:
            self.count += 1

    def _event(self, event: str, **_kw) -> None:
        if self.armed and event == CACHE_HIT:
            self.count += 1


def peak_bytes(devices) -> int | None:
    """Peak bytes in use on the fullest device, where it is reported."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def free(*trees) -> None:
    """Delete every device array in ``trees`` now, whoever else holds a
    reference, so the reference that runs next has the memory."""
    import jax

    for tree in trees:
        for leaf in jax.tree.leaves(tree):
            if isinstance(leaf, jax.Array) and not leaf.is_deleted():
                leaf.delete()


def sleep_until(t: float) -> None:
    left = t - clock()
    if left > 0:
        time.sleep(left)


def start_trace(run: Run) -> None:
    """Start the profiler, host spans and device activity only (no Python
    call tracing: it would slow the host the window measures)."""
    import shutil

    import jax

    shutil.rmtree(run.out_dir / "trace", ignore_errors=True)
    run.out_dir.mkdir(parents=True, exist_ok=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(run.out_dir / "trace"),
                             profiler_options=opts)


def stop_trace() -> None:
    import jax

    jax.profiler.stop_trace()
