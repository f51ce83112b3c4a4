"""Find what ``BENCHMARK.json`` names.

Everything that belongs to one configuration, traffic mix, per-layer
metric, runner or reference sits in a file of its own under ``bench/``,
found by the name that ``BENCHMARK.json`` or a data file gives it:

* configuration ``<name>``: the ``file`` of its entry in ``configs``;
* traffic ``<name>``: ``bench/traffic/<name>.json``;
* per-layer metric ``<name>``: ``bench/metrics/<name>.py``, else
  ``bench/metrics/<stem>.py`` where ``<stem>`` is the name up to its
  first dot (``mfu.train`` and ``mfu.serve`` share ``mfu.py``);
* runner ``<name>`` (the traffic file's ``runner``):
  ``bench/runners/<name>.py``;
* reference ``<name>`` (the configuration file's ``reference``):
  ``bench/refs/<name>.py``.

A later change adds a cell, a configuration or a metric by adding such
files and entries; it edits none of these functions.
"""

from __future__ import annotations

import importlib.util
import json
import pathlib
import sys
from types import ModuleType

#: root of the checkout: the directory that holds ``BENCHMARK.json``
ROOT = pathlib.Path(__file__).resolve().parents[2]


class NotFound(LookupError):
    """A name that ``BENCHMARK.json`` or a data file gives has no file."""


def load_benchmark(root: pathlib.Path = ROOT) -> dict:
    path = root / "BENCHMARK.json"
    if not path.is_file():
        raise NotFound(f"no BENCHMARK.json at {root}")
    return json.loads(path.read_text())


def _entry(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise NotFound(f"no {what} named {name!r} in BENCHMARK.json")


def workload(bench: dict, name: str) -> dict:
    return _entry(bench["workloads"], name, "workload")


def load_config(bench: dict, name: str, root: pathlib.Path = ROOT) -> dict:
    entry = _entry(bench["configs"], name, "configuration")
    path = root / entry["file"]
    if not path.is_file():
        raise NotFound(f"configuration {name!r}: no file {path}")
    return json.loads(path.read_text())


def load_traffic(name: str, root: pathlib.Path = ROOT) -> dict:
    path = root / "bench" / "traffic" / f"{name}.json"
    if not path.is_file():
        raise NotFound(f"traffic {name!r}: no file {path}")
    return json.loads(path.read_text())


def load_module(path: pathlib.Path) -> ModuleType:
    """Import a file under ``bench/`` by its path (names may hold dots
    and dashes, which ``import`` does not take)."""
    mod_name = "bench_file_" + "_".join(
        "".join(c if c.isalnum() else "_" for c in part)
        for part in path.relative_to(path.parents[1]).with_suffix("").parts)
    if mod_name in sys.modules:
        return sys.modules[mod_name]
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


def _module(kind: str, name: str, root: pathlib.Path) -> ModuleType:
    path = root / "bench" / kind / f"{name}.py"
    if not path.is_file():
        raise NotFound(f"no {kind[:-1]} {name!r}: no file {path}")
    return load_module(path)


def runner(name: str, root: pathlib.Path = ROOT) -> ModuleType:
    return _module("runners", name, root)


def reference(name: str, root: pathlib.Path = ROOT) -> ModuleType:
    return _module("refs", name, root)


def metric_reader(name: str, root: pathlib.Path = ROOT):
    """The ``read(ctx)`` function of per-layer metric ``name``."""
    base = root / "bench" / "metrics"
    for stem in (name, name.split(".")[0]):
        path = base / f"{stem}.py"
        if path.is_file():
            return load_module(path).read
    raise NotFound(f"per-layer metric {name!r}: no reader under {base}")


def cell_metrics(bench: dict, cell: str, group: str) -> list[dict]:
    """The metrics of ``group`` (``end_to_end`` or ``per_layer``) that
    cell ``cell`` reports.

    A metric with a ``workloads`` key applies to the cells it lists.  An
    end-to-end metric without one applies to every cell; a per-layer
    metric without one applies to every cell that reports the end-to-end
    metric it ``moves``.
    """
    def listed(m: dict) -> bool | None:
        ws = m.get("workloads")
        return None if ws is None else cell in ws

    e2e = [m for m in bench["end_to_end"] if listed(m) is not False]
    if group == "end_to_end":
        return e2e
    names = {m["name"] for m in e2e}
    out = []
    for m in bench["per_layer"]:
        hit = listed(m)
        if hit or (hit is None and m["moves"] in names):
            out.append(m)
    return out
