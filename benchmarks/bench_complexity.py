"""Paper Table 1 analog: framework complexity metrics.

Reports our op surface / LOC / per-function operator counts next to the
paper's published PyTorch & TensorFlow numbers (reference values from the
paper's Table 1; we cannot re-measure those here).
"""

from __future__ import annotations

from pathlib import Path

from repro.core.tensor import TensorBackend
from repro.launch.compile_cache import enable_compile_cache

ROOT = Path(__file__).resolve().parents[1]

PAPER = {
    "pytorch": {"loc": 1_798_292, "ops": 2166, "add": 55, "conv": 85,
                "sum": 25},
    "tensorflow": {"loc": 1_306_159, "ops": 1423, "add": 20, "conv": 30,
                   "sum": 10},
    "flashlight": {"loc": 27_173, "ops": 60, "add": 1, "conv": 2, "sum": 1},
}


def count_loc(subdir: str = "src/repro") -> int:
    total = 0
    for p in (ROOT / subdir).rglob("*.py"):
        total += sum(1 for line in p.read_text().splitlines()
                     if line.strip() and not line.strip().startswith("#"))
    return total


def run() -> list[tuple[str, float, str]]:
    prims = TensorBackend.primitive_ops()
    n_ops = len(prims)
    loc_all = count_loc("src/repro")
    loc_core = count_loc("src/repro/core")
    n_add = prims.count("add")
    n_conv = sum(1 for p in prims if p.startswith("conv"))
    n_sum = prims.count("sum")
    rows = [
        ("complexity_op_surface", float(n_ops),
         f"paper: fl={PAPER['flashlight']['ops']} "
         f"pt={PAPER['pytorch']['ops']} tf={PAPER['tensorflow']['ops']}"),
        ("complexity_loc_total", float(loc_all),
         f"paper fl=27173; pt=1.8M tf=1.3M"),
        ("complexity_loc_core", float(loc_core),
         "tensor+autograd+nn+optim+memory+dist+data"),
        ("complexity_ops_performing_add", float(n_add),
         f"paper: fl=1 pt=55 tf=20"),
        ("complexity_ops_performing_conv", float(n_conv),
         f"paper: fl=2 pt=85 tf=30"),
        ("complexity_ops_performing_sum", float(n_sum),
         f"paper: fl=1 pt=25 tf=10"),
    ]
    return rows


if __name__ == "__main__":
    enable_compile_cache()
    for name, val, derived in run():
        print(f"{name},{val:.0f},{derived}")
