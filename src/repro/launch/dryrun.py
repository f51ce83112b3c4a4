import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture × input shape)
cell on the production meshes, with 512 placeholder host devices.

For each cell this records (to JSON under artifacts/dryrun/):
  * compile success, lower/compile wall time
  * compiled.memory_analysis() — per-device bytes (proves fit / flags
    over-budget cells)
  * compiled.cost_analysis() — per-device HLO FLOPs and bytes accessed
  * collective byte totals parsed from the post-SPMD HLO (all-gather /
    all-reduce / reduce-scatter / all-to-all / collective-permute)
  * MODEL_FLOPS = 6·N(·_active)·tokens for the §Roofline useful-compute ratio

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --mesh both            # all cells
  PYTHONPATH=src python -m repro.launch.dryrun --arch gemma3-27b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --variant fsdp ...     # §Perf variants
"""

import argparse
import json
import math
import re
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp

import repro
from repro.configs.base import ARCHS, SHAPES, cells, get_config
from repro.launch.mesh import make_production_mesh, mesh_chips
from repro.launch.steps import BASELINE, CellPlan, Variant
from repro.models.meta import is_meta

ART = Path(__file__).resolve().parents[3] / "artifacts" / "dryrun"

VARIANTS = {
    "baseline": BASELINE,
    # §Perf hillclimb variants
    "fsdp": Variant(name="fsdp", sharding="fsdp"),
    "blockwise": Variant(name="blockwise", attention="blockwise"),
    "fsdp_blockwise": Variant(name="fsdp_blockwise", sharding="fsdp",
                              attention="blockwise"),
    "remat_dots": Variant(name="remat_dots", remat="dots"),
    "chunked_ce": Variant(name="chunked_ce", ce="chunked"),
    "gspmd_decode": Variant(name="gspmd_decode", decode="gspmd"),
    "fp8_cache": Variant(name="fp8_cache", cache="fp8"),
    "opt_bf16": Variant(name="opt_bf16", opt_dtype="bf16"),
    "best_train": Variant(name="best_train", sharding="fsdp",
                          attention="blockwise", ce="chunked",
                          remat="dots"),
    "best_train_full": Variant(name="best_train_full", sharding="fsdp",
                               attention="blockwise", ce="chunked",
                               remat="full"),
    # beyond-paper: drop dense TP, keep EP + vocab TP, FSDP everything else
    "ep_fsdp": Variant(name="ep_fsdp", sharding="ep_fsdp"),
    "ep_fsdp_blockwise": Variant(name="ep_fsdp_blockwise",
                                 sharding="ep_fsdp",
                                 attention="blockwise"),
    "best_ep": Variant(name="best_ep", sharding="ep_fsdp",
                       attention="blockwise", ce="chunked",
                       opt_dtype="bf16"),
    "best_ep_dots": Variant(name="best_ep_dots", sharding="ep_fsdp",
                            attention="blockwise", ce="chunked",
                            remat="dots", opt_dtype="bf16"),
    # beyond-paper: explicit all-to-all expert dispatch under shard_map
    "ep_a2a": Variant(name="ep_a2a", sharding="ep_fsdp", moe_impl="a2a"),
    "best_v3": Variant(name="best_v3", sharding="ep_fsdp", moe_impl="a2a",
                       opt_dtype="bf16"),
    "best_v3_dots": Variant(name="best_v3_dots", sharding="ep_fsdp",
                            moe_impl="a2a", opt_dtype="bf16", remat="dots"),
    "gemma_best": Variant(name="gemma_best", sharding="ep_fsdp",
                          opt_dtype="bf16"),
    "gemma_best_dots": Variant(name="gemma_best_dots", sharding="ep_fsdp",
                               opt_dtype="bf16", remat="dots"),
}

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1, "u64": 8, "u32": 4, "u16": 2,
    "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_DEF_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w\.\-]+)\s*=\s*(.+)$")
_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")


def _type_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def parse_collectives(hlo_text: str) -> dict:
    """Sum wire bytes per collective kind from post-SPMD HLO.

    Approximations (documented in EXPERIMENTS.md): all-reduce moves ~2×
    its per-device payload (reduce-scatter + all-gather phases of a ring);
    the others move ~1× their per-device result/operand.
    """
    defs: dict[str, int] = {}
    totals = {k: 0 for k in _COLLECTIVES}
    counts = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        m = _DEF_RE.match(line)
        if not m:
            continue
        name, rhs = m.groups()
        # which collective (if any) does this instruction run?
        op = None
        for c in _COLLECTIVES:
            if f" {c}(" in rhs or rhs.startswith(f"{c}("):
                op = c
                break
        # store result size of every instruction for operand lookups
        first_paren = rhs.find("(")
        type_str = rhs[:first_paren] if first_paren > 0 else rhs
        defs[name] = _type_bytes(type_str)
        if op is None:
            continue
        payload = defs[name]
        if op == "all-reduce":
            payload *= 2
        elif op == "reduce-scatter":
            # wire ≈ operand size; look it up
            args = rhs[rhs.find("(") + 1: rhs.find(")")]
            ops_bytes = 0
            for a in args.split(","):
                a = a.strip().lstrip("%")
                a = a.split(" ")[-1].lstrip("%")
                ops_bytes += defs.get(a, 0)
            payload = ops_bytes or payload
        totals[op] += payload
        counts[op] += 1
    return {"bytes": totals, "counts": counts,
            "total_bytes": sum(totals.values())}


def model_flops(plan: CellPlan) -> dict:
    """6·N·D with MoE-active accounting."""
    cfg, shape = plan.cfg, plan.shape
    total = active = 0
    for m in jax.tree.leaves(plan.param_metas, is_leaf=is_meta):
        n = math.prod(m.shape)
        total += n
        if "experts" in (m.axes or ()):
            frac = cfg.moe.top_k / cfg.moe.n_routed
            active += int(n * frac)
        else:
            active += n
    if shape.kind == "decode":
        tokens = shape.global_batch
    else:
        tokens = shape.global_batch * shape.seq_len
    mult = 3 if shape.kind == "train" else 1   # fwd+bwd vs fwd
    return {"params_total": total, "params_active": active,
            "tokens": tokens,
            "model_flops": 2 * mult * active * tokens}


def run_cell(arch: str, shape_name: str, mesh, mesh_name: str,
             variant: Variant, out_dir: Path, keep_hlo: bool = False) -> dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    rec: dict = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                 "variant": variant.name, "chips": mesh_chips(mesh)}
    try:
        plan = CellPlan(cfg, shape, mesh, variant)
        fn, args, in_sh, out_sh, donate = plan.lowerable()
        t0 = time.time()
        with repro.session(mesh=mesh,
                           batch_axes=plan.rules.mesh_axes_for("batch"),
                           sharding_rules=plan.rules,
                           tag=f"dryrun:{arch}/{shape_name}/{variant.name}"
                           ) as sess:
            rec["session"] = sess.describe()
            jitted = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                             donate_argnums=donate)
            lowered = jitted.lower(*args)
            rec["lower_s"] = round(time.time() - t0, 2)
            t0 = time.time()
            compiled = lowered.compile()
            rec["compile_s"] = round(time.time() - t0, 2)
        mem = compiled.memory_analysis()
        rec["memory"] = {
            k: int(getattr(mem, k))
            for k in ("argument_size_in_bytes", "output_size_in_bytes",
                      "temp_size_in_bytes", "generated_code_size_in_bytes",
                      "alias_size_in_bytes")
            if hasattr(mem, k)}

        cost = compiled.cost_analysis()
        rec["cost"] = {k: float(v) for k, v in cost.items()
                       if isinstance(v, (int, float))
                       and k in ("flops", "bytes accessed",
                                 "bytes accessed output", "transcendentals")}
        hlo = compiled.as_text()
        rec["hlo_chars"] = len(hlo)
        rec["collectives"] = parse_collectives(hlo)   # raw (loop-blind)
        from repro.launch.hlo_analysis import analyze_hlo

        rec["hlo_analysis"] = analyze_hlo(hlo)        # loop-corrected
        rec.update(model_flops(plan))
        rec["sharding_warnings"] = sorted(set(plan.rules.warnings))[:20]
        rec["ok"] = True
        if keep_hlo:
            (out_dir / f"{arch}__{shape_name}.hlo.txt").write_text(hlo)
        del compiled, lowered, hlo
    except Exception as e:  # noqa: BLE001 - record and continue
        rec["ok"] = False
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single", choices=["single", "multi",
                                                         "both"])
    ap.add_argument("--variant", default="baseline",
                    choices=sorted(VARIANTS))
    ap.add_argument("--force", action="store_true",
                    help="recompute cells that already have artifacts")
    ap.add_argument("--keep-hlo", action="store_true")
    ap.add_argument("--include-skipped", action="store_true")
    args = ap.parse_args()

    meshes = []
    if args.mesh in ("single", "both"):
        meshes.append(("single_pod_16x16",
                       make_production_mesh(multi_pod=False)))
    if args.mesh in ("multi", "both"):
        meshes.append(("multi_pod_2x16x16",
                       make_production_mesh(multi_pod=True)))

    variant = VARIANTS[args.variant]
    todo = cells()
    if args.arch:
        a = args.arch.replace("-", "_").replace(".", "")
        a = {"codeqwen15_7b": "codeqwen15_7b"}.get(a, a)
        todo = [c for c in todo if c[0] == a or c[0].replace("_", "-") ==
                args.arch]
    if args.shape:
        todo = [c for c in todo if c[1] == args.shape]

    for mesh_name, mesh in meshes:
        out_dir = ART / mesh_name / variant.name
        out_dir.mkdir(parents=True, exist_ok=True)
        for arch, shape_name, skip_reason in todo:
            path = out_dir / f"{arch}__{shape_name}.json"
            if path.exists() and not args.force:
                print(f"[skip-cached] {mesh_name} {arch} {shape_name}")
                continue
            if skip_reason and not args.include_skipped:
                rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                       "variant": variant.name, "ok": True, "skipped": True,
                       "skip_reason": skip_reason}
                path.write_text(json.dumps(rec, indent=1))
                print(f"[skipped]  {mesh_name} {arch} {shape_name}: "
                      f"{skip_reason[:60]}")
                continue
            print(f"[compile]  {mesh_name} {variant.name} {arch} "
                  f"{shape_name} ...", flush=True)
            rec = run_cell(arch, shape_name, mesh, mesh_name, variant,
                           out_dir, keep_hlo=args.keep_hlo)
            path.write_text(json.dumps(rec, indent=1))
            status = "OK" if rec.get("ok") else "FAIL"
            print(f"[{status}]      {mesh_name} {arch} {shape_name} "
                  f"lower={rec.get('lower_s', '?')}s "
                  f"compile={rec.get('compile_s', '?')}s "
                  f"{rec.get('error', '')}", flush=True)


if __name__ == "__main__":
    main()
