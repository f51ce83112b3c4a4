#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, on the chip.

    python3 bench/controls.py --workload <name> --seeds 1,2,3 --seconds 10 [--control]

For each seed it runs the cell as ``bench/run.py`` does (a short window
is enough: only the readings matter) and prints the numbers that decide
``correct``.  With ``--control`` it also reads the control, the reference
put in the program's place in the next lower precision (fp8 for the
bfloat16 configurations here), and for training cells the fault of half
the batch left out, the mean taken over the rest (the reference in the
program's place again).  All seeds run in one process, so set-up
compiles once.  The benchmark's own runs never run this.
"""

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _train_faults(run, res) -> dict:
    from bench.lib import check, traffic

    t = run.traffic
    vocab = run.ref.dims(run.config)["v"]
    batches = [traffic.packed_rows(t, run.seed, vocab, i)
               for i in range(t["check_steps"])]
    ref = res["reference_readings"]
    hp = t["optimizer"]
    out = {}
    ctl = run.ref.train_reference(run.config, run.seed, batches, hp,
                                  quant="fp8")
    out["control"] = dict(check.train_numbers(ctl, ref), losses=ctl["losses"])
    half = [(tok[: len(tok) // 2], lab[: len(lab) // 2])
            for tok, lab in batches]
    fault = run.ref.train_reference(run.config, run.seed, half, hp)
    out["half_batch"] = dict(check.train_numbers(fault, ref),
                             losses=fault["losses"])
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--out", default=str(ROOT / "bench" / "out"))
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    from bench.lib import harness, registry

    harness.use_program(ROOT)
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    if jax.devices()[0].platform != "tpu":
        print("controls: no TPU", file=sys.stderr)
        return 1
    enable_compile_cache()
    bench = registry.load_benchmark(ROOT)
    wl = registry.workload(bench, args.workload)
    config = registry.load_config(bench, wl["config"], ROOT)
    traffic = registry.load_traffic(wl["traffic"], ROOT)
    ref = registry.reference(config["reference"], ROOT)
    runner = registry.runner(traffic["runner"], ROOT)
    out_path = pathlib.Path(args.out) / f"controls-{wl['name']}.jsonl"
    out_path.parent.mkdir(parents=True, exist_ok=True)
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        run = harness.Run(workload=wl, config=config, traffic=traffic,
                          ref=ref, seed=seed, seconds=args.seconds,
                          trace=False, chips=wl["chips"], t_process=t,
                          root=ROOT, control=args.control)
        res = runner.run(run)
        row = {"seed": seed, "correct": res["correct"],
               "checks": res["checks"], "info": res.get("check_info"),
               "e2e": res["e2e"], "setup_s": res["t0"] - t,
               "memory_peak_bytes": res["memory_peak_bytes"],
               "compiles_in_window": res["compiles_in_window"]}
        if "reference_readings" in res:
            row["readings"] = {"program": res["program_readings"],
                               "reference": res["reference_readings"]}
            if args.control:
                row["faults"] = _train_faults(run, res)
        row["wall_s"] = time.perf_counter() - t
        line = json.dumps(row, default=float)
        print(line, flush=True)
        with out_path.open("a") as f:
            f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
