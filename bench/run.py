#!/usr/bin/env python3
"""Run one cell of the benchmark on the chips of this machine.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

``<name>`` is a workload of ``BENCHMARK.json``; its configuration, traffic
file, runner and reference are found by name (``bench/lib/registry.py``).
The run loads, warms up every shape the cell uses, measures for
``--seconds`` seconds, checks the timed path's output against the plain
reference, and prints one JSON object as the last line of standard
output.  With ``--trace 0`` its metrics are the cell's end-to-end
metrics; with ``--trace 1`` the window runs under the profiler and the
metrics are the cell's per-layer metrics.

It exits non-zero without printing a result when JAX finds no TPU, fewer
chips than the cell asks for, a device with no published peaks, or no
program beside ``BENCHMARK.json``.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _say(tag: str, msg: str) -> None:
    print(f"[{tag}] {msg}", file=sys.stderr, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        _say("bench", f"no program under {ROOT / 'src'}: run from a "
             "checkout of the repository")
        return 2
    sys.path.insert(0, str(ROOT))
    from bench.lib import harness, peaks, registry

    harness.use_program(ROOT)
    bench = registry.load_benchmark(ROOT)
    wl = registry.workload(bench, args.workload)
    config = registry.load_config(bench, wl["config"], ROOT)
    traffic = registry.load_traffic(wl["traffic"], ROOT)
    ref = registry.reference(config["reference"], ROOT)
    runner = registry.runner(traffic["runner"], ROOT)

    import jax

    devices = jax.devices()
    dev = devices[0]
    tag = f"{dev.platform} {dev.device_kind} x{len(devices)}"
    if dev.platform != "tpu":
        _say(tag, "no TPU: this benchmark measures only on a TPU")
        return 1
    if len(devices) < wl["chips"]:
        _say(tag, f"{wl['name']} needs {wl['chips']} chips")
        return 1
    try:
        peak = peaks.peaks_for(dev.device_kind)
    except peaks.UnknownDevice as e:
        _say(tag, str(e))
        return 1
    from repro.launch.compile_cache import enable_compile_cache

    _say(tag, f"compile cache {enable_compile_cache()}")
    run = harness.Run(workload=wl, config=config, traffic=traffic, ref=ref,
                      seed=args.seed, seconds=args.seconds,
                      trace=bool(args.trace), chips=wl["chips"],
                      t_process=T_PROCESS, root=ROOT)
    res = runner.run(run)
    _say(tag, f"compiles in the window: {res['compiles_in_window']}")
    _say(tag, "end to end " + json.dumps(res["e2e"]))
    _say(tag, "counters " + json.dumps(
        {k: v for k, v in res["counters"].items()
         if not isinstance(v, list)}))
    _say(tag, "check " + json.dumps(res.get("check_info", {})))

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": res["memory_peak_bytes"]}
    e2e = dict(res["e2e"], setup_s=res["t0"] - T_PROCESS)
    out = {"correct": bool(res["correct"]), "attempted": res["attempted"],
           "failed": res["failed"]}
    if args.trace:
        from bench.lib import trace

        red = trace.reduce_file(trace.find_xplane(run.out_dir / "trace"))
        device.update(busy_s=red.busy_s, window_s=red.window_s)
        ctx = {"counters": res["counters"], "trace": red, "peaks": peak,
               "counts": ref.counts(config), "chips": wl["chips"]}
        metrics = {}
        for m in registry.cell_metrics(bench, wl["name"], "per_layer"):
            value = registry.metric_reader(m["name"], ROOT)(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        out["breakdown"] = {"device_ops": red.top_ops,
                            "idle_gaps": red.idle_gaps}
        _say(tag, "programs " + json.dumps(red.programs))
    else:
        metrics = {}
        for m in registry.cell_metrics(bench, wl["name"], "end_to_end"):
            if m["name"] in e2e:
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}
    out["metrics"] = metrics
    out["device"] = device
    out["checks"] = res["checks"]
    for name, c in res["checks"].items():
        _say(tag, f"check {name} = {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
