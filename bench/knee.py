#!/usr/bin/env python3
"""Find the knee of an open-loop serving cell: the highest arrival rate the
engine sustains without a growing backlog.

    python3 bench/knee.py --workload starcoder2-7b.chat-shared --rates 1,2,3,4 --seconds 30

One engine (one set of seeded weights) serves each rate in turn, with the
cell's traffic file at that rate, a lead-in, then a window of
``--seconds``.  For each rate it prints the completed tokens per second,
TTFT p50/p90 from the due time, and the backlog: requests waiting for a
slot, sampled after every engine step, averaged over the window's first
and last quarter.  A backlog that grows from the first quarter to the
last means the rate is past the knee.  The rate a cell uses is then
written into its traffic file as a number; nothing reads this script's
output at run time.
"""

import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    from bench.lib import harness, registry, stats, traffic, weights
    from bench.lib.harness import clock

    harness.use_program(ROOT)
    import jax

    from repro.launch.compile_cache import enable_compile_cache
    from repro.models import build_model
    from repro.serving import Request

    if jax.devices()[0].platform != "tpu":
        print("knee: no TPU", file=sys.stderr)
        return 1
    enable_compile_cache()
    serve = registry.runner("serve", ROOT)
    bench = registry.load_benchmark(ROOT)
    wl = registry.workload(bench, args.workload)
    config = registry.load_config(bench, wl["config"], ROOT)
    base = registry.load_traffic(wl["traffic"], ROOT)
    ref = registry.reference(config["reference"], ROOT)
    run = harness.Run(workload=wl, config=config, traffic=base, ref=ref,
                      seed=args.seed, seconds=args.seconds, trace=False,
                      chips=wl["chips"], t_process=clock(), root=ROOT)
    cfg = harness.program_config(config, ref)
    model = build_model(cfg)
    params = jax.jit(lambda k: weights.program_tree(
        model.param_structs(), ref.layout(config), k))(
            weights.seed_key(args.seed))
    engine = serve._engine(run, model, params)
    serve._warm_up(engine, Request, cfg.vocab_size,
                   config["serving"]["chunk"], args.seed)
    uid = 0
    for rate in (float(r) for r in args.rates.split(",")):
        t = dict(base, rate_per_s=rate)
        lead = t["lead_in_s"]
        mix = traffic.chat_open_loop(t, args.seed, cfg.vocab_size,
                                     lead + args.seconds)
        reqs = []
        for m in mix:
            reqs.append((m.due, Request(uid=uid, prompt=m.prompt,
                                        max_new_tokens=m.max_new_tokens)))
            uid += 1
        tracker = serve._Tracker(engine, ref.counts(config))
        backlog = []
        step = tracker.step

        def sampled(run_):
            out = step(run_)
            backlog.append((clock(), engine.waiting))
            return out

        tracker.step = sampled
        t_arrive = clock()
        t0, t1 = t_arrive + lead, t_arrive + lead + args.seconds
        serve._open_loop(run, tracker, reqs, t_arrive, t0, t1)
        due = {id(r): t_arrive + d for d, r in reqs}
        all_r = [r for _, r in reqs]
        ttft = stats.ttft_from_due(((due[id(r)], r.first_token_time)
                                    for r in all_r), t0, t1)
        tok = stats.tokens_in_window([r.token_times for r in all_r], t0, t1)
        q = args.seconds / 4
        first = [w for t_, w in backlog if t0 <= t_ < t0 + q]
        last = [w for t_, w in backlog if t1 - q <= t_ < t1]
        row = {"rate_per_s": rate, "tok_s": tok / args.seconds,
               "requests_due": len(ttft),
               "ttft_p50_ms": stats.percentile(ttft, 50) * 1e3,
               "ttft_p90_ms": stats.percentile(ttft, 90) * 1e3,
               "backlog_first_quarter": sum(first) / max(1, len(first)),
               "backlog_last_quarter": sum(last) / max(1, len(last)),
               "unfinished": sum(1 for r in all_r if not r.done)}
        print(json.dumps(row), flush=True)
        if row["backlog_last_quarter"] > row["backlog_first_quarter"] + 2:
            break                     # past the knee: higher rates are too
        while engine.active or engine.waiting:
            engine.step()             # drain before the next rate
    return 0


if __name__ == "__main__":
    sys.exit(main())
