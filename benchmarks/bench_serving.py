"""Serving benchmark: staggered mixed-length arrivals through the
ServeEngine, dense vs paged KV cache, per scheduler — plus a
prefix-sharing section under a Poisson arrival trace.

Measures, per scenario:
 * tokens/s (decode throughput over the whole trace),
 * time-to-first-token (mean/p-max over requests, submit -> first token),
 * jitted calls: decode steps and prefill calls per admission — the
   chunked-prefill claim is visible here: the legacy path pays
   O(prompt_len) one-token decodes per admission, the chunked path
   O(prompt_len / chunk),
 * preemptions and block-pool stats (paged scenarios),
 * full Session/ServingPolicy provenance via ``engine.describe()``.

The sharing section drives N requests with a common 32-token system
prompt (Poisson arrivals by default, ``--trace staggered`` for the
legacy stream) through sharing-off vs sharing-on paged engines and a
2-replica prefix-affinity router, reports prefill-tokens-saved and
follower TTFT, and *asserts* the decoded tokens are identical.

Run:  PYTHONPATH=src python benchmarks/bench_serving.py [--quick]
                       [--out serving.json] [--arch codeqwen1.5-7b]
                       [--trace poisson|staggered]

The JSON output is uploaded as a CI artifact (see .github/workflows)
to start a serving-perf trajectory across PRs.
"""

from __future__ import annotations

import argparse

import jax
import numpy as np

import bench_artifact
import repro
from repro import obs
from repro.configs.base import get_config
from repro.core.tensor import ops
from repro.launch.compile_cache import enable_compile_cache
from repro.models import build_model
from repro.runtime import ServingPolicy
from repro.serving import FixedProposer, Request, Router, ServeEngine


def make_workload(n_requests: int, max_new: int, seed: int = 0):
    """Mixed-length prompts with staggered arrival steps."""
    rng = np.random.default_rng(seed)
    reqs = []
    for uid in range(n_requests):
        length = int(rng.integers(3, 28))
        prompt = [int(t) for t in rng.integers(1, 60, size=length)]
        arrival = int(rng.integers(0, 3)) + 2 * uid   # staggered stream
        reqs.append((arrival, Request(uid=uid, prompt=prompt,
                                      max_new_tokens=max_new,
                                      priority=int(rng.integers(0, 3)))))
    return sorted(reqs, key=lambda ar: ar[0])


def make_shared_workload(n_requests: int, max_new: int, *,
                         shared_len: int = 32, trace: str = "poisson",
                         rate: float = 0.5, seed: int = 7):
    """N requests sharing a ``shared_len``-token system prompt.

    Every prompt is the same system prefix plus a short unique tail, so
    a prefix-sharing cache prefills the system prompt once and maps it
    into every follower.  Arrivals are a Poisson process (exponential
    inter-arrival gaps, ``rate`` requests per engine step) by default,
    or the legacy staggered stream with ``trace="staggered"``.
    """
    rng = np.random.default_rng(seed)
    system = [int(t) for t in rng.integers(1, 60, size=shared_len)]
    if trace == "poisson":
        gaps = rng.exponential(scale=1.0 / rate, size=n_requests)
        arrivals = np.floor(np.cumsum(gaps)).astype(int)
    elif trace == "staggered":
        arrivals = np.array([2 * uid for uid in range(n_requests)])
    else:
        raise ValueError(f"unknown trace {trace!r}")
    reqs = []
    for uid in range(n_requests):
        tail = [int(t) for t in rng.integers(1, 60,
                                             size=int(rng.integers(4, 9)))]
        reqs.append((int(arrivals[uid]),
                     Request(uid=uid, prompt=system + tail,
                             max_new_tokens=max_new)))
    return sorted(reqs, key=lambda ar: ar[0])


def drive(engine: ServeEngine, workload, max_steps: int = 5000):
    """Submit requests at their arrival step; run to completion."""
    pending = list(workload)
    done = []
    t0 = obs.now()
    for step in range(max_steps):
        while pending and pending[0][0] <= step:
            engine.submit(pending.pop(0)[1])
        done.extend(engine.step())
        if not pending and not engine.active and not engine.waiting:
            break
    wall = obs.now() - t0
    return done, wall


def _fresh(workload):
    """Copy a workload so every scenario decodes the same requests."""
    return [(a, Request(uid=r.uid, prompt=list(r.prompt),
                        max_new_tokens=r.max_new_tokens,
                        priority=r.priority))
            for a, r in workload]


def run_scenario(name: str, model, params, policy: ServingPolicy, *,
                 slots: int, max_seq: int, workload) -> dict:
    with repro.session(tag=f"bench_serving:{name}"):
        engine = ServeEngine(model, params, batch_slots=slots,
                             max_seq=max_seq, policy=policy)
    done, wall = drive(engine, _fresh(workload))
    toks = sum(len(r.generated) for r in done)
    ttfts = [r.first_token_time - r.submit_time for r in done
             if r.first_token_time is not None]
    admissions = len(done) + engine.preemptions
    prompt_tokens = sum(len(r.prompt) for _, r in workload)
    out = {
        "scenario": name,
        "requests": len(done),
        "tokens": toks,
        "wall_s": round(wall, 3),
        "tok_per_s": round(toks / wall, 1) if wall > 0 else None,
        "ttft_mean_s": round(float(np.mean(ttfts)), 4) if ttfts else None,
        "ttft_max_s": round(float(np.max(ttfts)), 4) if ttfts else None,
        "decode_calls": engine.decode_calls,
        "prefill_calls": engine.prefill_calls,
        "prefill_calls_per_admission":
            round(engine.prefill_calls / max(1, admissions), 2),
        "prompt_tokens": prompt_tokens,
        "preemptions": engine.preemptions,
        "provenance": engine.describe(),
    }
    return out


def run_sharing_scenario(name: str, model, params, policy: ServingPolicy, *,
                         slots: int, max_seq: int, workload,
                         replicas: int = 1) -> tuple[dict, dict]:
    """Drive the shared-prompt trace; return (stats, tokens-by-uid).

    Tracks TTFT per request so the leader (first arrival, pays the full
    system-prompt prefill) can be separated from the followers (whose
    prefill the sharing cache shortens).  With ``replicas > 1`` the same
    trace goes through a :class:`Router` instead of a single engine.
    """
    with repro.session(tag=f"bench_serving:{name}"):
        engines = [ServeEngine(model, params, batch_slots=slots,
                               max_seq=max_seq, policy=policy)
                   for _ in range(replicas)]
    fresh = _fresh(workload)
    if replicas == 1:
        done, wall = drive(engines[0], fresh)
    else:
        router = Router(engines)
        pending = list(fresh)
        done = []
        t0 = obs.now()
        for step in range(5000):
            while pending and pending[0][0] <= step:
                router.submit(pending.pop(0)[1])
            done.extend(router.step())
            if not pending and not any(e.active or e.waiting
                                       for e in engines):
                break
        wall = obs.now() - t0
    toks = sum(len(r.generated) for r in done)
    leader_uid = fresh[0][1].uid
    ttft = {r.uid: r.first_token_time - r.submit_time for r in done
            if r.first_token_time is not None}
    follower = [t for uid, t in ttft.items() if uid != leader_uid]
    saved = sum(e.prefill_tokens_saved for e in engines)
    stats = {
        "scenario": name,
        "requests": len(done),
        "replicas": replicas,
        "tokens": toks,
        "wall_s": round(wall, 3),
        "tok_per_s": round(toks / wall, 1) if wall > 0 else None,
        "ttft_mean_s": (round(float(np.mean(list(ttft.values()))), 4)
                        if ttft else None),
        "ttft_follower_mean_s": (round(float(np.mean(follower)), 4)
                                 if follower else None),
        "prefill_calls": sum(e.prefill_calls for e in engines),
        "prefill_tokens_saved": saved,
        "shared_admissions": sum(e.shared_admissions for e in engines),
        "preemptions": sum(e.preemptions for e in engines),
        "provenance": engines[0].describe(),
    }
    return stats, {r.uid: list(r.generated) for r in done}


def run_sharing_section(model, params, *, slots: int, max_seq: int,
                        n_req: int, max_new: int, trace: str,
                        chunk: int) -> dict:
    """Sharing-off vs sharing-on vs routed, same shared-prompt trace.

    Asserts the decoded tokens are identical across all three paths and
    that sharing actually saved prefill work — the bench doubles as the
    acceptance check for the prefix-sharing serving stack.
    """
    workload = make_shared_workload(n_req, max_new, trace=trace)
    base = dict(cache="paged", scheduler="fifo", block_size=8,
                prefill_chunk=chunk)
    runs = [
        ("shared-prompt-sharing-off", ServingPolicy(**base), 1),
        ("shared-prompt-sharing-on",
         ServingPolicy(**base, prefix=True), 1),
        ("shared-prompt-router-2x",
         ServingPolicy(**base, prefix=True, routing="prefix_affinity"), 2),
    ]
    results, tokens = [], {}
    for name, policy, replicas in runs:
        stats, gen = run_sharing_scenario(
            name, model, params, policy, slots=slots, max_seq=max_seq,
            workload=workload, replicas=replicas)
        results.append(stats)
        tokens[name] = gen
        print(f"[{name:>28s}] {stats['tokens']:4d} tok at "
              f"{stats['tok_per_s']:8.1f} tok/s | "
              f"ttft {stats['ttft_mean_s']}s "
              f"(followers {stats['ttft_follower_mean_s']}s) | "
              f"prefill saved {stats['prefill_tokens_saved']} tok | "
              f"shared admissions {stats['shared_admissions']}")
    off, on, routed = results
    gen_off = tokens["shared-prompt-sharing-off"]
    for other in ("shared-prompt-sharing-on", "shared-prompt-router-2x"):
        assert tokens[other] == gen_off, \
            f"{other} decoded different tokens than sharing-off"
    assert off["prefill_tokens_saved"] == 0
    assert on["prefill_tokens_saved"] > 0, \
        "sharing-on saved no prefill tokens on a shared-prompt trace"
    assert routed["prefill_tokens_saved"] > 0
    print(f"\nprefix sharing: {on['prefill_tokens_saved']} prefill tokens "
          f"saved across {on['shared_admissions']} shared admissions; "
          f"follower ttft {off['ttft_follower_mean_s']}s -> "
          f"{on['ttft_follower_mean_s']}s; decoded tokens identical "
          "across sharing-off / sharing-on / routed")
    return {"trace": trace, "shared_prompt_tokens": 32,
            "requests": n_req, "results": results}


def run_obs_section(model, params, *, slots: int, max_seq: int,
                    n_req: int, max_new: int, chunk: int,
                    trace_path: str) -> dict:
    """Drive one paged scenario with observability on; export the trace.

    The same run exercises all three instrumented layers — the serving
    engine (request lifecycle spans/instants), the paged KV cache's
    memory telemetry bridge (``mem.alloc``/``mem.free``,
    ``kv.grow``), and the graph compiler (a small ``repro.compile``
    function called twice: trace/pass/lower spans on the miss, a
    program-cache-hit counter on the replay).  Asserts:

    * the exported JSON passes the Chrome trace-event schema validator
      (i.e. Perfetto will load it),
    * span/instant names from all three layers are present, and
    * TTFT / inter-token percentiles computed from the trace by
      ``repro.obs.summarize`` match the benchmark's own numbers
      (``Request`` timestamps) within 1%.
    """
    from repro.obs import save_trace, validate_chrome_trace
    from repro.obs.summarize import summarize

    workload = make_workload(n_req, max_new, seed=3)
    policy = ServingPolicy(cache="paged", scheduler="fifo", block_size=8,
                           prefill_chunk=chunk)

    @repro.compile
    def poly(x, y):
        return ops.tanh(ops.add(ops.mul(x, y), x))

    with repro.session(obs=True, tag="bench_serving:obs") as sess:
        a = np.linspace(-1.0, 1.0, 4096, dtype=np.float32)
        poly(a, a)                       # compiler layer: trace + lower
        poly(a + 1.0, a - 1.0)           # program-cache hit
        engine = ServeEngine(model, params, batch_slots=slots,
                             max_seq=max_seq, policy=policy)
        done, wall = drive(engine, _fresh(workload))
        tracer = obs.get_tracer(sess)

    assert tracer is not None, "session(obs=True) produced no tracer"
    trace = save_trace(tracer, trace_path)
    errors = validate_chrome_trace(trace)
    assert not errors, f"exported trace fails schema validation: {errors}"

    span_names = {s.name for s in tracer.spans}
    inst_names = {i.name for i in tracer.instants}
    for want in ("serve.step", "serve.decode_step",        # serving
                 "kv.grow",                                # memory
                 "compiler.trace", "compiler.lower"):      # compiler
        assert want in span_names, f"missing span {want!r} in trace"
    for want in ("request.submit", "request.first_token", "request.done",
                 "mem.alloc"):
        assert want in inst_names, f"missing instant {want!r} in trace"
    hits = tracer.metrics.snapshot()["counters"]
    assert hits.get("compiler.program_cache_hit", 0) >= 1

    # the trace-side latency summary must agree with the benchmark's own
    # Request-timestamp numbers within 1%
    summary = summarize(trace)
    ttfts = [r.first_token_time - r.submit_time for r in done
             if r.first_token_time is not None]
    inter = []
    for r in done:
        ts = sorted(r.token_times)
        inter.extend(b - a for a, b in zip(ts, ts[1:]))

    def check(name, bench_vals, dist):
        assert dist["count"] == len(bench_vals), \
            (name, dist["count"], len(bench_vals))
        for q in (50, 90, 99):
            want = float(np.percentile(bench_vals, q))
            got = dist[f"p{q}"]
            assert abs(got - want) <= 0.01 * abs(want) + 1e-9, \
                f"{name} p{q}: trace {got} vs bench {want}"

    check("ttft", ttfts, summary["requests"]["ttft_s"])
    check("inter_token", inter, summary["requests"]["inter_token_s"])

    toks = sum(len(r.generated) for r in done)
    print(f"[{'obs-on-paged-fifo':>28s}] {toks:4d} tok in {wall:7.2f}s | "
          f"{len(tracer.spans)} spans + {len(tracer.instants)} instants "
          f"-> {trace_path} (schema ok, "
          f"ttft/inter-token match bench within 1%)")
    return {"requests": len(done), "tokens": toks,
            "spans": len(tracer.spans), "instants": len(tracer.instants),
            "dropped_events": tracer.dropped,
            "trace_path": trace_path,
            "ttft_s": summary["requests"]["ttft_s"],
            "inter_token_s": summary["requests"]["inter_token_s"],
            "metrics": tracer.metrics.snapshot()}


def make_spec_workload(n_requests: int, max_new: int, seed: int = 11):
    """Short prompts, longer generations: greedy decode from a tiny
    model settles into short cycles, which n-gram self-drafting then
    predicts — the regime where wide verify amortizes per-step dispatch.
    """
    rng = np.random.default_rng(seed)
    reqs = []
    for uid in range(n_requests):
        length = int(rng.integers(5, 12))
        prompt = [int(t) for t in rng.integers(1, 60, size=length)]
        reqs.append((2 * uid, Request(uid=uid, prompt=prompt,
                                      max_new_tokens=max_new)))
    return reqs


def run_spec_scenario(name: str, model, params, policy: ServingPolicy, *,
                      slots: int, max_seq: int, workload, warmup,
                      proposer=None) -> tuple[dict, dict]:
    """Drive the trace on one engine; return (stats, tokens-by-uid).

    The warmup trace runs first on the *same* engine (jit caches are
    per-engine) so the timed run measures steady-state decode, not
    compilation.
    """
    with repro.session(tag=f"bench_serving:{name}"):
        engine = ServeEngine(model, params, batch_slots=slots,
                             max_seq=max_seq, policy=policy,
                             proposer=proposer)
    drive(engine, _fresh(warmup))          # compile-only wave, untimed
    done, wall = drive(engine, _fresh(workload))
    toks = sum(len(r.generated) for r in done)
    spec = engine.describe()["speculative"]
    stats = {
        "scenario": name,
        "requests": len(done),
        "tokens": toks,
        "wall_s": round(wall, 3),
        "tok_per_s": round(toks / wall, 1) if wall > 0 else None,
        "decode_calls": engine.decode_calls,
        "verify_calls": spec["verify_calls"],
        "spec_rounds": spec["rounds"],
        "accepted_tokens": spec["accepted_tokens"],
        "rejected_tokens": spec["rejected_tokens"],
        "accepted_per_step": spec["accepted_per_step"],
        "rollback_blocks_freed": (engine.kv.rollback_blocks_freed
                                  if engine.paged else 0),
        "provenance": engine.describe(),
    }
    return stats, {r.uid: list(r.generated) for r in done}


def run_spec_section(model, params, *, slots: int, max_seq: int,
                     n_req: int, max_new: int, chunk: int) -> dict:
    """Speculative decode vs one-token decode, same trace, three drafts.

    * ``spec-off-one-token`` — the baseline.
    * ``spec-ngram-k4`` — n-gram self-drafting.  An *untrained* target
      never repeats itself, so acceptance here is near the floor; the
      scenario checks identity and reports honest self-draft acceptance.
    * ``spec-oracle-k4`` — a ``FixedProposer`` replaying the baseline's
      own continuation (a perfect draft).  Every emitted token still
      comes from the target's argmax through the full verify/rollback
      path; the oracle only controls the acceptance rate, isolating the
      engine-mechanics speedup of wide verify at high acceptance.

    Asserts greedy tokens are bit-identical across all three and that
    the high-acceptance run beats one-token decode by >= 1.3x
    end-to-end — the acceptance check for the speculative stack.
    """
    workload = make_spec_workload(n_req, max_new)
    warmup = make_spec_workload(2, 8, seed=12)
    base = dict(cache="paged", scheduler="fifo", block_size=8,
                prefill_chunk=chunk)
    spec_policy = ServingPolicy(**base, speculative=dict(
        enabled=True, k=4, draft="ngram", ngram=3))
    plain, gen_plain = run_spec_scenario(
        "spec-off-one-token", model, params, ServingPolicy(**base),
        slots=slots, max_seq=max_seq, workload=workload, warmup=warmup)
    ngram, gen_ngram = run_spec_scenario(
        "spec-ngram-k4", model, params, spec_policy,
        slots=slots, max_seq=max_seq, workload=workload, warmup=warmup)

    # oracle replay: full greedy sequence per request, continuation
    # looked up by matching the slot context against a sequence prefix
    seqs = [list(r.prompt) + list(gen_plain[r.uid]) for _, r in workload]

    def replay(ctx):
        n = len(ctx)
        for seq in seqs:
            if len(seq) >= n and seq[:n] == ctx:
                return seq[n:]
        return []

    oracle, gen_oracle = run_spec_scenario(
        "spec-oracle-k4", model, params, spec_policy,
        slots=slots, max_seq=max_seq, workload=workload, warmup=warmup,
        proposer=FixedProposer(replay))

    for stats in (plain, ngram, oracle):
        print(f"[{stats['scenario']:>28s}] {stats['tokens']:4d} tok in "
              f"{stats['wall_s']:7.2f}s = {stats['tok_per_s']:8.1f} tok/s"
              f" | verify {stats['verify_calls']} / decode "
              f"{stats['decode_calls']} calls | accepted/step "
              f"{stats['accepted_per_step']}")
    assert gen_ngram == gen_plain, \
        "ngram speculative decode emitted different greedy tokens"
    assert gen_oracle == gen_plain, \
        "oracle speculative decode emitted different greedy tokens"
    assert oracle["accepted_per_step"] > 2.0, \
        "oracle draft should accept most proposals"
    speedup = plain["wall_s"] / max(oracle["wall_s"], 1e-9)
    print(f"\nspeculative decode: {oracle['accepted_per_step']} accepted "
          f"tokens/step at oracle draft ({ngram['accepted_per_step']} "
          f"ngram self-draft), {oracle['verify_calls']} verify vs "
          f"{plain['decode_calls']} one-token calls, "
          f"{oracle['rollback_blocks_freed'] + ngram['rollback_blocks_freed']}"
          f" blocks rolled back; {speedup:.2f}x end-to-end, "
          "greedy tokens identical across all drafts")
    assert speedup >= 1.3, \
        f"speculative speedup {speedup:.2f}x < 1.3x over one-token decode"
    return {"requests": n_req, "max_new": max_new,
            "speedup": round(speedup, 2),
            "results": [plain, ngram, oracle]}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="small model + short trace (CI smoke)")
    ap.add_argument("--out", default=None, help="write JSON here")
    ap.add_argument("--arch", default="codeqwen1.5-7b")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=64)
    ap.add_argument("--requests", type=int, default=None)
    ap.add_argument("--max-new", type=int, default=None)
    ap.add_argument("--trace", default="poisson",
                    choices=("poisson", "staggered"),
                    help="arrival process for the sharing section")
    ap.add_argument("--obs-trace", metavar="PATH", default=None,
                    help="run an observability-on scenario and write a "
                    "Perfetto-loadable Chrome trace JSON to PATH")
    args = ap.parse_args()
    enable_compile_cache()

    overrides = {}
    if args.quick:
        overrides = dict(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
                         d_ff=128, vocab_size=64)
    cfg = get_config(args.arch, reduced=True, **overrides)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    n_req = args.requests or (6 if args.quick else 12)
    max_new = args.max_new or (6 if args.quick else 16)
    workload = make_workload(n_req, max_new)
    chunk = 8

    # ~half the blocks a full complement of slots could want: the
    # priority scenario exercises evict + requeue under real pressure
    tight_pool = 2 * args.slots + 1
    scenarios = [
        ("dense-fifo-legacy-prefill",
         ServingPolicy(cache="dense", scheduler="fifo", prefill_chunk=0)),
        ("dense-fifo-chunked",
         ServingPolicy(cache="dense", scheduler="fifo",
                       prefill_chunk=chunk)),
        ("paged-fifo",
         ServingPolicy(cache="paged", scheduler="fifo", block_size=16,
                       prefill_chunk=chunk)),
        ("paged-sjf",
         ServingPolicy(cache="paged", scheduler="sjf", block_size=16,
                       prefill_chunk=chunk)),
        ("paged-priority-tight-pool",
         ServingPolicy(cache="paged", scheduler="priority", block_size=16,
                       num_blocks=tight_pool, prefill_chunk=chunk)),
    ]

    results = []
    for name, policy in scenarios:
        res = run_scenario(name, model, params, policy, slots=args.slots,
                           max_seq=args.max_seq, workload=workload)
        results.append(res)
        print(f"[{name:>28s}] {res['tokens']:4d} tok in "
              f"{res['wall_s']:7.2f}s = {res['tok_per_s']:8.1f} tok/s | "
              f"ttft {res['ttft_mean_s']}s | "
              f"prefill calls/admission {res['prefill_calls_per_admission']}"
              f" | preempt {res['preemptions']}")

    legacy = results[0]
    chunked = results[1]
    print(f"\nchunked prefill: {chunked['prefill_calls']} jitted prefill "
          f"calls vs {legacy['prefill_calls']} legacy one-token calls "
          f"({legacy['prefill_calls'] / max(1, chunked['prefill_calls']):.1f}"
          f"x fewer compiled-call dispatches per admission stream)")

    print()
    sharing = run_sharing_section(model, params, slots=args.slots,
                                  max_seq=args.max_seq, n_req=8,
                                  max_new=max_new, trace=args.trace,
                                  chunk=chunk)

    print()
    speculative = run_spec_section(model, params, slots=args.slots,
                                   max_seq=max(args.max_seq, 64),
                                   n_req=6 if args.quick else 8,
                                   max_new=48, chunk=chunk)

    payload = {"arch": cfg.name, "slots": args.slots,
               "max_seq": args.max_seq, "prefill_chunk": chunk,
               "results": results, "sharing": sharing,
               "speculative": speculative}

    if args.obs_trace:
        print()
        payload["observability"] = run_obs_section(
            model, params, slots=args.slots, max_seq=args.max_seq,
            n_req=min(n_req, 6), max_new=max_new, chunk=chunk,
            trace_path=args.obs_trace)

    bench_artifact.emit("serving", payload, out=args.out,
                        quick=args.quick, echo=not args.out)


if __name__ == "__main__":
    main()
