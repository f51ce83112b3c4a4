"""Serving cells: ``ServeEngine`` driven by an open loop of timed
arrivals (``loop: open``) or by a closed loop of clients that each send
their next request when the last one finishes (``loop: closed``).

Set-up builds the seeded weights on the device in one jitted call and
the engine, then warms the cell's own shapes: one prefill chunk of
``[slots, chunk]``, one decode of ``[slots, 1]``, and the copy-on-write of
a shared block.  Then a lead-in brings the engine to steady state, and
the window measures.  Tokens are greedy, so after the window a sample of
the finished requests, the longest among them, is read back through the
float32 reference.
"""

from __future__ import annotations

import numpy as np

from bench.lib import check, flops, harness, stats, traffic, weights
from bench.lib.harness import clock


def _engine(run, model, params):
    import repro
    from repro.runtime import ServingPolicy
    from repro.serving import ServeEngine

    s = run.config["serving"]
    policy = ServingPolicy(cache="paged", block_size=s["block_size"],
                           prefix=s["prefix"], prefill_chunk=s["chunk"])
    with repro.session(serving=policy, tag=f"bench:{run.workload['name']}"):
        return ServeEngine(model, params, batch_slots=s["slots"],
                           max_seq=s["max_seq"])


def _warm_up(engine, request_cls, vocab: int, chunk: int, seed: int) -> int:
    """Compile the cell's shapes before the window: a prompt longer than
    one chunk, then one that shares it up to the middle of a block that
    the first one filled, so the follower's first write copies a shared
    block (copy-on-write).  Returns the copies made."""
    r = traffic.rng(seed, 7)
    first = r.integers(0, vocab, chunk + 64).tolist()
    second = first[:chunk + 39] + r.integers(0, vocab, 20).tolist()
    for uid, prompt in ((-1, first), (-2, second)):
        engine.submit(request_cls(uid=uid, prompt=prompt, max_new_tokens=2))
        engine.run_until_done()
    return engine.kv.cow_copies


def _warm_prefixes(engine, request_cls, mix, seed: int) -> None:
    """Put every shared system prompt of the mix in the prefix cache, as a
    deployment that has served it before holds it: one request each, of
    the system prompt and one more token."""
    r = traffic.rng(seed, 8)
    seen = {}
    for m in mix:
        seen.setdefault(m.meta["system"], m.prompt[:m.meta["system_len"]])
    for i, prompt in enumerate(seen.values()):
        extra = int(r.integers(0, engine.model.cfg.vocab_size))
        engine.submit(request_cls(uid=-10 - i, prompt=prompt + [extra],
                                  max_new_tokens=1))
    engine.run_until_done()


class _Tracker:
    """What the window sees of the engine, from outside it."""

    def __init__(self, engine, counts):
        self.engine = engine
        self.counts = counts
        self.inflight: list = []
        self.decode_steps: list[tuple[int, int]] = []   # (slots, keys)
        self.t0 = None

    def step(self, run) -> list:
        before = [(r, len(r.generated)) for r in self.inflight]
        with run.annotate("bench.engine_step"):
            done = self.engine.step()
        end = clock()
        if self.t0 is not None and self.t0 <= end:
            slots = keys = 0
            for r, n in before:
                if len(r.generated) > n:
                    slots += 1
                    keys += len(r.prompt) + len(r.generated) - 1
            if slots:
                self.decode_steps.append((slots, keys))
        self.inflight = [r for r in self.inflight if not r.done]
        return done

    def submit(self, run, req) -> None:
        with run.annotate("bench.submit"):
            self.engine.submit(req)
        self.inflight.append(req)

    def open_window(self, run, t0: float) -> None:
        e = self.engine
        self.t0 = t0
        self._window = run.annotate("bench.window")
        self._window.__enter__()
        self._start = (e.prefill_calls, e.prefill_tokens_saved)
        self._unadmitted = {id(r) for r in self.inflight if r.admit_seq < 0}

    def close_window(self) -> None:
        self._window.__exit__(None, None, None)

    def counters(self, all_reqs) -> dict:
        """Engine counters over the window, and the work of the requests
        admitted in it."""
        e = self.engine
        calls = e.prefill_calls - self._start[0]
        saved = e.prefill_tokens_saved - self._start[1]
        admitted = [r for r in all_reqs if r.admit_seq >= 0 and (
            id(r) in self._unadmitted or r.submit_time >= self.t0)]
        prompt_tokens = sum(len(r.prompt) - 1 for r in admitted)
        c = self.counts
        prefill_flops = sum(
            c.forward_flops(len(r.prompt) - 1,
                            flops.causal_keys(0, len(r.prompt) - 1))
            for r in admitted)
        prefill_flops -= 2.0 * c.matmul_params * saved
        decode_flops = sum(c.forward_flops(s, k)
                           for s, k in self.decode_steps)
        return {"prefill_calls": calls, "prefill_tokens_saved": saved,
                "admitted_prompt_tokens": prompt_tokens,
                "decode_steps": list(self.decode_steps),
                "model_flops": prefill_flops + decode_flops}


def _open_loop(run, tracker, reqs, t_arrive: float, t0: float, t1: float):
    """Submit each request when it is due; step while there is work;
    otherwise wait for the next arrival.  Returns the submit lags."""
    lags = []
    i = 0
    opened = False
    while True:
        now = clock()
        if not opened and now >= t0:
            tracker.open_window(run, t0)
            opened = True
        if now >= t1:
            tracker.close_window()
            break
        while i < len(reqs) and t_arrive + reqs[i][0] <= now:
            due, req = reqs[i]
            tracker.submit(run, req)
            lags.append((t_arrive + due, req.submit_time - (t_arrive + due)))
            i += 1
        if tracker.engine.active or tracker.engine.waiting:
            tracker.step(run)
        else:
            nxt = t_arrive + reqs[i][0] if i < len(reqs) else t1
            if not opened:
                nxt = min(nxt, t0)
            with run.annotate("bench.wait_arrival"):
                harness.sleep_until(min(nxt, t1))
    return lags


def _closed_loop(run, tracker, queues, t0: float, t1: float):
    """Each client sends its next request as soon as its last one is
    done; the window opens at ``t0``.  Returns every request sent."""
    queues = [list(q) for q in queues]
    current: dict = {}
    sent = []
    opened = False
    while True:
        now = clock()
        if not opened and now >= t0:
            tracker.open_window(run, t0)
            opened = True
        if now >= t1:
            tracker.close_window()
            break
        for ci, q in enumerate(queues):
            if q and (ci not in current or current[ci].done):
                current[ci] = q.pop(0)
                tracker.submit(run, current[ci])
                sent.append(current[ci])
        if tracker.engine.active or tracker.engine.waiting:
            tracker.step(run)
        else:
            with run.annotate("bench.wait_arrival"):
                harness.sleep_until(t0 if not opened else t1)
    return sent


def _sample(finished: list, check_cfg: dict, seed: int) -> list:
    """The finished request with most served tokens, then others drawn
    from the seed until ``tokens`` served tokens or ``requests``
    requests are in."""
    if not finished:
        return []
    longest = max(finished, key=lambda r: len(r.generated))
    rest = [r for r in finished if r is not longest]
    order = traffic.rng(seed, 9).permutation(len(rest))
    picked, n = [longest], len(longest.generated)
    for j in order:
        if n >= check_cfg["tokens"] or len(picked) >= check_cfg["requests"]:
            break
        picked.append(rest[j])
        n += len(rest[j].generated)
    return picked


def reference_gaps(run, picked, control: bool = False) -> dict:
    """Widest gap of the served tokens of ``picked`` under the reference
    (and, with ``control``, of the control's first choices)."""
    seqs = []
    for r in picked:
        toks = list(r.prompt) + list(r.generated)
        n = len(r.prompt)
        seqs.append((toks[:-1], list(range(n - 1, len(toks) - 1))))
    ref = run.ref.logits_at(run.config, run.seed, seqs)
    served = [np.asarray(r.generated) for r in picked]
    gaps = np.concatenate([check.token_gaps(lg, s)
                           for lg, s in zip(ref, served)])
    out = {"max_gap": float(gaps.max()), "tokens_checked": int(gaps.size),
           "requests_checked": len(picked)}
    if control:
        ctl = run.ref.logits_at(run.config, run.seed, seqs, quant="fp8")
        cg = np.concatenate([check.control_gaps(a, b)
                             for a, b in zip(ref, ctl)])
        out["control_max_gap"] = float(cg.max())
    return out


def run(run):
    import jax

    from repro.models import build_model
    from repro.serving import Request

    t = run.traffic
    s = run.config["serving"]
    cfg = harness.program_config(run.config, run.ref)
    model = build_model(cfg)
    lay = run.ref.layout(run.config)
    params = jax.jit(lambda k: weights.program_tree(
        model.param_structs(), lay, k))(weights.seed_key(run.seed))
    engine = _engine(run, model, params)
    cow = _warm_up(engine, Request, cfg.vocab_size, s["chunk"], run.seed)
    counter = harness.CompileCounter()
    tracker = _Tracker(engine, run.ref.counts(run.config))
    lead = t["lead_in_s"]

    if t["loop"] == "open":
        mix = traffic.chat_open_loop(t, run.seed, cfg.vocab_size,
                                     lead + run.seconds)
        reqs = [(m.due, Request(uid=i, prompt=m.prompt,
                                max_new_tokens=m.max_new_tokens))
                for i, m in enumerate(mix)]
        _check_fits(s["max_seq"], [r for _, r in reqs])
        _warm_prefixes(engine, Request, mix, run.seed)
        all_reqs = [r for _, r in reqs]
        if run.trace:
            harness.start_trace(run)
        t_arrive = clock()
        t0 = t_arrive + lead
        t1 = t0 + run.seconds
        counter.armed = True
        lags = _open_loop(run, tracker, reqs, t_arrive, t0, t1)
        due = {id(r): t_arrive + d for d, r in reqs}
    else:
        clients = traffic.closed_loop_clients(t, run.seed, cfg.vocab_size)
        queues, uid = [], 0
        for q in clients:
            queues.append([])
            for m in q:
                queues[-1].append(Request(uid=uid, prompt=m.prompt,
                                          max_new_tokens=m.max_new_tokens))
                uid += 1
        _check_fits(s["max_seq"], [r for q in queues for r in q])
        if run.trace:
            harness.start_trace(run)
        t0 = clock() + lead
        t1 = t0 + run.seconds
        counter.armed = True
        all_reqs = _closed_loop(run, tracker, queues, t0, t1)
        lags = []
        due = {id(r): r.submit_time for r in all_reqs}
    counter.close()
    if run.trace:
        harness.stop_trace()
    devices = jax.devices()[:run.chips]
    peak = harness.peak_bytes(devices)
    counters = tracker.counters(all_reqs)
    harness.free(params, engine.params, engine.cache, engine.kv.pools)
    del engine, params

    times = [r.token_times for r in all_reqs]
    ttft = stats.ttft_from_due(
        ((due[id(r)], r.first_token_time) for r in all_reqs
         if id(r) in due), t0, t1)
    gaps = stats.inter_token_gaps(times, t0, t1)
    tokens = stats.tokens_in_window(times, t0, t1)
    e2e = {"serve_tok_s": tokens / run.seconds}
    if ttft:
        for q in (50, 80, 90):
            e2e[f"ttft_p{q}_ms"] = stats.percentile(ttft, q) * 1e3
    if gaps:
        for q in (50, 99):
            e2e[f"itl_p{q}_ms"] = stats.percentile(gaps, q) * 1e3
    counters.update({
        "slots": s["slots"], "chunk": s["chunk"], "window_s": run.seconds,
        "gen_lag_s": [lag for d, lag in lags if t0 <= d < t1],
        "tokens": tokens, "ttft_samples": len(ttft),
        "itl_samples": len(gaps), "warm_up_cow_copies": cow})

    finished = [r for r in all_reqs
                if r.done and r.token_times and r.token_times[-1] < t1]
    picked = _sample(finished, t["check"], run.seed)
    numbers = (reference_gaps(run, picked, control=run.control)
               if picked else {})
    correct, checks = check.judge(numbers, run.limits())
    attempted = sum(1 for r in all_reqs if t0 <= due.get(id(r), -1) < t1)
    return {"t0": t0, "t1": t1, "e2e": e2e, "counters": counters,
            "correct": correct and bool(picked), "checks": checks,
            "check_info": {k: v for k, v in numbers.items()
                           if k not in checks},
            "attempted": attempted, "failed": 0,
            "memory_peak_bytes": peak,
            "compiles_in_window": counter.count}


def _check_fits(max_seq: int, reqs) -> None:
    for r in reqs:
        if len(r.prompt) + r.max_new_tokens > max_seq - 1:
            raise ValueError(f"request {r.uid}: {len(r.prompt)} + "
                             f"{r.max_new_tokens} tokens exceed max_seq "
                             f"{max_seq}")
