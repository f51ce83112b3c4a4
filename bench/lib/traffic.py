"""Traffic from the parameters of a data file and the run's seed.

The file fixes the work: lengths are quantiles of their distribution
(``stratified``), arrival gaps are quantiles of the exponential, and the
file's ``layout_seed`` decides how sizes pair up, in what order requests
come and which system prompt each one carries.  The run's seed draws the
token ids (and, elsewhere, the weights).  So every seed asks the same
work of the system on the same schedule, and the spread between runs is
the system's own.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from statistics import NormalDist

import numpy as np


def rng(seed: int, *stream: int) -> np.random.Generator:
    """A generator for any whole number and sub-stream."""
    seed = int(seed) % (1 << 64)
    return np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, *stream])


def stratified_lognormal(n: int, median: float, sigma: float, lo: int,
                         hi: int) -> np.ndarray:
    """``n`` integer lengths at the quantiles ``(i + 0.5) / n`` of a
    lognormal with this median and log-space sigma, clipped to
    ``[lo, hi]``, in ascending order."""
    nd = NormalDist()
    z = np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    vals = np.rint(median * np.exp(sigma * z))
    return np.clip(vals, lo, hi).astype(np.int64)


def stratified_uniform(n: int, lo: float, hi: float) -> np.ndarray:
    return lo + (hi - lo) * (np.arange(n) + 0.5) / n


def exponential_gaps(n: int, rate: float) -> np.ndarray:
    """``n`` gaps at the quantiles of an exponential with this rate."""
    u = (np.arange(n) + 0.5) / n
    return -np.log1p(-u) / rate


def zipf_counts(n: int, k: int, s: float) -> np.ndarray:
    """How many of ``n`` draws fall on each of ``k`` ranks under Zipf(s),
    by largest remainder, so the counts are fixed and sum to ``n``."""
    w = 1.0 / np.arange(1, k + 1) ** s
    exact = n * w / w.sum()
    counts = np.floor(exact).astype(np.int64)
    rest = n - counts.sum()
    counts[np.argsort(-(exact - counts), kind="stable")[:rest]] += 1
    return counts


@dataclass
class ServeRequest:
    """One request of a serving mix.  ``due`` is seconds after the
    arrivals start (open loop) or None (closed loop: due when sent)."""

    prompt: list[int]
    max_new_tokens: int
    due: float | None = None
    meta: dict = field(default_factory=dict)


def _lengths(spec: dict, n: int) -> np.ndarray:
    return stratified_lognormal(n, spec["median"], spec["sigma"],
                                spec["min"], spec["max"])


def chat_open_loop(params: dict, seed: int, vocab: int,
                   duration_s: float) -> list[ServeRequest]:
    """Poisson arrivals at ``rate_per_s`` for ``duration_s`` seconds of
    shared system prompts plus user turns (see the traffic file)."""
    rate = params["rate_per_s"]
    n = max(1, math.ceil(rate * duration_s))
    layout = rng(params["layout_seed"])
    sysp = params["system_prompts"]
    k = sysp["count"]
    sys_len = np.rint(stratified_uniform(k, sysp["min"], sysp["max"])
                      ).astype(np.int64)
    sys_len = sys_len[layout.permutation(k)]            # rank -> length
    ranks = np.repeat(np.arange(k), zipf_counts(n, k, sysp["zipf_s"]))
    user = _lengths(params["user_turn"], n)[layout.permutation(n)]
    out = _lengths(params["output"], n)[layout.permutation(n)]
    order = layout.permutation(n)
    gaps = exponential_gaps(n, rate)[layout.permutation(n)]
    due = np.cumsum(gaps) - gaps[0]
    tok = rng(seed, 2)
    prompts = [tok.integers(0, vocab, int(L)).tolist() for L in sys_len]
    reqs = []
    for i, j in enumerate(order):
        rank = int(ranks[j])
        turn = tok.integers(0, vocab, int(user[j])).tolist()
        reqs.append(ServeRequest(prompt=prompts[rank] + turn,
                                 max_new_tokens=int(out[j]),
                                 due=float(due[i]),
                                 meta={"system": rank,
                                       "system_len": int(sys_len[rank])}))
    return reqs


def closed_loop_clients(params: dict, seed: int, vocab: int
                        ) -> list[list[ServeRequest]]:
    """``clients`` queues of requests sent back to back.  Each client's
    first request starts part-way through its answer: the part already
    produced is folded into its prompt and the rest is left to decode,
    so the window opens with contexts spread as in steady state."""
    c = params["clients"]
    per = params["requests_per_client"]
    n = c * per
    layout = rng(params["layout_seed"])
    prompt = _lengths(params["prompt"], n)[layout.permutation(n)]
    out = _lengths(params["output"], n)[layout.permutation(n)]
    residual = stratified_uniform(c, 0.0, 1.0)[layout.permutation(c)]
    order = layout.permutation(n).reshape(c, per)
    tok = rng(seed, 2)
    clients = []
    for ci in range(c):
        queue = []
        for qi, j in enumerate(order[ci]):
            p_len, o_len = int(prompt[j]), int(out[j])
            done = 0
            if qi == 0:
                left = max(1, int(math.ceil(residual[ci] * o_len)))
                done = o_len - left
                o_len = left
            ids = tok.integers(0, vocab, p_len + done).tolist()
            queue.append(ServeRequest(prompt=ids, max_new_tokens=o_len,
                                      meta={"folded": done}))
        clients.append(queue)
    return clients


def packed_rows(params: dict, seed: int, vocab: int, step: int
                ) -> tuple[np.ndarray, np.ndarray]:
    """One training batch: ``batch`` rows of ``seq`` tokens, packed from
    documents of lognormal length, each ended by ``eos_id``.  Tokens
    follow a Zipf law over a fixed shuffle of the vocabulary, so there is
    something to learn.  Step ``step`` of seed ``seed`` is always the
    same batch, and no two steps share a row."""
    b, s = params["batch"], params["seq"]
    docs = params["documents"]
    r = rng(seed, 3, step)
    vocab_order = rng(params["layout_seed"]).permutation(vocab)
    need = b * (s + 1)
    parts, total = [], 0
    while total < need:
        n = int(np.clip(np.rint(docs["median"] * np.exp(
            docs["sigma"] * r.standard_normal())), docs["min"], docs["max"]))
        ranks = np.minimum(r.zipf(docs["zipf_s"], n) - 1, vocab - 1)
        parts.append(vocab_order[ranks])
        parts.append(np.array([docs["eos_id"]]))
        total += n + 1
    flat = np.concatenate(parts)[:need].reshape(b, s + 1).astype(np.int32)
    return flat[:, :-1], flat[:, 1:]
