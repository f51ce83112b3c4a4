"""Deferred-execution fusion win (the ArrayFire-JIT reproduction, Fig. 2),
now measured through the ``repro.compiler`` pipeline.

Three sections:

 * chain     — elementwise chains: eager (one XLA dispatch per op) vs the
   legacy lazy path (node-at-a-time) vs the full pipeline (CSE + fusion
   collapse the chain into generated cluster kernels);
 * attention — plain-ops ``softmax(QK^T * scale)V`` variants through the
   attention matcher: the generated template kernel vs the hand-written
   ``kernels.flash_attention`` vs the unfused per-op path (kernel counts
   + steady-state wall time; the template must stay within 1.25x of the
   hand-written kernel);
 * epilogue  — ``gelu(x @ w + b)``: the fused matmul-epilogue kernel (one
   dispatch) vs the unfused per-op path (>= 3 dispatches).

Reported per scenario: wall time, dispatched-call counts, generated-kernel
counts, and per-pass node reductions (the PassManager's own stats).

Run:  PYTHONPATH=src python benchmarks/bench_fusion.py [--quick]
                       [--out fusion.json] [--n-ops 16] [--iters 20]

The JSON output is uploaded as a CI artifact (next to bench_serving's)
to start a compiler-perf trajectory across PRs.
"""

from __future__ import annotations

import argparse
import functools
import time

import jax
import jax.numpy as jnp

import bench_artifact
import repro
from repro.core.tensor import ops
from repro.launch.compile_cache import enable_compile_cache
from repro.runtime import CompilerPolicy


def _chain(x, n):
    for _ in range(n):
        x = ops.mul(ops.add(x, x), ops.full_like(x, 0.5))
        x = ops.tanh(x)
    return x


def _time(fn, iters, repeat: int = 1):
    """Mean seconds per call, min over ``repeat`` measurement blocks."""
    out = fn()                       # warm up (trace/compile/jit)
    jax.block_until_ready(out)
    best = float("inf")
    for _ in range(repeat):
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn()
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / iters)
    return best, out


def bench(n_ops: int = 16, iters: int = 20, side: int = 256) -> dict:
    x = jnp.ones((side, side))

    t_eager, ref = _time(lambda: _chain(x, n_ops), iters)

    def lazy_run(policy):
        lb_holder = {}

        def run():
            with repro.session(backend="lazy", compiler=policy,
                               tag="bench_fusion") as sess:
                lb = sess.backend_instance()
                lb_holder["lb"] = lb
                return ops.materialize(_chain(x, n_ops))

        t, out = _time(run, iters)
        lb = lb_holder["lb"]
        return t, out, lb.last_compile_report, lb

    t_legacy, out_legacy, rep_legacy, _ = lazy_run(CompilerPolicy.legacy())
    t_comp, out_comp, rep_comp, lb = lazy_run(CompilerPolicy())

    import numpy as np
    exact = bool((np.asarray(out_comp) == np.asarray(ref)).all())

    passes = {p["pass"]: {"nodes_before": p["nodes"][0],
                          "nodes_after": p["nodes"][1],
                          "removed": p["nodes"][0] - p["nodes"][1],
                          **{k: v for k, v in p.items()
                             if k not in ("pass", "nodes", "edges")}}
              for p in rep_comp["passes"]}
    return {
        "n_ops": 3 * n_ops,
        "shape": [side, side],
        "eager_s": t_eager,
        "lazy_legacy_s": t_legacy,
        "compiled_s": t_comp,
        "speedup_vs_eager": t_eager / t_comp,
        "speedup_vs_legacy": t_legacy / t_comp,
        "legacy_dispatches": rep_legacy["dispatches"],
        "compiled_dispatches": rep_comp["dispatches"],
        "pallas_kernels": rep_comp["pallas_kernels"],
        "program_cache_hits": lb.program_cache_hits,
        "numerics_exact_vs_eager": exact,
        "passes": passes,
    }


def _attn_program(q, k, v, scale):
    s0 = ops.matmul(q, ops.transpose(k, (0, 2, 1)))
    s = ops.mul(s0, ops.full_like(s0, scale))
    m = ops.max(s, axis=-1, keepdims=True)
    e = ops.exp(ops.sub(s, ops.stop_gradient(m)))
    p = ops.div(e, ops.sum(e, axis=-1, keepdims=True))
    return ops.matmul(p, v)


def bench_attention(iters: int = 10, b: int = 1, h: int = 4, s: int = 256,
                    d: int = 64) -> dict:
    """Generated attention template vs hand-written flash_attention vs
    the unfused per-op path, at [B*H, S, D]."""
    from repro.kernels.flash_attention import flash_attention

    scale = 1.0 / (d ** 0.5)
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    bh = b * h
    q = jax.random.normal(keys[0], (bh, s, d), jnp.float32)
    k = jax.random.normal(keys[1], (bh, s, d), jnp.float32)
    v = jax.random.normal(keys[2], (bh, s, d), jnp.float32)
    interpret = jax.default_backend() != "tpu"

    # generated: plain ops through the attention matcher -> one template
    compiled = repro.compile(lambda a, b_, c: _attn_program(a, b_, c, scale))
    t_template, out_t = _time(lambda: compiled(q, k, v), iters, repeat=3)
    exe = compiled.last_executable
    kinds = [c["kind"] for c in exe.describe()["clusters"]]

    # hand-written flash kernel on the same problem ([B, H, S, D] layout)
    q4, k4, v4 = (t.reshape(b, h, s, d) for t in (q, k, v))
    flash = jax.jit(functools.partial(
        flash_attention, causal=False, scale=scale, interpret=interpret))
    t_flash, out_f = _time(lambda: flash(q4, k4, v4), iters, repeat=3)

    # unfused: the legacy per-op path over the same program
    legacy = repro.compile(policy=CompilerPolicy.legacy())(
        lambda a, b_, c: _attn_program(a, b_, c, scale))
    t_unfused, _ = _time(lambda: legacy(q, k, v), iters, repeat=3)

    import numpy as np
    err = float(np.max(np.abs(np.asarray(out_t)
                              - np.asarray(out_f).reshape(bh, s, d))))
    return {
        "shape_bhsd": [b, h, s, d],
        "template_s": t_template,
        "flash_attention_s": t_flash,
        "unfused_s": t_unfused,
        "template_vs_flash_ratio": t_template / t_flash,
        "speedup_vs_unfused": t_unfused / t_template,
        "generated_dispatches": exe.n_dispatches,
        "generated_kernels": exe.n_kernels,
        "cluster_kinds": kinds,
        "unfused_dispatches": legacy.last_executable.n_dispatches,
        "template_vs_flash_max_abs_err": err,
    }


def bench_epilogue(iters: int = 10, m: int = 256, k: int = 256,
                   n: int = 256) -> dict:
    """``gelu(x @ w + b)``: fused matmul-epilogue kernel vs per-op."""
    keys = jax.random.split(jax.random.PRNGKey(1), 3)
    x = jax.random.normal(keys[0], (m, k), jnp.float32)
    w = jax.random.normal(keys[1], (k, n), jnp.float32) * (k ** -0.5)
    bias = jax.random.normal(keys[2], (n,), jnp.float32)

    def f(x, w, bias):
        return ops.gelu(ops.add(ops.matmul(x, w), bias))

    fused = repro.compile(f)
    t_fused, out_fused = _time(lambda: fused(x, w, bias), iters, repeat=3)
    exe = fused.last_executable
    legacy = repro.compile(policy=CompilerPolicy.legacy())(f)
    t_unfused, out_ref = _time(lambda: legacy(x, w, bias), iters, repeat=3)

    import numpy as np
    err = float(np.max(np.abs(np.asarray(out_fused) - np.asarray(out_ref))))
    return {
        "shape_mkn": [m, k, n],
        "fused_s": t_fused,
        "unfused_s": t_unfused,
        "speedup_vs_unfused": t_unfused / t_fused,
        "fused_dispatches": exe.n_dispatches,
        "fused_kernels": exe.n_kernels,
        "cluster_kinds": [c["kind"] for c in exe.describe()["clusters"]],
        "unfused_dispatches": legacy.last_executable.n_dispatches,
        "max_abs_err_vs_unfused": err,
    }


def run() -> list[tuple[str, float, str]]:
    """CSV rows for benchmarks/run.py."""
    r = bench()
    a = bench_attention()
    e = bench_epilogue()
    pass_txt = " ".join(
        f"{name}:{p['nodes_before']}->{p['nodes_after']}"
        for name, p in r["passes"].items())
    return [
        ("fusion_eager_chain_s", r["eager_s"],
         f"{r['n_ops']} dispatches per chain"),
        ("fusion_lazy_legacy_chain_s", r["lazy_legacy_s"],
         f"{r['legacy_dispatches']} dispatches (node-at-a-time)"),
        ("fusion_compiled_chain_s", r["compiled_s"],
         f"{r['compiled_dispatches']} dispatch(es), "
         f"{r['pallas_kernels']} generated kernel(s); "
         f"passes[{pass_txt}]; "
         f"exact={r['numerics_exact_vs_eager']}; "
         f"speedup vs eager={r['speedup_vs_eager']:.2f}x "
         f"legacy={r['speedup_vs_legacy']:.2f}x"),
        ("fusion_attention_template_s", a["template_s"],
         f"{a['generated_kernels']} generated kernel(s) vs hand-written "
         f"{a['template_vs_flash_ratio']:.2f}x, unfused "
         f"{a['unfused_dispatches']} dispatches"),
        ("fusion_epilogue_fused_s", e["fused_s"],
         f"{e['unfused_dispatches']} dispatches -> "
         f"{e['fused_dispatches']}; speedup "
         f"{e['speedup_vs_unfused']:.2f}x"),
    ]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="small chain / few iters; emit JSON for CI")
    ap.add_argument("--out", default=None, help="write JSON here")
    ap.add_argument("--n-ops", type=int, default=None)
    ap.add_argument("--iters", type=int, default=None)
    args = ap.parse_args()
    enable_compile_cache()

    n_ops = args.n_ops or 16
    iters = args.iters or (5 if args.quick else 20)
    side = 128 if args.quick else 256
    result = bench(n_ops=n_ops, iters=iters, side=side)
    attn = bench_attention(iters=iters)
    epi = bench_epilogue(iters=iters)
    bench_artifact.emit("fusion",
                        {**result, "attention": attn, "epilogue": epi},
                        out=args.out, quick=args.quick)
    assert result["numerics_exact_vs_eager"], "compiled != eager"
    assert result["compiled_dispatches"] <= 2, \
        "pipeline failed to collapse the chain"
    assert attn["generated_dispatches"] == 1 \
        and attn["generated_kernels"] == 1 \
        and attn["cluster_kinds"] == ["attention"], \
        "attention matcher failed to produce one generated kernel"
    assert attn["template_vs_flash_ratio"] <= 1.25, \
        (f"generated template {attn['template_vs_flash_ratio']:.2f}x "
         "slower than hand-written flash_attention (budget 1.25x)")
    assert epi["unfused_dispatches"] >= 3 and epi["fused_dispatches"] == 1, \
        "epilogue fusion failed to collapse matmul+bias+gelu"


if __name__ == "__main__":
    main()
