#!/usr/bin/env python3
"""Chip smoke test: the main paths, once, on a TPU, through their entry points.

    python chip_smoke.py              # one chip: serve, train, compile
    python chip_smoke.py --chips 4    # four chips: the sharded train step

One process drives the chip.  With no arguments it runs three phases on
one chip, each with its own check:

* serve   -- ``ServeEngine`` on starcoder2-7b at published widths, 8 of its
  32 layers, seeded random bf16 weights, paged KV cache with prefix
  sharing and chunked prefill; 12 requests in two waves, drained with
  ``run_until_done``.  One request's cached-decode log-probs are compared
  with ``LM.forward`` over the same tokens.
* train   -- 3 steps of mamba2-370m, whole, batch 8 x 2048, through
  ``repro.launch.train.run`` (``make_batches`` -> ``train``).
* compile -- ``repro.compile`` on ``gelu(x @ w + b)`` and on a 16-op
  elementwise chain at starcoder2 widths, each against eager.

``--chips 4`` runs only the sharded mamba2-370m train step
(``CellPlan``, baseline and fsdp rules) on a (data 2, model 2) mesh and
compares its losses with the same steps on one device.

The last line of standard output is ``{"ok": true, "device": {...}}``.
Without a TPU the script exits non-zero before any phase runs.  A phase
that fails raises, which ends the run with a non-zero exit.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

REPO = pathlib.Path(__file__).resolve().parent

# -- tolerances --------------------------------------------------------------
#: serve: ||engine log-probs - LM.forward log-probs|| / ||forward log-probs
#: - their mean||, over the vocabulary.  Both run the same bf16 weights, but
#: the engine attends over the paged pool chunk by chunk and the forward
#: over the whole sequence, so roundings land at different points, and a
#: stack of random-weight layers amplifies them: on CPU at d_model 1152 and
#: 2304 with 8 layers, correct runs measured 0.09-0.38, and bf16 LM.forward
#: differs from its own f32 run by 0.53-0.70.  Gross faults (a position off
#: by one, a cache holding another prompt, half the prompt missing)
#: measured 0.89-1.07: two unrelated log-prob vectors.
SERVE_LOGP_REL = 0.6
#: compile, epilogue: bf16 output, compared as the kernel tests compare bf16
#: (the kernel and XLA round the f32 accumulator and gelu differently).
EPILOGUE_TOL = dict(rtol=2e-2, atol=2e-2)
#: compile, f32 chain: the TPU approximates transcendentals in hardware and
#: Mosaic and XLA may refine them differently; the chain contracts errors.
#: A wrong block or op order gives errors of order 1.
CHAIN_ATOL = 1e-4
#: --chips 4: |sharded loss - one-device loss| after each of 3 steps.
#: bf16 parameters; sharded matmuls and reductions sum partial results in
#: another order, which moves a loss near ln(50280) = 10.8 in its third or
#: fourth significant digit at most.
MESH_LOSS_ATOL = 2e-2


class CheckFailed(RuntimeError):
    """A phase's output failed its check."""


def _check(ok: bool, msg: str) -> None:
    if not ok:
        raise CheckFailed(msg)


def _peak_bytes(jax) -> str:
    stats = jax.devices()[0].memory_stats()
    if not stats:
        return "peak_bytes_in_use=not reported by this backend"
    return (f"peak_bytes_in_use={stats.get('peak_bytes_in_use')} "
            f"bytes_in_use={stats.get('bytes_in_use')}")


def _requests(rng, n: int, vocab: int, lo: int, hi: int, prefix_len: int):
    """``n`` prompts of ``lo..hi`` tokens; every other one starts with the
    same ``prefix_len``-token prefix."""
    prefix = rng.integers(0, vocab, prefix_len).tolist()
    prompts = []
    for i in range(n):
        length = int(rng.integers(lo, hi + 1))
        if i % 2 == 0:
            length = max(length, prefix_len + 1)
            tail = rng.integers(0, vocab, length - prefix_len).tolist()
            prompts.append(prefix + tail)
        else:
            prompts.append(rng.integers(0, vocab, length).tolist())
    return prompts


def serve_phase(cfg, *, slots: int = 16, max_seq: int = 4096,
                n_requests: int = 12, prompt_range=(200, 3000),
                prefix_len: int = 1024, new_tokens: int = 32,
                chunk: int = 256, seed: int = 0) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    import repro
    from repro.models import build_model
    from repro.runtime import ServingPolicy
    from repro.serving import Request, ServeEngine

    model = build_model(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(seed))
    rng = np.random.default_rng(seed)
    prompts = _requests(rng, n_requests, cfg.vocab_size, *prompt_range,
                        prefix_len)
    policy = ServingPolicy(cache="paged", block_size=16, prefix=True,
                           prefill_chunk=chunk)
    with repro.session(serving=policy, tag="chip_smoke:serve"):
        engine = ServeEngine(model, params, batch_slots=slots,
                             max_seq=max_seq)

    # set-up: one warm-up request compiles the prefill and decode steps
    t0 = time.perf_counter()
    warm = rng.integers(0, cfg.vocab_size, chunk + 1).tolist()
    engine.submit(Request(uid=-1, prompt=warm, max_new_tokens=2))
    engine.run_until_done()
    setup_s = time.perf_counter() - t0

    reqs = [Request(uid=i, prompt=p, max_new_tokens=new_tokens)
            for i, p in enumerate(prompts)]
    half = len(reqs) // 2
    t0 = time.perf_counter()
    for r in reqs[:half]:
        engine.submit(r)
    for _ in range(4):
        engine.step()
    for r in reqs[half:]:
        engine.submit(r)
    engine.run_until_done()
    wall_s = time.perf_counter() - t0

    bad = [r.uid for r in reqs
           if not r.done or len(r.generated) != new_tokens]
    _check(not bad, f"requests without all their tokens: {bad}")
    desc = engine.describe()
    saved = desc["prefill_tokens_saved"]
    _check(saved > 0, "prefix sharing saved no prefill tokens")
    generated = sum(len(r.generated) for r in reqs)

    # one request's cached decode against the whole-sequence forward; the
    # engine's log-prob body is the one beam search steps with
    shortest_shared = min(prompts[::2], key=len)
    check = Request(uid=n_requests, prompt=shortest_shared, max_new_tokens=4)
    engine.submit(check)
    engine.step()
    engine._ensure_capacity()
    (slot,) = [s for s, r in engine.active.items() if r is check]
    logp, _ = engine._decode_logits(
        engine.params, engine.cache, jnp.asarray(engine.slot_tok),
        jnp.asarray(engine.slot_pos), engine._block_table())
    tokens = jnp.asarray([check.prompt + check.generated], jnp.int32)
    logits, _, _ = jax.jit(model.forward)(params, tokens)
    ref = jax.nn.log_softmax(logits[0, -1].astype(jnp.float32))
    diff = logp[slot] - ref
    rel = float(jnp.linalg.norm(diff) / jnp.linalg.norm(ref - ref.mean()))
    max_abs = float(jnp.max(jnp.abs(diff)))
    engine.run_until_done()
    _check(bool(np.isfinite(rel)) and rel <= SERVE_LOGP_REL,
           f"cached decode log-probs differ from LM.forward: relative "
           f"error {rel} (> {SERVE_LOGP_REL}), max abs {max_abs}")

    out = {"setup_s": setup_s, "wall_s": wall_s, "requests": len(reqs),
           "tokens_generated": generated, "prefill_tokens_saved": saved,
           "shared_admissions": desc["shared_admissions"],
           "preemptions": desc["preemptions"],
           "logp_rel_err": rel, "logp_max_abs_err": max_abs}
    print(f"[serve] {cfg.name} layers={cfg.n_layers} d_model={cfg.d_model} "
          f"slots={slots} max_seq={max_seq} " + json.dumps(out))
    print(f"[serve] {_peak_bytes(jax)}")
    return out


def train_phase(cfg, *, steps: int = 3, batch: int = 8, seq: int = 2048
                ) -> dict:
    import jax
    import numpy as np

    from repro.launch.train import run

    history = run(cfg, steps=steps, batch=batch, seq=seq)
    losses = [h["loss"] for h in history]
    _check(len(losses) == steps, f"{len(losses)} of {steps} steps ran")
    _check(bool(np.all(np.isfinite(losses))), f"non-finite loss: {losses}")
    out = {"losses": losses, "step_s": [h["sec"] for h in history]}
    print(f"[train] {cfg.name} batch={batch} seq={seq} " + json.dumps(out)
          + " (step 0 includes compilation)")
    print(f"[train] {_peak_bytes(jax)}")
    return out


def _lowerings(fn) -> dict:
    desc = fn.last_executable.describe()
    return {"dispatches": desc["dispatches"],
            "pallas_clusters": sum(c["lowering"] == "pallas"
                                   for c in desc["clusters"]),
            "jit_clusters": sum(c["lowering"] == "jit"
                                for c in desc["clusters"]),
            "kinds": [c["kind"] for c in desc["clusters"]]}


def compile_phase(*, m: int = 2048, k: int = 4608, n: int = 18432,
                  chain_shape=(4096, 4608), seed: int = 0) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    import repro
    from repro.core.tensor import ops

    def epilogue(x, w, b):
        return ops.gelu(ops.add(ops.matmul(x, w), b))

    def chain(x):                   # 4 x (mul, sin, add, tanh) = 16 ops
        for _ in range(4):
            x = ops.tanh(ops.add(ops.mul(x, x), ops.sin(x)))
        return x

    kx, kw, kb, kc = jax.random.split(jax.random.PRNGKey(seed), 4)
    x = jax.random.normal(kx, (m, k), jnp.bfloat16)
    w = (jax.random.normal(kw, (k, n), jnp.float32) * k ** -0.5
         ).astype(jnp.bfloat16)
    b = jax.random.normal(kb, (n,), jnp.bfloat16)
    c = jax.random.normal(kc, chain_shape, jnp.float32)

    out = {}
    for name, fn, args, tol, kind in (
            ("epilogue", epilogue, (x, w, b), EPILOGUE_TOL, "epilogue"),
            ("chain", chain, (c,), dict(rtol=0.0, atol=CHAIN_ATOL),
             "elementwise")):
        compiled = repro.compile(fn)
        t0 = time.perf_counter()
        got = jax.block_until_ready(compiled(*args))
        first_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        got = jax.block_until_ready(compiled(*args))
        again_s = time.perf_counter() - t0
        want = jax.block_until_ready(fn(*args))
        low = _lowerings(compiled)
        err = float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                    - want.astype(jnp.float32))))
        row = dict(low, first_call_s=first_s, second_call_s=again_s,
                   max_abs_err=err)
        print(f"[compile] {name} " + json.dumps(row))
        _check(low["pallas_clusters"] == 1 and low["jit_clusters"] == 0
               and low["kinds"] == [kind],
               f"{name}: expected one {kind} Pallas kernel, got {low}")
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32), **tol)
        out[name] = row
    print(f"[compile] {_peak_bytes(jax)}")
    return out


def mesh_phase(cfg, *, devices, batch: int = 8, seq: int = 2048,
               steps: int = 3, seed: int = 0) -> dict:
    """The sharded train step of ``CellPlan`` on a (data 2, model 2) mesh
    of ``devices``, for the baseline and fsdp rules, against the same
    steps on a 1x1 mesh of ``devices[0]``."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from repro.configs.base import ShapeSpec
    from repro.launch.steps import CellPlan, Variant
    from repro.launch.train import make_batches

    shape = ShapeSpec("chip_smoke_train", seq, batch, "train")
    batches = list(make_batches(cfg, batch, seq, steps, seed=seed))
    meshes = {"one_device": Mesh(np.array(devices[:1]).reshape(1, 1),
                                 ("data", "model")),
              "2x2": Mesh(np.array(devices[:4]).reshape(2, 2),
                          ("data", "model"))}
    runs = (("one_device", "baseline"), ("2x2", "baseline"),
            ("2x2", "fsdp"))
    losses = {}
    for mesh_name, rules in runs:
        plan = CellPlan(cfg, shape, meshes[mesh_name],
                        Variant(name=rules, sharding=rules))
        fn, _, in_sh, out_sh, donate = plan.lowerable()
        step = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                       donate_argnums=donate)
        p_sh, o_sh, rep, b_sh = in_sh
        params = jax.device_put(plan.model.init(jax.random.PRNGKey(seed)),
                                p_sh)
        opt = jax.device_put(plan.optimizer.init(params), o_sh)
        got = []
        t0 = time.perf_counter()
        for i, batch_i in enumerate(batches):
            params, opt, metrics = step(params, opt,
                                        jax.device_put(np.int32(i + 1), rep),
                                        jax.device_put(batch_i, b_sh))
            got.append(float(metrics["loss"]))
        wall = time.perf_counter() - t0
        key = f"{mesh_name}/{rules}"
        losses[key] = got
        print(f"[mesh] {key} losses={got} wall_s={wall} "
              "(includes compilation)")
    ref = np.asarray(losses["one_device/baseline"])
    _check(bool(np.all(np.isfinite(ref))), f"non-finite losses: {ref}")
    diffs = {}
    for key in ("2x2/baseline", "2x2/fsdp"):
        diffs[key] = float(np.max(np.abs(np.asarray(losses[key]) - ref)))
        _check(diffs[key] <= MESH_LOSS_ATOL,
               f"{key} losses {losses[key]} differ from one device "
               f"{ref.tolist()} by {diffs[key]} (> {MESH_LOSS_ATOL})")
    print("[mesh] max |loss - one_device| " + json.dumps(diffs))
    print(f"[mesh] {_peak_bytes(jax)}")
    return {"losses": losses, "max_abs_diff": diffs}


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the sharded train step on a 2x2 mesh")
    args = ap.parse_args(argv)

    src = REPO / "src"
    if not (src / "repro").is_dir():
        print(f"chip_smoke: no repro package under {src}; run this script "
              "from a checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, str(src))

    import jax

    from repro.configs.base import get_config
    from repro.launch.compile_cache import enable_compile_cache

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU: JAX found {len(devices)} "
              f"{dev.platform} device(s); this script runs only on a TPU",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices; JAX found {len(devices)}", file=sys.stderr)
        return 1
    print(f"[device] platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)} "
          f"compile_cache={enable_compile_cache()}")

    t_all = time.perf_counter()
    if args.chips == 4:
        mesh_phase(get_config("mamba2-370m"), devices=devices)
    else:
        t0 = time.perf_counter()
        serve_phase(get_config("starcoder2-7b", n_layers=8))
        print(f"[serve] phase_s={time.perf_counter() - t0}")
        t0 = time.perf_counter()
        train_phase(get_config("mamba2-370m"))
        print(f"[train] phase_s={time.perf_counter() - t0}")
        t0 = time.perf_counter()
        compile_phase()
        print(f"[compile] phase_s={time.perf_counter() - t0}")
    print(f"[done] total_s={time.perf_counter() - t_all}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
