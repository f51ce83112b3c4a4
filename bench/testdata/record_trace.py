#!/usr/bin/env python3
"""Record the small TPU trace that ``bench/tests`` reduces.

    python3 bench/testdata/record_trace.py <out_dir>

Runs two jitted programs a few times under the profiler, inside a
``bench.window`` annotation, with ``bench.engine_step`` around each call
and a ``bench.wait_arrival`` sleep between them, so the trace holds
device operations, programs and idle gaps with known host annotations.
Run it on a TPU; copy the ``.xplane.pb`` it writes next to this file.
"""

import sys
import time

import jax
import jax.numpy as jnp


def main(out_dir: str) -> int:
    if jax.devices()[0].platform != "tpu":
        print("record_trace: no TPU", file=sys.stderr)
        return 1

    @jax.jit
    def _decode_fn(x, w):
        return jnp.tanh(x @ w)

    @jax.jit
    def _prefill_fn(x, w):
        return jnp.sin(x @ w) @ w

    x = jnp.ones((1024, 1024), jnp.bfloat16)
    w = jnp.ones((1024, 1024), jnp.bfloat16) * 1e-3
    jax.block_until_ready((_decode_fn(x, w), _prefill_fn(x, w)))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(out_dir, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        for i in range(3):
            with jax.profiler.TraceAnnotation("bench.engine_step"):
                jax.block_until_ready(_prefill_fn(x, w))
                for _ in range(4):
                    jax.block_until_ready(_decode_fn(x, w))
            with jax.profiler.TraceAnnotation("bench.wait_arrival"):
                time.sleep(0.02 * (i + 1))
    jax.profiler.stop_trace()
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
