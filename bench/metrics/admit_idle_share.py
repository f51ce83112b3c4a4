"""KV cache / admission: share of the window in which the device was idle
while the engine admitted requests (the ``serve.admit`` span and those
it holds: KV mapping, block growth, copy-on-write), in %.  Read from the
program's spans on the profiler's trace (``bench/lib/spans.py``);
nothing where the trace has none."""

from bench.lib import spans


def read(ctx):
    att = spans.for_ctx(ctx)
    return None if att is None else spans.admit_idle_share(att)
