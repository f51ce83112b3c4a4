"""Serving engine (host): median over the window's engine steps that
decode of the step's host time less the time the host waited for the
decode's tokens, in ms -- host work per step that the device cannot
overlap.  Read from the program's ``serve.step`` and
``serve.decode_wait`` spans on the profiler's trace
(``bench/lib/spans.py``); nothing where the trace has none."""

from bench.lib import spans


def read(ctx):
    att = spans.for_ctx(ctx)
    return None if att is None else spans.step_host_ms(att)
