"""Whole step: the model FLOPs the window's work needed per second, over
the chips' peak bf16 rate, in %.  The FLOPs are the algorithm's own
(``bench/lib/flops.py``): no recomputation, causal attention over live
context, the prefix cache's savings left out."""


def read(ctx):
    c = ctx["counters"]
    fl = c.get("model_flops")
    if not fl:
        return None
    peak = ctx["peaks"]["bf16_flops_per_s"] * ctx["chips"]
    return 100.0 * fl / c["window_s"] / peak
