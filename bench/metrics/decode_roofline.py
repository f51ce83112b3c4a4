"""Decode program: least time the chip could take for the window's decode
calls over the device time the decode program took, in %.

Per call the least time is the larger of its bytes over the peak
bandwidth -- every weight once, plus the live keys and values of each
active slot (its context, not the cache's capacity) -- and its FLOPs over
the peak rate.  The device time is the sum of the decode program's
events in the trace's window.  Returns nothing without a trace or a
decode call."""

from bench.lib import trace


def read(ctx):
    red = ctx.get("trace")
    steps = ctx["counters"].get("decode_steps")
    if red is None or not steps:
        return None
    calls, secs = trace.program_seconds(red, "_decode_fn")
    if not secs:
        return None
    c, pk = ctx["counts"], ctx["peaks"]
    least = 0.0
    for slots, keys in steps:
        nbytes = c.weight_bytes + keys * c.kv_bytes_per_pos
        fl = c.forward_flops(slots, keys)
        least += max(nbytes / pk["hbm_bytes_per_s"],
                     fl / pk["bf16_flops_per_s"])
    # the trace may hold a call or two fewer or more than the host saw
    # at the window's edges: scale to the calls traced
    least *= calls / len(steps)
    return 100.0 * least / secs
