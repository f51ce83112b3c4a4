"""Serving scheduler: share of the positions that the window's prefill
calls computed that some request needed, in %.  Each call computes
``slots x chunk`` positions whatever is admitted; the needed ones are the
admitted prompts' tokens less those the prefix cache supplied.  Read
from the engine's counters (``prefill_calls``,
``prefill_tokens_saved``)."""


def read(ctx):
    c = ctx["counters"]
    calls = c.get("prefill_calls")
    if not calls:
        return None
    needed = c["admitted_prompt_tokens"] - c["prefill_tokens_saved"]
    return 100.0 * needed / (calls * c["slots"] * c["chunk"])
