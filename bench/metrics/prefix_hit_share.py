"""KV cache: share of the admitted prompt tokens that the prefix cache
supplied instead of prefill, in %.  Read from the engine's counter
``prefill_tokens_saved`` over the window."""


def read(ctx):
    c = ctx["counters"]
    total = c.get("admitted_prompt_tokens")
    if not total:
        return None
    return 100.0 * c["prefill_tokens_saved"] / total
