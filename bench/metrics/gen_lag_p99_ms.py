"""Load generator: 99th percentile of how late each request due in the
window was handed to the engine (submit time minus due time), in ms.
Read from the harness's own clock; only open-loop cells have due times."""

from bench.lib import stats


def read(ctx):
    lags = ctx["counters"].get("gen_lag_s")
    if not lags:
        return None
    return stats.percentile(lags, 99) * 1e3
