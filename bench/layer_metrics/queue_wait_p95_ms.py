"""Scheduler: 95th percentile of the wait from submit to admission, over
the requests admitted in the traced window."""
from bench.lib.stats import percentile


def read(ctx, name):
    v = percentile(ctx["obs"]["waits"], 95)
    return None if v is None else v * 1e3
