"""Scheduler: mean share of the engine's slots decoding in a step, over
the steps of the traced window."""
from bench.lib.stats import mean


def read(ctx, name):
    obs = ctx["obs"]
    v = mean([n / obs["n_slots"] for _, _, n, _ in obs["steps"]])
    return None if v is None else v * 100.0
