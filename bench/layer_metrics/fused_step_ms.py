"""Model step: device time of the fused-step program per dispatch, from
the profiler trace."""
from bench.lib import names
from bench.lib.trace import reduce


def read(ctx, name):
    if not ctx["trace"]:
        return None
    red = reduce(ctx["trace"], kernel=names.PAGED_KERNEL,
                 step_module=names.STEP_MODULE)
    if red is None or not red["step_calls"]:
        return None
    return red["step_s"] / red["step_calls"] * 1e3
