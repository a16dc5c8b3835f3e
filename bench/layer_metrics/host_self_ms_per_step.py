"""Engine host loop: mean, over the engine steps in the trace, of the
program's ``nbl.step`` span less the time its ``nbl.admission``,
``nbl.fused_step`` and ``nbl.readback`` spans cover: planning, page
faults, commit, emission and the step's own bookkeeping. The in-program
twin of ``host_ms_per_step``, on the profiler's clock. The profiler keeps
only spans that open and close while it traces, so each step counted lies
wholly inside the traced window."""
from bench.lib.stats import mean, union_length

CHILDREN = ("nbl.admission", "nbl.fused_step", "nbl.readback")


def read(ctx, name):
    if not ctx["trace"]:
        return None
    host = ctx["trace"]["host"]
    kids = [(s, s + d) for n, s, d in host if n in CHILDREN]
    selfs = [d - union_length([(a, b) for a, b in kids
                               if s <= a and b <= s + d])
             for n, s, d in host if n == "nbl.step"]
    v = mean(selfs)
    return None if v is None else v / 1e6
