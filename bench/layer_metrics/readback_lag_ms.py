"""Engine host loop: time the logits take to reach the host after the
chip has finished the step. Mean, over the program's ``nbl.readback``
spans, of the span's end less the end of the last fused-step program
(``names.STEP_MODULE``) that ends inside it, on any chip; a span in which
no step program ends is left out. Both ends are on the profiler's clock."""
import bisect
import re

from bench.lib import names
from bench.lib.stats import mean


def read(ctx, name):
    if not ctx["trace"]:
        return None
    norm = ctx["trace"]
    step = re.compile(names.STEP_MODULE)
    ends = sorted(s + d for mods in norm["modules"].values()
                  for n, s, d in mods if step.search(n))
    lags = []
    for n, s, d in norm["host"]:
        if n != "nbl.readback":
            continue
        i = bisect.bisect_right(ends, s + d) - 1
        if i >= 0 and ends[i] >= s:
            lags.append(s + d - ends[i])
    v = mean(lags)
    return None if v is None else v / 1e6
