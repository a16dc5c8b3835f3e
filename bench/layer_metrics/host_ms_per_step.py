"""Engine host loop: mean host time of a step outside its fused dispatch
with its logits readback (StepTimeline's host_s - dispatch_s) and outside
admission (whose prefill dispatches and readbacks wait on the device),
over the steps of the traced window: planning, page faults, commit and
token emission."""
from bench.lib.stats import mean


def read(ctx, name):
    steps = ctx["obs"]["steps"]
    v = mean([host - disp - admit for host, disp, _, admit in steps])
    return None if v is None else v * 1e3
