"""Kernel: the paged-attention kernel's least time at the chip's peaks
(operations and bytes of the live KV pages, q and o; the larger of the
two bounds) over its device time in the trace, per fused step.
``paged_attn_roofline.chat`` and ``.batch`` share this reader. The
trace's kernel time is per chip, and a step sharded over several chips
gives each its share of the KV heads, so the model-wide least time is
divided by the chips."""
from bench.lib import names
from bench.lib.flops import ModelShape, roofline_s
from bench.lib.trace import reduce


def read(ctx, name):
    obs, trace = ctx["obs"], ctx["trace"]
    if not trace or not obs["dispatches"]:
        return None
    red = reduce(trace, kernel=names.PAGED_KERNEL,
                 step_module=names.STEP_MODULE)
    if red is None or not red["kernel_s"] or not red["step_calls"]:
        return None
    shape = ModelShape.from_config(ctx["config"])
    ps = ctx["config"]["engine"]["page_size"]
    peak = ctx["peak"]
    least = [roofline_s(*shape.paged_attn_cost(ln, ps), peak["bf16_flops"],
                        peak["hbm_bytes_per_s"])[0]
             for ln in obs["dispatches"]]
    per_step = sum(least) / len(least) / ctx["chips"]
    return per_step * red["step_calls"] / red["kernel_s"] * 100.0
