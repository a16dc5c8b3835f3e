"""Model step: model operations of the traced window's work (every fused
decode row at its live length, every admission prefill at its prompt
length) over the window times the chips' bf16 peak. ``step_mfu.chat`` and
``step_mfu.batch`` share this reader."""
from bench.lib.flops import ModelShape


def read(ctx, name):
    obs = ctx["obs"]
    if not obs["dispatches"] or ctx["window_s"] <= 0:
        return None
    shape = ModelShape.from_config(ctx["config"])
    work = sum(shape.decode_flops(ln) for ln in obs["dispatches"])
    work += sum(shape.prefill_flops(n) for n in obs["prefills"])
    peak = ctx["peak"]["bf16_flops"] * ctx["chips"]
    return work / (ctx["window_s"] * peak) * 100.0
