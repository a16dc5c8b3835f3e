"""Engine host loop: mean duration of the program's ``nbl.commit`` span,
one per fused step: sampling every row, chunk progress, token emission
into the streams and retirement, after the logits reached the host."""
from bench.lib.stats import mean


def read(ctx, name):
    if not ctx["trace"]:
        return None
    v = mean([d for n, _, d in ctx["trace"]["host"] if n == "nbl.commit"])
    return None if v is None else v / 1e6
