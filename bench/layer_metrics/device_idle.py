"""Device: share of the traced window in which no operation ran on the
chip (1 - the union of device-operation intervals over the window).
``device_idle.chat`` and ``.batch`` share this reader."""
from bench.lib.trace import reduce


def read(ctx, name):
    if not ctx["trace"]:
        return None
    red = reduce(ctx["trace"], kernel="$^", step_module="$^")
    if red is None or red["span_s"] <= 0:
        return None
    return (1.0 - red["busy_s"] / red["span_s"]) * 100.0
