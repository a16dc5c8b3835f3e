"""The per-layer readers that count per chip, on hand-made normalized
traces of one and of four chips, and on the recorded one-chip chat trace,
whose readings are pinned at what they were before the readers learned of
several chips."""
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(ROOT))

import pytest  # noqa: E402

from bench.lib.spec import Bench, read_metric  # noqa: E402

MS = 1_000_000                                 # ns per millisecond
KERNEL = "%closed_call.1 = bf16[48,8,4,128] custom-call(a)"
SHARDED_KERNEL = "%shard_map.69 = bf16[48,2,4,128] custom-call(a)"
# the chat trace's decode rows: 30 rows of 300-793 live tokens a step
DISPATCH = [300 + 17 * i for i in range(30)]


@pytest.fixture(scope="module")
def bench():
    return Bench(str(ROOT))


def span(name, t0, t1):
    return [name, int(t0 * MS), int((t1 - t0) * MS)]


def steps_trace(planes: int, kernel: str, kernel_ms: float) -> dict:
    """Four fused steps of 20 ms on each of ``planes`` chips, each with one
    kernel call of ``kernel_ms`` and a copy."""
    dev, mods = {}, {}
    for c in range(planes):
        evs, ms = [], []
        for i in range(4):
            t = 25.0 * i
            ms.append(span("jit__fused(7)", t, t + 20))
            evs += [span(kernel, t + 1, t + 1 + kernel_ms),
                    span("%copy.2 = copy(a)", t + 12, t + 19)]
        dev[f"/device:TPU:{c}"], mods[f"/device:TPU:{c}"] = evs, ms
    return {"device": dev, "modules": mods, "host": []}


def ctx(bench, norm, chips, cell="mistral7b-8L-nbl3.chat-poisson"):
    cfg = bench.config(bench.cell(cell)["config"])
    return {"trace": norm, "config": cfg, "chips": chips,
            "peak": bench.peaks()["TPU v5 lite"], "window_s": 0.1,
            "obs": {"dispatches": [DISPATCH] * 4, "steps": [],
                    "prefills": [], "waits": [], "n_slots": 48}}


def test_roofline_reads_alike_on_one_chip_and_on_four(bench):
    """Four chips each run the kernel over a quarter of the KV heads, so a
    quarter of its bytes, in a quarter of the time: the same share."""
    one = read_metric(bench, "paged_attn_roofline.chat",
                      ctx(bench, steps_trace(1, KERNEL, 8.0), 1))
    four = read_metric(bench, "paged_attn_roofline.chat",
                       ctx(bench, steps_trace(4, SHARDED_KERNEL, 2.0), 4))
    assert one is not None and 0 < one < 100
    assert four == pytest.approx(one)
    # the kernel's time alone: half the time, twice the share
    assert read_metric(bench, "paged_attn_roofline.chat", ctx(
        bench, steps_trace(4, SHARDED_KERNEL, 1.0), 4)) == \
        pytest.approx(2 * one)


@pytest.mark.parametrize("metric,value", [
    ("paged_attn_roofline.chat", 0.6720271333985466),
    ("fused_step_ms", 131.121458),
    ("step_mfu.chat", 5.273360593122318),
    ("device_idle.chat", 8.348216785113438),
])
def test_recorded_chat_trace_readings_are_pinned(bench, metric, value):
    """The recorded one-chip trace reads as it did before the readers
    counted per chip (values from the readers of the parent change)."""
    recorded = json.loads((HERE / "data" / "chat_trace_3steps.json")
                          .read_text())
    c = ctx(bench, recorded, 1)
    c["window_s"] = 0.4293
    c["obs"]["dispatches"] = [DISPATCH] * 3
    c["obs"]["prefills"] = [1024, 200]
    got = read_metric(bench, metric, c)
    assert got == pytest.approx(value, rel=1e-12)
