"""The trace reduction on a small recorded trace (three fused decode
steps of the Mistral chat cell on a TPU v5e, names cut to the HLO
instruction name) and on a hand-made two-chip trace."""
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parents[1]))

import pytest  # noqa: E402

from bench.lib import names, trace  # noqa: E402

PLANE = "/device:TPU:0"


@pytest.fixture(scope="module")
def recorded():
    return json.loads((HERE / "data" / "chat_trace_3steps.json").read_text())


def merged(iv):
    out = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def test_busy_idle_and_window(recorded):
    red = trace.reduce(recorded, kernel=names.PAGED_KERNEL,
                       step_module=names.STEP_MODULE)
    evs = recorded["device"][PLANE]
    busy = sum(e - s for s, e in merged([(s, s + d) for _, s, d in evs]))
    starts = [s for _, s, _ in evs] + [h[1] for h in recorded["host"]]
    ends = [s + d for _, s, d in evs] + [h[1] + h[2]
                                         for h in recorded["host"]]
    assert red["busy_s"] == pytest.approx(busy / 1e9)
    assert red["span_s"] == pytest.approx((max(ends) - min(starts)) / 1e9)
    assert red["busy_s"] == pytest.approx(0.393493485)
    assert red["span_s"] == pytest.approx(0.429335329)
    # idle: 1 - busy / window, about 8%
    assert 1 - red["busy_s"] / red["span_s"] == pytest.approx(0.08348,
                                                              abs=1e-4)


def test_kernel_and_step_time(recorded):
    red = trace.reduce(recorded, kernel=names.PAGED_KERNEL,
                       step_module=names.STEP_MODULE)
    steps = sorted((s, s + d) for n, s, d in recorded["modules"][PLANE]
                   if n.startswith("jit__fused("))
    assert len(steps) == 3
    a, b = steps[1]                            # the edges are left out
    kern = [d for n, s, d in recorded["device"][PLANE]
            if n.startswith("%closed_call") and a <= s < b]
    assert len(kern) == 5                      # one per attention layer
    assert red["step_calls"] == 1 and red["kernel_calls"] == 5
    assert red["step_s"] == pytest.approx((b - a) / 1e9)
    assert red["kernel_s"] == pytest.approx(sum(kern) / 1e9)
    assert red["kernel_s"] == pytest.approx(0.06498348)


def test_breakdown(recorded):
    red = trace.reduce(recorded, kernel=names.PAGED_KERNEL,
                       step_module=names.STEP_MODULE)
    ops = dict(red["device_ops"])
    assert red["device_ops"][0][0] == "closed_call (custom-call)"
    assert "while" not in ops                  # loops are not leaves
    assert len(red["device_ops"]) <= 10 and len(red["idle_gaps"]) <= 10
    assert red["idle_gaps"][0][0] == "bench.commit_fused"
    idle = sum(v for _, v in red["idle_gaps"])
    assert idle <= red["span_s"] - red["busy_s"] + 1e-9


def test_leaves_drop_containers():
    evs = [["%while.1 = op(", 0, 100], ["%a.1 = op(", 10, 20],
           ["%b.2 = op(", 40, 10], ["%c.3 = op(", 120, 5]]
    assert [e[0] for e in trace.leaves(evs)] == ["%a.1 = op(", "%b.2 = op(",
                                                 "%c.3 = op("]


def test_two_chips_average_and_name_gaps():
    us = 1000                                  # ns per microsecond
    dev = {"/device:TPU:0": [["%k.1 = custom-call(", 0, 600 * us],
                             ["%x.2 = op(", 800 * us, 200 * us]],
           "/device:TPU:1": [["%k.1 = custom-call(", 0, 1000 * us]]}
    mods = {p: [["jit__fused(1)", 0, 1000 * us]] for p in dev}
    host = [["bench.commit_fused", 600 * us, 200 * us]]
    red = trace.reduce({"device": dev, "modules": mods, "host": host},
                       kernel=r"custom-call\(", step_module=r"^jit__fused\(")
    assert red["chips"] == 2
    assert red["busy_s"] == pytest.approx((800 + 1000) / 2 * 1e-6)
    assert red["kernel_s"] == pytest.approx((600 + 1000) / 2 * 1e-6)
    # chip 0 idles 200 us while the host commits; averaged over 2 chips
    assert red["idle_gaps"] == [["bench.commit_fused",
                                 pytest.approx(100e-6)]]
    assert red["device_ops"][0][0] == "k (custom-call)"


def test_no_device_ops_reads_nothing():
    assert trace.reduce({"device": {}, "modules": {}, "host": []},
                        kernel="x", step_module="y") is None


def test_op_family():
    assert trace.op_family("%copy.105 = bf16[2] copy(x)") == "copy"
    assert trace.op_family("%closed_call.11 = bf16[1] custom-call(a)") == \
        "closed_call (custom-call)"
