"""The readers of the engine's step-phase spans, on a hand-made trace of
two fused steps with known times, and on the recorded chat trace, which
predates those spans."""
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(ROOT))

import pytest  # noqa: E402

from bench.lib import names, trace  # noqa: E402
from bench.lib.spec import Bench, read_metric  # noqa: E402

MS = 1_000_000                                 # ns per millisecond
PLANE = "/device:TPU:0"
METRICS = ("host_self_ms_per_step", "commit_ms_per_step", "readback_lag_ms")


def span(name, t0, t1):
    return [name, int(t0 * MS), int((t1 - t0) * MS)]


def two_steps() -> dict:
    """Two whole engine steps, after the tail of one the profiler cut (its
    ``nbl.step`` opened before tracing did, so only its later phases are
    recorded). Each step program ends inside its readback, and the eager
    slice of the logits runs after it there."""
    host = [
        span("nbl.readback", -2, 0.5),         # the cut step's tail
        span("nbl.commit", 0.5, 1),
        span("bench.step", 0.9, 30.5),
        span("nbl.step", 1, 30),
        span("nbl.admission", 1, 4),
        span("nbl.prefill", 1.5, 2),           # inside admission
        span("nbl.plan", 4, 5),
        span("nbl.fused_step", 5, 6),
        span("bench.commit_fused", 6, 29.5),
        span("nbl.readback", 6, 20),
        span("nbl.commit", 20, 29),
        span("nbl.step", 31, 55),
        span("nbl.admission", 32, 33),
        span("nbl.plan", 33, 34),
        span("nbl.fused_step", 34, 35),
        span("nbl.readback", 35, 47),
        span("nbl.commit", 47, 54),
    ]
    mods = [span("jit__fused(1)", -20, -1), span("jit__fused(1)", 5.5, 18),
            span("jit_dynamic_slice(2)", 18.5, 19),
            span("jit__fused(1)", 35.2, 44),
            span("jit_dynamic_slice(2)", 44.5, 45)]
    kern = "%closed_call.1 = bf16[1] custom-call(a)"
    copy = "%copy.2 = copy(a)"
    dev = [span(kern, -20, -2), span(copy, -2, -1),
           span(kern, 5.5, 17), span(copy, 17, 18),
           span("%slice.3 = slice(a)", 18.5, 19),
           span(kern, 35.2, 43), span(copy, 43, 44),
           span("%slice.3 = slice(a)", 44.5, 45)]
    return {"device": {PLANE: dev}, "modules": {PLANE: mods}, "host": host}


def ctx(norm) -> dict:
    return {"trace": norm, "obs": {}, "window_s": 0.06, "config": {},
            "peak": {}, "chips": 1}


@pytest.fixture(scope="module")
def bench():
    return Bench(str(ROOT))


def test_host_self_time(bench):
    # step 1: 29 ms less admission 3, enqueue 1 and readback 14 = 11 ms;
    # step 2: 24 ms less 1, 1 and 12 = 10 ms; the cut step is left out
    assert read_metric(bench, "host_self_ms_per_step", ctx(two_steps())) \
        == pytest.approx(10.5)


def test_commit_time(bench):
    # 0.5 ms (the cut step's whole commit), 9 ms and 7 ms
    assert read_metric(bench, "commit_ms_per_step", ctx(two_steps())) \
        == pytest.approx(5.5)


def test_readback_lag(bench):
    # 0.5 - (-1), 20 - 18 and 47 - 44: the eager slice after the step
    # program is not a step program
    assert read_metric(bench, "readback_lag_ms", ctx(two_steps())) \
        == pytest.approx(6.5 / 3)


def test_idle_gaps_are_named_by_program_phases():
    red = trace.reduce(two_steps(), kernel=names.PAGED_KERNEL,
                       step_module=names.STEP_MODULE)
    # -1 -> 5.5 ms in admission; 18 -> 18.5 and 44 -> 44.5 in readbacks;
    # 19 -> 35.2 (middle 27.1) in step 1's commit; 45 -> 55, the window's
    # end, in step 2 (step 2's commit opens after the last device event,
    # and the reduction does not consult such spans)
    assert dict(red["idle_gaps"]) == pytest.approx(
        {"nbl.admission": 0.0065, "nbl.readback": 0.001,
         "nbl.commit": 0.0162, "nbl.step": 0.01})


@pytest.mark.parametrize("metric", METRICS)
def test_absent_spans_read_nothing(bench, metric):
    recorded = json.loads((HERE / "data" / "chat_trace_3steps.json")
                          .read_text())
    assert read_metric(bench, metric, ctx(recorded)) is None
    assert read_metric(bench, metric, ctx(None)) is None
