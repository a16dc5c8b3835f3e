"""Observability layer: registry/tracer/timeline units + engine wiring.

Unit coverage for the three obs subsystems (metrics registry with
Prometheus rendering, request tracer, step-timeline ring), then the
integration claims the layer is sold on:

* attaching ``Observability`` changes ZERO device work — a deterministic
  replay produces identical tokens and identical dispatch counts
  (decode sweeps, prefills, prefill tokens) with obs on vs off, in every
  engine mode;
* registry counters equal the engine's own counters after any replay;
* every request's span tree is well-formed (nested, terminated, no
  overlap) including cancellation in EVERY lifecycle state — queued,
  mid-chunking, decoding;
* the Chrome-trace export of a chunked+shared workload makes the
  prefill-decode interleaving claim visible: decode-carrying step events
  on the engine track overlap the window spanned by a request's chunk
  spans;
* ``Engine.stats()`` windowing keeps ``n`` = lifetime while clipping the
  percentile set to ``stats_window`` (and reporting ``window_n``).
"""
from __future__ import annotations

import json
import re
from contextlib import contextmanager

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.launch.engine import Engine
from repro.models import init_params
from repro.obs import (
    LATENCY_BUCKETS, MetricsRegistry, Observability, StepRecord,
    StepTimeline, Tracer,
)

MAX_LEN = 32
PAGE_SIZE = 4

MODES = {
    "ring": {},
    "paged": dict(paged=True, page_size=PAGE_SIZE),
    "prefix": dict(paged=True, page_size=PAGE_SIZE, prefix_sharing=True),
    "chunked": dict(paged=True, page_size=PAGE_SIZE, chunked_prefill=True,
                    prefill_chunk_tokens=PAGE_SIZE),
    "chunked_shared": dict(paged=True, page_size=PAGE_SIZE,
                           chunked_prefill=True, prefix_sharing=True,
                           prefill_chunk_tokens=PAGE_SIZE),
}


# ------------------------------------------------------------- registry --

def test_counter_and_gauge():
    r = MetricsRegistry()
    c = r.counter("c_total", "help text")
    c.inc()
    c.inc(4)
    assert c.value == 5
    with pytest.raises(ValueError):
        c.inc(-1)
    g = r.gauge("g")
    g.set(7)
    g.set(5)
    assert g.value == 5
    # factories are idempotent by name, and type mismatches are errors
    assert r.counter("c_total") is c
    with pytest.raises(ValueError):
        r.gauge("c_total")


def test_histogram_buckets_and_percentile():
    r = MetricsRegistry()
    h = r.histogram("h_seconds", buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 0.5, 5.0, 50.0):       # last lands in +Inf
        h.observe(v)
    assert h.count == 5 and h.sum == pytest.approx(56.05)
    snap = r.snapshot()["histograms"]["h_seconds"]
    assert snap["count"] == 5
    assert snap["buckets"] == [[0.1, 1], [1.0, 3], [10.0, 4]]
    # percentiles interpolate within the winning bucket and stay bounded
    assert 0.0 < h.percentile(50) <= 1.0
    assert h.percentile(100) == 10.0            # +Inf clamps to top bound
    assert MetricsRegistry().histogram("empty").percentile(99) == 0.0
    # the shared latency ladder is strictly ascending, 10 us .. 100 s
    assert list(LATENCY_BUCKETS) == sorted(set(LATENCY_BUCKETS))
    assert LATENCY_BUCKETS[0] == pytest.approx(1e-5)
    assert LATENCY_BUCKETS[-1] == pytest.approx(1e2)


def test_prometheus_rendering_parses():
    r = MetricsRegistry(labels={"engine_mode": "paged"})
    r.bind(nbl_m="2", engine_mode="clobber-must-not-win")
    r.counter("x_total", "a counter").inc(3)
    r.gauge("g").set(1.5)
    h = r.histogram("lat_seconds", "a histogram")
    h.observe(0.02)
    text = r.render_prometheus()
    assert re.search(r"^# TYPE x_total counter$", text, re.M)
    assert re.search(r"^# TYPE lat_seconds histogram$", text, re.M)
    assert 'engine_mode="paged"' in text and 'nbl_m="2"' in text
    assert "clobber" not in text                 # bind never overwrites
    # every sample line parses as <name>{labels} <value>
    sample = re.compile(r'^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^}]*\})? '
                        r'[-+0-9.einfEINF]+$')
    samples = [ln for ln in text.splitlines() if not ln.startswith("#")]
    assert samples and all(sample.match(ln) for ln in samples), samples
    # histogram: cumulative buckets are monotone and +Inf == _count
    cums = [float(ln.rsplit(" ", 1)[1]) for ln in samples
            if ln.startswith("lat_seconds_bucket")]
    assert cums == sorted(cums) and cums[-1] == 1.0
    assert re.search(r"^lat_seconds_count\{.*\} 1$", text, re.M)


def test_snapshot_is_json_ready():
    obs = Observability()
    obs.tokens.inc(3)
    obs.h_ttft.observe(0.01)
    json.dumps(obs.snapshot())                   # must not raise
    json.dumps(obs.tracer.chrome_trace())


# --------------------------------------------------------------- tracer --

def test_tracer_lifecycle_and_exports(tmp_path):
    tr = Tracer()
    tr.begin(1, "queued", t=0.0)
    tr.end(1, "queued", t=1.0)
    tr.begin(1, "prefill", t=1.0)
    tr.end(1, "wrong-name", t=1.5)               # mismatched close: no-op
    tr.end(1, "prefill", t=2.0, tokens=8)
    tr.begin(1, "decoding", t=2.0)
    tr.instant(1, "first_token", t=2.5)
    tr.terminate(1, "retired", t=3.0)            # closes open decoding span
    tr.terminate(1, "cancelled", t=9.0)          # idempotent: first wins
    got = tr.get(1)
    assert got.status == "retired"
    assert [s.name for s in got.spans] == ["queued", "prefill", "decoding"]
    assert got.spans[1].args == {"tokens": 8}
    got.validate()
    tr.validate_all()

    n = tr.export_jsonl(str(tmp_path / "t.jsonl"))
    assert n == 1
    row = json.loads((tmp_path / "t.jsonl").read_text().splitlines()[0])
    assert row["status"] == "retired" and len(row["spans"]) == 3

    tr.step_event("step", 0.0, 0.5, n_decoding=1)
    chrome = tr.chrome_trace()
    names = {e["ph"] for e in chrome["traceEvents"]}
    assert {"M", "X", "i"} <= names
    tids = {e["tid"] for e in chrome["traceEvents"] if e["ph"] == "X"}
    assert 0 in tids and 2 in tids               # engine track + rid 1
    n = tr.export_chrome_trace(str(tmp_path / "t.trace.json"))
    assert n == len(chrome["traceEvents"])
    json.loads((tmp_path / "t.trace.json").read_text())


def test_tracer_validate_catches_malformed():
    tr = Tracer()
    tr.begin(5, "queued", t=0.0)
    with pytest.raises(AssertionError):          # open span at terminal
        tr.get(5).validate()
    tr.terminate(5, "retired", t=1.0)
    tr.get(5).validate()
    bad = Tracer()
    bad.begin(6, "a", t=0.0)
    bad.end(6, "a", t=2.0)
    bad.begin(6, "b", t=1.0)                     # overlaps span a
    bad.end(6, "b", t=3.0)
    bad.terminate(6, "retired", t=3.0)
    with pytest.raises(AssertionError):
        bad.get(6).validate()


def test_tracer_evicts_only_terminal():
    tr = Tracer(max_traces=2)
    tr.begin(1, "queued", t=0.0)
    tr.terminate(1, "retired", t=1.0)
    tr.begin(2, "queued", t=0.0)                 # live
    tr.begin(3, "queued", t=0.0)                 # forces eviction of rid 1
    rids = {t.rid for t in tr.traces()}
    assert rids == {2, 3}


# ------------------------------------------------------------- timeline --

def test_timeline_ring_bounds_and_order():
    tl = StepTimeline(capacity=3)
    assert len(tl) == 0 and tl.last() is None
    # regression: an EMPTY timeline is falsy (len 0) but must still accept
    # appends — guards have to be `is not None`, not truthiness
    assert not tl and tl is not None
    for i in range(5):
        tl.append(StepRecord(step=i, t=float(i), host_s=0.0, dispatch_s=0.0,
                             n_decoding=1, n_chunking=0, n_queued=0,
                             tokens_emitted=1, prefill_tokens=0,
                             chunk_tokens=0))
    assert len(tl) == 3 and tl.total_steps == 5
    assert [r.step for r in tl.snapshot()] == [2, 3, 4]   # oldest first
    assert tl.last().step == 4
    with pytest.raises(ValueError):
        StepTimeline(capacity=0)


# ----------------------------------------------------- engine integration --

def _workload(cfg, rng, n=4, shared=0):
    sys_p = rng.integers(0, cfg.vocab_size, shared)
    reqs = []
    for _ in range(n):
        tail = rng.integers(0, cfg.vocab_size, int(rng.integers(2, 10)))
        reqs.append(np.concatenate([sys_p, tail]).astype(np.int32))
    return reqs


def _run(mode, obs, n_slots=2, max_new=5, shared=0):
    cfg = get_config("tiny-dense")
    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(7)
    eng = Engine(cfg, params, max_len=MAX_LEN, n_slots=n_slots, obs=obs,
                 **MODES[mode])
    rids = [eng.submit(p, max_new)
            for p in _workload(cfg, rng, shared=shared)]
    out = eng.run()
    return eng, {r: tuple(out[r]) for r in rids}


@pytest.mark.parametrize("mode", list(MODES))
def test_engine_obs_zero_dispatch_and_counters(mode, monkeypatch):
    shared = 2 * PAGE_SIZE if "shared" in mode or mode == "prefix" else 0
    annotated = []
    real_annotate = Observability.annotate

    def counting(self, name):
        annotated.append(name)
        return real_annotate(self, name)
    monkeypatch.setattr(Observability, "annotate", counting)
    obs = Observability()
    eng_on, out_on = _run(mode, obs, shared=shared)
    n_annotated = len(annotated)
    assert n_annotated > 0
    eng_off, out_off = _run(mode, None, shared=shared)
    assert len(annotated) == n_annotated       # obs=None: no annotate call
    # obs is host-side bookkeeping only: identical tokens + device work
    assert out_on == out_off
    assert eng_on.n_fused_dispatches == eng_off.n_fused_dispatches
    assert eng_on.n_legacy_dispatches == eng_off.n_legacy_dispatches
    assert eng_on.n_decode_steps == eng_off.n_decode_steps
    assert eng_on.n_prefills == eng_off.n_prefills
    assert eng_on.n_prefill_tokens == eng_off.n_prefill_tokens
    # registry counters == the engine's own counters
    assert obs.decode_steps.value == eng_on.n_decode_steps
    assert obs.prefills.value == eng_on.n_prefills
    assert obs.prefill_tokens.value == eng_on.n_prefill_tokens
    assert obs.chunks.value == eng_on.n_chunks
    assert obs.finished.value == eng_on.n_finished == len(out_on)
    assert obs.tokens.value == \
        sum(len(t) for t in out_on.values()) + obs.tokens_discarded.value
    assert obs.submitted.value == len(out_on)
    assert obs.prefix_hits.value == eng_on.n_prefix_hits
    # spans: every request retired with a well-formed tree
    for rid in out_on:
        t = obs.tracer.get(rid)
        assert t is not None and t.status == "retired"
        t.validate()
        assert t.spans[0].name == "queued"
        assert t.spans[-1].name == "decoding"
        assert any(e[0] == "first_token" for e in t.events)
    # timeline recorded every step (incl. the falsy-when-empty first one)
    assert len(obs.timeline) > 0
    assert obs.timeline.last().step == obs.timeline.total_steps - 1
    # histograms saw every request
    assert obs.h_ttft.count == len(out_on)
    assert obs.h_latency.count == len(out_on)


class _SpanLog(Observability):
    """Records every annotate enter and exit, in order, as a tree."""

    def __init__(self):
        super().__init__(trace=False, timeline_capacity=0)
        self.root = {"name": None, "children": []}
        self._stack = [self.root]

    @contextmanager
    def annotate(self, name):
        node = {"name": name, "children": []}
        self._stack[-1]["children"].append(node)
        self._stack.append(node)
        try:
            yield
        finally:
            assert self._stack.pop() is node


STEP_PHASES = ["nbl.admission", "nbl.plan", "nbl.fused_step",
               "nbl.readback", "nbl.commit"]


@pytest.mark.parametrize("mode", ["paged", "chunked"])
def test_fused_step_phase_spans(mode):
    """Each engine step is one ``nbl.step`` span; a step that launches the
    fused dispatch holds the five phase spans once each, in order, and the
    admission prefills nest inside ``nbl.admission``."""
    obs = _SpanLog()
    eng, _ = _run(mode, obs)
    assert eng.fused and eng.n_fused_dispatches > 0
    steps = obs.root["children"]
    assert {s["name"] for s in steps} == {"nbl.step"}
    fused = [s for s in steps
             if any(c["name"] == "nbl.fused_step" for c in s["children"])]
    assert len(fused) == eng.n_fused_dispatches
    for s in fused:
        assert [c["name"] for c in s["children"]] == STEP_PHASES
    for s in steps:
        adm = s["children"][0]
        assert adm["name"] == "nbl.admission"
        assert {c["name"] for c in adm["children"]} <= {"nbl.prefill"}
    n_prefill = sum(len(s["children"][0]["children"]) for s in steps)
    assert n_prefill == (0 if mode == "chunked" else eng.n_prefills)


@pytest.mark.parametrize("state", ["queued", "chunking", "decoding"])
def test_cancel_span_wellformed_in_every_state(state):
    cfg = get_config("tiny-dense")
    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(3)
    obs = Observability()
    eng = Engine(cfg, params, max_len=MAX_LEN, n_slots=1, obs=obs,
                 **MODES["chunked_shared"])
    decoy = eng.submit(rng.integers(0, cfg.vocab_size, 4).astype(np.int32),
                       6)
    victim = eng.submit(
        rng.integers(0, cfg.vocab_size, 16).astype(np.int32), 4)
    if state == "queued":
        pass                                      # slot 0 busy: never admitted
    elif state == "chunking":
        eng.step()                                # decoy admitted + decoding
        while not eng.finished.get(decoy):
            eng.step()
        eng.step()                                # victim starts chunking
        assert eng.slot_chunk_pos[0] >= 0         # mid-prompt
    else:
        while not eng.finished.get(decoy):
            eng.step()
        while not eng.finished.get(victim) and \
                not any(r is not None and r.rid == victim and r.tokens
                        for r in eng.slot_req):
            eng.step()                            # victim has emitted
    assert eng.cancel(victim)
    assert not eng.cancel(victim)                 # already terminal
    eng.run()
    t = obs.tracer.get(victim)
    assert t.status == "cancelled"
    t.validate()
    obs.tracer.validate_all()
    assert obs.cancelled.value == eng.n_cancelled == 1
    if state == "chunking":
        assert any(s.name == "chunk" for s in t.spans)
    assert eng.allocator.in_use == eng.prefix_index.n_entries


def test_stats_windowing_keeps_lifetime_n():
    cfg = get_config("tiny-dense")
    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(5)
    eng = Engine(cfg, params, max_len=MAX_LEN, n_slots=2, stats_window=2)
    for _ in range(5):
        eng.submit(rng.integers(0, cfg.vocab_size, 6).astype(np.int32), 3)
    eng.run()
    s = eng.stats()
    assert s["n"] == 5                            # lifetime served count
    assert s["window_n"] == 2                     # percentile subset
    # unbounded window: no clipping marker
    eng2 = Engine(cfg, params, max_len=MAX_LEN, n_slots=2, stats_window=None)
    for _ in range(3):
        eng2.submit(rng.integers(0, cfg.vocab_size, 6).astype(np.int32), 3)
    eng2.run()
    s2 = eng2.stats()
    assert s2["n"] == 3 and "window_n" not in s2


def test_chrome_trace_shows_interleaving():
    """Acceptance: in a chunked+shared workload the exported trace makes
    the interleaving visible — decode-carrying engine step events overlap
    the window spanned by the long request's chunk spans."""
    cfg = get_config("tiny-dense")
    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(11)
    obs = Observability()
    eng = Engine(cfg, params, max_len=MAX_LEN, n_slots=3, obs=obs,
                 **MODES["chunked_shared"])
    sys_p = rng.integers(0, cfg.vocab_size, 2 * PAGE_SIZE)
    shorts = [np.concatenate(
        [sys_p, rng.integers(0, cfg.vocab_size, 2)]).astype(np.int32)
        for _ in range(2)]
    for p in shorts:
        eng.submit(p, 12)
    eng.step()                                    # shorts admitted, decoding
    eng.step()
    long_p = np.concatenate(
        [sys_p, rng.integers(0, cfg.vocab_size, 16)]).astype(np.int32)
    lid = eng.submit(long_p, 3)
    eng.run()
    assert eng.n_interleaved_decode_steps >= 1
    assert obs.interleaved.value == eng.n_interleaved_decode_steps
    assert obs.prefix_hits.value >= 1             # shared prefix was reused

    t = obs.tracer.get(lid)
    chunks = sorted((s for s in t.spans if s.name == "chunk"),
                    key=lambda s: s.t0)
    assert len(chunks) >= 2                       # genuinely chunked
    chrome = obs.tracer.chrome_trace()
    lo, hi = chunks[0].t0, chunks[-1].t1
    lo_us = (lo - obs.tracer._t0) * 1e6
    hi_us = (hi - obs.tracer._t0) * 1e6
    interleaved = [
        e for e in chrome["traceEvents"]
        if e.get("tid") == 0 and e.get("ph") == "X"
        and e["args"].get("n_decoding", 0) > 0
        and e["args"].get("n_chunking", 0) > 0
        and e["ts"] < hi_us and e["ts"] + e["dur"] > lo_us]
    assert interleaved, "no decode step overlaps the chunk window"
