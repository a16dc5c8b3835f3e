"""Engine-wide observability: metrics registry + request traces + timeline.

Three complementary views over one serving engine, all dependency-free and
HOST-side only (recording never adds a device dispatch):

``MetricsRegistry`` (registry.py)
    Counters / gauges / histograms with fixed log-spaced latency buckets,
    labeled by engine mode and NBL-m, snapshot-consistent under the
    AsyncEngine step-loop thread. Rendered as JSON or Prometheus text
    exposition (the server's ``metrics`` op).
``Tracer`` (trace.py)
    Per-request lifecycle spans (queued -> [chunk x N | prefill] ->
    decoding -> terminal, with preempt/suspend/first-token instants) plus
    an engine step track, exportable as JSONL or a Chrome-trace/Perfetto
    file.
``StepTimeline`` (timeline.py)
    Ring buffer of per-``step()`` records: decode batch size, chunk tokens,
    allocator occupancy + refcount distribution, PrefixIndex size and LRU
    evictions, host-vs-dispatch wall split.

:class:`Observability` bundles the three behind the HOOK surface the
engine calls (``on_submit`` / ``on_admit`` / ``on_step`` / ...). The
engine holds ``obs=None`` by default and guards every hook call with one
``is not None`` branch, so the disabled hot path pays a single branch and
nothing else. ``python -m repro.launch.server`` enables it by default
(``--no-obs`` to disable); see docs/observability.md for the metric
catalog and span schema.

This module (and only this module) owns ``time.perf_counter`` — engine
code uses the monotonic lifecycle clock, and scripts/ci.sh lints that no
new raw perf_counter call sites appear outside ``obs/``.
"""
from __future__ import annotations

import time
from contextlib import nullcontext
from typing import Optional

from repro.obs.registry import (  # noqa: F401  (re-exported)
    LATENCY_BUCKETS, Counter, Gauge, Histogram, MetricsRegistry,
)
from repro.obs.timeline import StepRecord, StepTimeline  # noqa: F401
from repro.obs.trace import RequestTrace, Span, Tracer   # noqa: F401

clock = time.perf_counter       # the one sanctioned high-res timer


class Observability:
    """The hook layer the engine drives; owns registry + tracer + timeline.

    Default level records everything except jax profiler annotations.
    ``trace_annotations=True`` makes every ``annotate`` site a
    ``jax.profiler.TraceAnnotation``: the jit calls (``nbl.prefill``,
    ``nbl.decode``, ``nbl.fused_step``, ``nbl.spec_*``) and the engine
    step's phases (``nbl.step`` enclosing ``nbl.admission``,
    ``nbl.plan``, ``nbl.readback``, ``nbl.commit``). The spans land in the
    profiler's own timeline, on the device trace's clock, so an idle gap
    on the device can be put down to a host phase. ``trace=False`` /
    ``timeline_capacity=0`` shed the corresponding subsystem; the
    registry always exists.
    """

    def __init__(self, *, labels: Optional[dict] = None, trace: bool = True,
                 timeline_capacity: int = 1024,
                 trace_annotations: bool = False, max_traces: int = 4096):
        self.registry = MetricsRegistry(labels=labels)
        self.tracer = Tracer(max_traces=max_traces) if trace else None
        self.timeline = StepTimeline(timeline_capacity) \
            if timeline_capacity else None
        self.trace_annotations = bool(trace_annotations)
        self._null = nullcontext()       # shared: annotate() allocates 0
        self._last_evictions = 0         # delta base for the counter
        self._last_fused = 0             # dispatch-counter delta bases:
        self._last_legacy = 0            # registry == engine, end-of-step

        r = self.registry
        # --- metric catalog (docs/observability.md) --- counters
        self.submitted = r.counter(
            "nbl_requests_submitted_total", "requests accepted into the queue")
        self.admitted = r.counter(
            "nbl_requests_admitted_total",
            "admissions into a slot (re-admission after preemption counts)")
        self.finished = r.counter(
            "nbl_requests_finished_total", "requests retired EOS/max-token")
        self.rejected = r.counter(
            "nbl_requests_rejected_total", "reject-with-error drops")
        self.cancelled = r.counter(
            "nbl_requests_cancelled_total", "cancel() terminal retirements")
        self.tokens = r.counter(
            "nbl_tokens_emitted_total",
            "every generated token emission (preemption replays re-count)")
        self.tokens_discarded = r.counter(
            "nbl_tokens_discarded_total",
            "generated tokens discarded by preemption restarts")
        self.prefills = r.counter(
            "nbl_prefills_total", "prefill jit dispatches (chunks count)")
        self.prefill_tokens = r.counter(
            "nbl_prefill_tokens_total", "valid (unpadded) tokens prefilled")
        self.decode_steps = r.counter(
            "nbl_decode_steps_total", "batched decode dispatches")
        self.chunks = r.counter(
            "nbl_chunks_total", "chunked-prefill chunks processed")
        self.chunk_tokens = r.counter(
            "nbl_chunk_tokens_total", "prompt tokens prefilled via chunks")
        self.interleaved = r.counter(
            "nbl_interleaved_decode_steps_total",
            "decode steps emitted while a prompt was mid-chunking")
        self.preemptions = r.counter(
            "nbl_preemptions_total", "mid-flight preemption restarts")
        self.evictions = r.counter(
            "nbl_prefix_evictions_total", "PrefixIndex LRU pages evicted")
        self.prefix_hits = r.counter(
            "nbl_prefix_hits_total", "admissions served a cached prefix")
        self.shared_tokens = r.counter(
            "nbl_shared_prompt_tokens_total",
            "prompt tokens skipped via prefix sharing")
        self.spec_bursts = r.counter(
            "nbl_spec_bursts_total",
            "speculative draft-and-verify bursts (one draft scan + one "
            "verifier cache-extend each)")
        self.spec_draft_tokens = r.counter(
            "nbl_spec_draft_tokens_total",
            "draft tokens proposed by speculative bursts")
        self.spec_accepted = r.counter(
            "nbl_spec_accepted_tokens_total",
            "draft-origin tokens accepted and actually emitted")
        self.spec_tokens = r.counter(
            "nbl_spec_tokens_total",
            "tokens emitted by speculative bursts (accepted + corrections)")
        self.fused_dispatches = r.counter(
            "nbl_fused_dispatches_total",
            "fused-step jit launches (ONE per fused step with work)")
        self.legacy_dispatches = r.counter(
            "nbl_legacy_dispatches_total",
            "legacy step-path jit launches (batched decode + chunk "
            "prefills — the dispatches the fused jit replaces)")
        # --- gauges
        self.g_queue = r.gauge("nbl_queue_depth", "scheduler queue length")
        self.g_active = r.gauge("nbl_slots_active", "occupied slots")
        self.g_slots = r.gauge("nbl_slots_total", "engine slot count")
        self.g_pages_used = r.gauge("nbl_pages_in_use", "allocator occupancy")
        self.g_pages_free = r.gauge("nbl_pages_free", "allocator free pages")
        self.g_kv_pool = r.gauge(
            "nbl_kv_pool_bytes_per_device",
            "KV page-pool bytes on the fullest device, set at engine build")
        self.g_prefix = r.gauge("nbl_prefix_index_entries",
                                "PrefixIndex published pages")
        self.g_budget_util = r.gauge(
            "nbl_step_budget_utilization",
            "last step's planned tokens / step_tokens budget "
            "(0.0 when unbudgeted or on the legacy path)")
        # --- histograms (fixed log-spaced latency buckets)
        self.h_ttft = r.histogram("nbl_ttft_seconds",
                                  "submit -> first token")
        self.h_latency = r.histogram("nbl_request_latency_seconds",
                                     "submit -> terminal")
        self.h_queue_delay = r.histogram("nbl_queue_delay_seconds",
                                         "submit -> admission")
        self.h_step_host = r.histogram("nbl_step_host_seconds",
                                       "full step() wall time")
        self.h_step_dispatch = r.histogram(
            "nbl_step_dispatch_seconds",
            "decode jit call + logits device->host inside step()")

    # ------------------------------------------------------------- hooks --

    def bind(self, **labels) -> None:
        self.registry.bind(**labels)

    def annotate(self, name: str):
        """Context manager around a jit call site or a phase of the
        engine step: a jax profiler TraceAnnotation when enabled (device
        profile rows line up with the host timeline), else a no-op."""
        if self.trace_annotations:
            from jax.profiler import TraceAnnotation
            return TraceAnnotation(name)
        return self._null

    def on_submit(self, req, queue_depth: int) -> None:
        self.submitted.inc()
        self.g_queue.set(queue_depth)
        if self.tracer:
            self.tracer.begin(req.rid, "queued", t=req.t_submit)

    def on_reject(self, req, now: float) -> None:
        self.rejected.inc()
        if self.tracer:
            self.tracer.terminate(req.rid, "rejected", t=now)

    def on_admit(self, req, now: float, chunked: bool) -> None:
        self.admitted.inc()
        self.h_queue_delay.observe(max(0.0, now - req.t_submit))
        if self.tracer:
            if not self.tracer.has_open(req.rid, "queued"):
                # direct Scheduler.submit bypassed the traced submit path:
                # synthesize the queued span so the lifecycle stays whole
                self.tracer.begin(req.rid, "queued", t=req.t_submit)
            self.tracer.end(req.rid, "queued", t=now)
            if not chunked:
                self.tracer.begin(req.rid, "prefill", t=now)

    def on_prefill_done(self, req, now: float, n_tokens: int) -> None:
        """Non-chunked admission prefill completed; decoding begins."""
        if self.tracer:
            self.tracer.end(req.rid, "prefill", t=now, tokens=n_tokens)
            self.tracer.begin(req.rid, "decoding", t=now)

    def on_chunk(self, req, t0: float, t1: float, start: int, end: int,
                 final: bool) -> None:
        self.chunks.inc()
        self.chunk_tokens.inc(end - start)
        if self.tracer:
            self.tracer.begin(req.rid, "chunk", t=t0, start=start)
            self.tracer.end(req.rid, "chunk", t=t1, end=end)
            if final:
                self.tracer.begin(req.rid, "decoding", t=t1)

    def on_suspend(self, req, now: float) -> None:
        if self.tracer:
            self.tracer.instant(req.rid, "suspend", t=now)

    def on_token(self, req, first: bool, now: float) -> None:
        self.tokens.inc()
        if first:
            self.h_ttft.observe(max(0.0, now - req.t_submit))
            if self.tracer:
                self.tracer.instant(req.rid, "first_token", t=now)

    def on_retire(self, req, now: float) -> None:
        self.finished.inc()
        self.h_latency.observe(max(0.0, now - req.t_submit))
        if self.tracer:
            self.tracer.end(req.rid, "decoding", t=now,
                            tokens=len(req.tokens))
            self.tracer.terminate(req.rid, "retired", t=now)

    def on_cancel(self, req, now: float) -> None:
        self.cancelled.inc()
        if self.tracer:
            self.tracer.terminate(req.rid, "cancelled", t=now)

    def on_preempt(self, req, now: float, n_discarded: int) -> None:
        self.preemptions.inc()
        self.tokens_discarded.inc(n_discarded)
        if self.tracer:
            # whatever was open (decoding; chunking slots close their chunk
            # spans every step) ends here, and the request re-queues
            self.tracer.end(req.rid, "decoding", t=now)
            self.tracer.instant(req.rid, "preempt", t=now)
            self.tracer.begin(req.rid, "queued", t=now)

    def on_prefix_hit(self, req, n_shared_tokens: int) -> None:
        self.prefix_hits.inc()
        self.shared_tokens.inc(n_shared_tokens)

    def on_spec_burst(self, req, t0: float, t1: float, gamma: int,
                      n_accepted: int, n_emitted: int) -> None:
        """One speculative draft-and-verify burst for ``req``: γ draft
        tokens proposed, ``n_accepted`` of them emitted (post-truncation —
        tokens past max_new/EOS never count) plus the verifier's
        correction for ``n_emitted`` total. Fired BEFORE the burst's
        token emissions so the span precedes any terminal transition the
        final token triggers."""
        self.spec_bursts.inc()
        self.spec_draft_tokens.inc(gamma)
        self.spec_accepted.inc(n_accepted)
        self.spec_tokens.inc(n_emitted)
        if self.tracer:
            # request tracks are FLAT (validate() forbids overlap), so the
            # burst is spliced into the decoding span rather than nested:
            # decoding ends at burst start and reopens at burst end — the
            # reopened span is what retire/preempt later closes
            self.tracer.end(req.rid, "decoding", t=t0)
            self.tracer.begin(req.rid, "spec", t=t0, gamma=gamma)
            self.tracer.end(req.rid, "spec", t=t1, accepted=n_accepted,
                            emitted=n_emitted)
            self.tracer.begin(req.rid, "decoding", t=t1)

    def on_prefill(self, n_tokens: int) -> None:
        self.prefills.inc()
        self.prefill_tokens.inc(n_tokens)

    def on_step(self, engine, *, t0: float, t1: float, dispatch_s: float,
                n_decoding: int, n_chunking: int, tokens_emitted: int,
                prefill_tokens: int, chunk_tokens: int,
                tokens_planned: int = 0,
                budget_utilization: float = 0.0) -> None:
        """End-of-step rollup: counters, gauges, step histograms, the
        engine trace track, and one StepRecord. Reads only host state."""
        host_s = t1 - t0
        self.h_step_host.observe(host_s)
        if n_decoding:
            self.decode_steps.inc()
            self.h_step_dispatch.observe(dispatch_s)
            if n_chunking:
                self.interleaved.inc()
        # dispatch-split counters mirror the engine's lifetime counts via
        # one end-of-step delta (the evictions pattern): registry ==
        # engine exactly, wherever inside the step the dispatch happened
        fused_cum = getattr(engine, "n_fused_dispatches", 0)
        legacy_cum = getattr(engine, "n_legacy_dispatches", 0)
        self.fused_dispatches.inc(fused_cum - self._last_fused)
        self.legacy_dispatches.inc(legacy_cum - self._last_legacy)
        self._last_fused, self._last_legacy = fused_cum, legacy_cum
        self.g_budget_util.set(budget_utilization)
        n_queued = len(engine.scheduler)
        self.g_queue.set(n_queued)
        self.g_active.set(len(engine.active_slots))
        self.g_slots.set(engine.n_slots)
        rec = StepRecord(
            step=(self.timeline.total_steps
                  if self.timeline is not None else 0),
            t=t0, host_s=host_s, dispatch_s=dispatch_s,
            n_decoding=n_decoding, n_chunking=n_chunking, n_queued=n_queued,
            tokens_emitted=tokens_emitted, prefill_tokens=prefill_tokens,
            chunk_tokens=chunk_tokens,
            preemptions_cum=engine.n_preemptions,
            tokens_planned=tokens_planned,
            budget_utilization=budget_utilization,
            fused_dispatches_cum=fused_cum,
            legacy_dispatches_cum=legacy_cum)
        if engine.paged:
            alloc = engine.allocator
            rec.pages_in_use = alloc.in_use
            rec.pages_free = alloc.free_pages
            rec.refcounts = alloc.refcount_histogram()
            self.g_pages_used.set(alloc.in_use)
            self.g_pages_free.set(alloc.free_pages)
            if engine.prefix_index is not None:
                rec.prefix_entries = engine.prefix_index.n_entries
                rec.evictions_cum = engine.prefix_index.n_evictions
                self.g_prefix.set(engine.prefix_index.n_entries)
                # evictions happen at several sites inside step() (reclaim
                # during admission / chunking / decode-page faults); one
                # end-of-step delta keeps the counter == n_evictions exact
                self.evictions.inc(rec.evictions_cum - self._last_evictions)
                self._last_evictions = rec.evictions_cum
        if self.timeline is not None:
            self.timeline.append(rec)
        if self.tracer:
            self.tracer.step_event(
                "step", t0, t1, n_decoding=n_decoding,
                n_chunking=n_chunking, tokens=tokens_emitted,
                chunk_tokens=chunk_tokens, dispatch_s=round(dispatch_s, 6))

    # ------------------------------------------------------------ exports --

    def snapshot(self) -> dict:
        """Registry snapshot plus the latest step record (JSON-ready)."""
        out = self.registry.snapshot()
        if self.timeline is not None:
            last = self.timeline.last()
            if last is not None:
                from dataclasses import asdict
                out["last_step"] = asdict(last)
        return out

    def render_prometheus(self) -> str:
        return self.registry.render_prometheus()
