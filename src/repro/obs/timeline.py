"""Step timeline: a bounded ring buffer of per-``step()`` engine records.

Where the registry answers "how much, total" and the tracer answers "what
happened to request X", the timeline answers "what did the ENGINE do on
each of the last N steps": decode batch size, chunk tokens prefilled,
allocator occupancy and the refcount distribution (how shared the pool
is), PrefixIndex size and cumulative LRU evictions, and the host-vs-
dispatch wall-time split — the breakdown a fused-step optimization pass
has to beat.

Records are plain dataclasses appended by the engine's step loop (one
producer); ``snapshot()`` copies the ring under a lock so a server scrape
never reads a half-written deque. Capacity is fixed at construction
(default 1024 steps) so a long-running server's memory stays bounded.
"""
from __future__ import annotations

import threading
from dataclasses import dataclass, field
from typing import Optional


@dataclass
class StepRecord:
    step: int                    # engine-lifetime step ordinal
    t: float                     # time.monotonic at step start
    host_s: float                # full step wall time
    dispatch_s: float            # decode-jit call + logits device->host
    n_decoding: int              # slots in the batched decode
    n_chunking: int              # slots mid-prompt (chunked prefill)
    n_queued: int                # scheduler depth after admission
    tokens_emitted: int          # step() return value
    prefill_tokens: int          # valid prompt tokens prefilled this step
    chunk_tokens: int            # subset of prefill_tokens via _chunk_step
    pages_in_use: int = 0
    pages_free: int = 0
    refcounts: dict = field(default_factory=dict)  # refcount -> n_pages
    prefix_entries: int = 0
    evictions_cum: int = 0       # PrefixIndex LRU evictions, lifetime
    preemptions_cum: int = 0
    # fused plan->execute->commit pipeline (PR 10): per-step budget
    # pressure and the lifetime dispatch split
    tokens_planned: int = 0      # StepPlan.tokens_planned (0 on legacy)
    budget_utilization: float = 0.0  # planned/budget; 0.0 when unbounded
    fused_dispatches_cum: int = 0    # fused-step jit launches, lifetime
    legacy_dispatches_cum: int = 0   # legacy decode+chunk jit launches


class StepTimeline:
    """Fixed-capacity ring of :class:`StepRecord`."""

    def __init__(self, capacity: int = 1024):
        if capacity < 1:
            raise ValueError(f"timeline capacity must be >= 1, "
                             f"got {capacity}")
        self.capacity = int(capacity)
        self._lock = threading.Lock()
        self._ring: list = []
        self._head = 0                       # next write index once full
        self.total_steps = 0                 # lifetime appends

    def append(self, rec: StepRecord) -> None:
        with self._lock:
            if len(self._ring) < self.capacity:
                self._ring.append(rec)
            else:
                self._ring[self._head] = rec
                self._head = (self._head + 1) % self.capacity
            self.total_steps += 1

    def snapshot(self) -> list:
        """Records oldest-first (a consistent copy)."""
        with self._lock:
            return self._ring[self._head:] + self._ring[:self._head]

    def last(self) -> Optional[StepRecord]:
        with self._lock:
            if not self._ring:
                return None
            return self._ring[(self._head - 1) % len(self._ring)]

    def __len__(self) -> int:
        with self._lock:
            return len(self._ring)
