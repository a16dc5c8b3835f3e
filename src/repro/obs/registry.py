"""Metrics registry: Counter / Gauge / Histogram with Prometheus rendering.

Dependency-free and host-side only — instruments are plain Python numbers
behind one registry lock, so a snapshot is CONSISTENT (no torn reads of a
histogram's count vs its buckets) even while the AsyncEngine step-loop
thread and client threads mutate concurrently. Nothing here ever touches a
jax array: recording a metric can never add a device dispatch.

Registry-level ``labels`` (the engine binds ``engine_mode`` and ``nbl_m``
at construction) are rendered into every series, so two engines' scrapes
are distinguishable without per-instrument label plumbing.

``LATENCY_BUCKETS`` is the single fixed log-spaced bucket ladder every
latency histogram uses: 4 buckets per decade from 10 µs to 100 s. Fixed
buckets keep ``observe`` O(log n_buckets) (bisect) with zero allocation,
and make any two histograms directly comparable.
"""
from __future__ import annotations

import threading
from bisect import bisect_left
from typing import Optional

# 4 log-spaced buckets per decade, 1e-5 s .. 1e2 s (29 upper bounds);
# +Inf is implicit (count - last cumulative bucket).
LATENCY_BUCKETS: tuple = tuple(
    round(10.0 ** (e / 4.0), 12) for e in range(-20, 9))


class Counter:
    """Monotone float/int counter. ``inc`` only; never reset in place."""

    __slots__ = ("name", "help", "_value", "_lock")

    def __init__(self, name: str, help: str, lock: threading.Lock):
        self.name, self.help = name, help
        self._value = 0                      # guarded-by: _lock
        self._lock = lock

    def inc(self, n=1) -> None:
        if n < 0:
            raise ValueError(f"counter {self.name}: inc({n}) < 0")
        with self._lock:
            self._value += n

    @property
    def value(self):
        # single attribute read: GIL-atomic, no torn state possible, and
        # taking the shared registry lock here would let a hot probe loop
        # contend with the step thread's inc()
        return self._value  # nbl: disable=guarded-by -- lock-free single read is GIL-atomic


class Gauge:
    """Point-in-time value, replaced by each ``set``."""

    __slots__ = ("name", "help", "_value", "_lock")

    def __init__(self, name: str, help: str, lock: threading.Lock):
        self.name, self.help = name, help
        self._value = 0                      # guarded-by: _lock
        self._lock = lock

    def set(self, v) -> None:
        with self._lock:
            self._value = v

    @property
    def value(self):
        return self._value  # nbl: disable=guarded-by -- lock-free single read is GIL-atomic


class Histogram:
    """Fixed-bucket histogram (cumulative counts rendered Prometheus-style).

    ``buckets`` are the UPPER bounds (sorted ascending); an observation
    lands in the first bucket whose bound is >= the value, or the implicit
    +Inf overflow. ``percentile`` interpolates within the winning bucket —
    good enough for a live ticker, not a substitute for the exact
    percentiles ``latency_stats`` computes over retained requests.
    """

    __slots__ = ("name", "help", "buckets", "_counts", "_sum", "_count",
                 "_lock")

    def __init__(self, name: str, help: str, lock: threading.Lock,
                 buckets: tuple = LATENCY_BUCKETS):
        if list(buckets) != sorted(buckets) or len(set(buckets)) != \
                len(buckets):
            raise ValueError("histogram buckets must be strictly ascending")
        self.name, self.help = name, help
        self.buckets = tuple(float(b) for b in buckets)
        self._counts = [0] * (len(self.buckets) + 1)   # [+Inf] is last # guarded-by: _lock
        self._sum = 0.0                      # guarded-by: _lock
        self._count = 0                      # guarded-by: _lock
        self._lock = lock

    def observe(self, v) -> None:
        v = float(v)
        i = bisect_left(self.buckets, v)
        with self._lock:
            self._counts[i] += 1
            self._sum += v
            self._count += 1

    @property
    def count(self) -> int:
        return self._count  # nbl: disable=guarded-by -- lock-free single read is GIL-atomic

    @property
    def sum(self) -> float:
        return self._sum  # nbl: disable=guarded-by -- lock-free single read is GIL-atomic

    def percentile(self, q: float) -> float:
        """Bucket-interpolated percentile, q in [0, 100]. 0.0 when empty."""
        with self._lock:
            total = self._count
            counts = list(self._counts)
        if total == 0:
            return 0.0
        rank = max(1, int(round(q / 100.0 * total)))
        cum = 0
        for i, c in enumerate(counts):
            cum += c
            if cum >= rank:
                hi = self.buckets[i] if i < len(self.buckets) \
                    else self.buckets[-1]
                lo = self.buckets[i - 1] if i > 0 else 0.0
                frac = (rank - (cum - c)) / max(1, c)
                return lo + (hi - lo) * frac
        return self.buckets[-1]


def _fmt_value(v) -> str:
    if isinstance(v, float):
        return repr(v)
    return str(v)


def _fmt_labels(labels: dict) -> str:
    if not labels:
        return ""
    inner = ",".join(f'{k}="{v}"' for k, v in sorted(labels.items()))
    return "{" + inner + "}"


class MetricsRegistry:
    """Instrument factory + consistent snapshot + Prometheus rendering.

    One registry per engine; ``counter``/``gauge``/``histogram`` are
    idempotent by name (the existing instrument is returned, so two code
    paths can share a series). ``snapshot`` and ``render_prometheus`` take
    the registry lock once, so a scrape mid-step never observes a
    histogram whose count and buckets disagree.
    """

    def __init__(self, labels: Optional[dict] = None):
        self._lock = threading.Lock()
        self.labels: dict = dict(labels or {})   # guarded-by: _lock
        self._metrics: dict = {}   # name -> instrument # guarded-by: _lock

    def bind(self, **labels) -> None:
        """Set registry labels that are not already set (the engine binds
        ``engine_mode``/``nbl_m`` defaults without clobbering a caller's)."""
        with self._lock:
            for k, v in labels.items():
                self.labels.setdefault(k, str(v))

    def _make(self, cls, name: str, help: str, **kw):
        with self._lock:
            inst = self._metrics.get(name)
            if inst is None:
                inst = cls(name, help, self._lock, **kw)
                self._metrics[name] = inst
            elif not isinstance(inst, cls):
                raise ValueError(f"metric {name!r} already registered as "
                                 f"{type(inst).__name__}")
            return inst

    def counter(self, name: str, help: str = "") -> Counter:
        return self._make(Counter, name, help)

    def gauge(self, name: str, help: str = "") -> Gauge:
        return self._make(Gauge, name, help)

    def histogram(self, name: str, help: str = "",
                  buckets: tuple = LATENCY_BUCKETS) -> Histogram:
        return self._make(Histogram, name, help, buckets=buckets)

    def snapshot(self) -> dict:
        """Consistent point-in-time copy of every series, JSON-ready."""
        with self._lock:
            out: dict = {"labels": dict(self.labels), "counters": {},
                         "gauges": {}, "histograms": {}}
            for name, m in self._metrics.items():
                if isinstance(m, Counter):
                    out["counters"][name] = m._value
                elif isinstance(m, Gauge):
                    out["gauges"][name] = m._value
                else:
                    cum, buckets = 0, []
                    for b, c in zip(m.buckets, m._counts):
                        cum += c
                        buckets.append([b, cum])
                    out["histograms"][name] = {
                        "count": m._count, "sum": m._sum, "buckets": buckets}
        return out

    def render_prometheus(self) -> str:
        """Text exposition format (one consistent scrape)."""
        with self._lock:
            labels = dict(self.labels)
            items = list(self._metrics.items())
            rows: dict = {}
            for name, m in items:
                if isinstance(m, Histogram):
                    rows[name] = ("histogram", m._count, m._sum,
                                  list(m._counts), m.buckets, m.help)
                else:
                    kind = "counter" if isinstance(m, Counter) else "gauge"
                    rows[name] = (kind, m._value, m.help)
        lines: list = []
        for name, row in rows.items():
            kind = row[0]
            help = row[-1]
            if help:
                lines.append(f"# HELP {name} {help}")
            lines.append(f"# TYPE {name} {kind}")
            if kind == "histogram":
                _, count, total, counts, buckets, _ = row
                cum = 0
                for b, c in zip(buckets, counts):
                    cum += c
                    lb = _fmt_labels({**labels, "le": repr(float(b))})
                    lines.append(f"{name}_bucket{lb} {cum}")
                lb = _fmt_labels({**labels, "le": "+Inf"})
                lines.append(f"{name}_bucket{lb} {count}")
                lines.append(f"{name}_sum{_fmt_labels(labels)} "
                             f"{_fmt_value(total)}")
                lines.append(f"{name}_count{_fmt_labels(labels)} {count}")
            else:
                _, value, _ = row
                lines.append(f"{name}{_fmt_labels(labels)} "
                             f"{_fmt_value(value)}")
        return "\n".join(lines) + "\n"
