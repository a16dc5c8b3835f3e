"""Profiler trace -> the numbers the per-layer readers take.

Two steps. ``normalize`` (in the process that held the chip, which alone
can read its trace) turns the profiler's ``.xplane.pb`` into plain JSON:
the device operations of each chip and the host spans the benchmark and
the program annotate. ``reduce`` works on that JSON alone, so the
reduction is checked on a small recorded trace without a chip.

Normalized form::

    {"device": {"<plane>": [[name, start_ns, dur_ns], ...]},   # XLA ops
     "modules": {"<plane>": [[name, start_ns, dur_ns], ...]},  # programs
     "host": [[name, start_ns, dur_ns], ...],                  # spans
     "lines": {"<plane>/<line>": n_events}}                    # inventory
"""
from __future__ import annotations

import re
from collections import defaultdict
from typing import Optional

from .stats import union_length

HOST_PREFIXES = ("nbl.", "bench.")
# the device plane's lines: leaf operations and whole compiled programs
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def normalize(xplane_path: str) -> dict:
    """Read a profiler trace file (needs JAX; run where the trace was
    taken)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(xplane_path)
    out = {"device": {}, "modules": {}, "host": [], "lines": {}}
    for plane in pd.planes:
        for line in plane.lines:
            evs = list(line.events)
            out["lines"][f"{plane.name}/{line.name}"] = len(evs)
            if plane.name.startswith("/device:"):
                key = ("device" if line.name == OPS_LINE else
                       "modules" if line.name == MODULES_LINE else None)
                if key:
                    out[key].setdefault(plane.name, []).extend(
                        [e.name, int(e.start_ns), int(e.duration_ns)]
                        for e in evs)
            elif plane.name.startswith("/host:"):
                out["host"].extend(
                    [e.name, int(e.start_ns), int(e.duration_ns)]
                    for e in evs if e.name.startswith(HOST_PREFIXES))
    return out


def _host_activity(spans: list, t: float) -> str:
    """Innermost annotated host span covering instant ``t``."""
    best, best_len = "host: outside the engine step", None
    for name, s, d in spans:
        if s <= t < s + d and (best_len is None or d < best_len):
            best, best_len = name, d
    return best


def op_family(name: str) -> str:
    """An operation's family: the HLO instruction name without its number
    (``%copy.105 = ...`` -> ``copy``); a custom call is tagged as one."""
    head = name.split(" = ")[0].lstrip("%")
    fam = re.sub(r"\.\d+$", "", head)
    return fam + " (custom-call)" if "custom-call(" in name else fam


def leaves(evs: list) -> list:
    """The events of one device line that contain no other event: the
    line nests a loop's body inside the loop's own event."""
    order = sorted(evs, key=lambda e: (e[1], -e[2]))
    parent = [False] * len(order)
    stack: list = []
    for i, (_, s, d) in enumerate(order):
        while stack and order[stack[-1]][1] + order[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            parent[stack[-1]] = True
        stack.append(i)
    return [e for e, p in zip(order, parent) if not p]


def reduce(norm: dict, *, kernel: str, step_module: str,
           gap_floor_ns: int = 10_000, top: int = 10) -> Optional[dict]:
    """Busy and idle time, kernel and step-program time, and the
    breakdown, over the traced window. ``kernel`` and ``step_module`` are
    regular expressions matched against operation and program names.
    Step programs cut by the window's edges (the first and the last) are
    left out of the step and kernel times. Returns None when the trace
    holds no device operation."""
    planes = sorted(p for p in norm["device"] if norm["device"][p])
    if not planes:
        return None
    t0 = min(s for p in planes for _, s, _ in norm["device"][p])
    t1 = max(s + d for p in planes for _, s, d in norm["device"][p])
    host = [h for h in norm["host"] if h[1] + h[2] >= t0 and h[1] <= t1]
    if host:
        t0 = min(t0, min(h[1] for h in host))
        t1 = max(t1, max(h[1] + h[2] for h in host))
    busy = step_time = kern_time = 0.0
    n_steps = n_kernel = 0
    ops = defaultdict(float)
    gaps = defaultdict(float)
    longest_gap = 0
    kre, sre = re.compile(kernel), re.compile(step_module)
    for p in planes:
        evs = norm["device"][p]
        busy += union_length([(s, s + d) for _, s, d in evs])
        leaf = leaves(evs)
        for name, s, d in leaf:
            ops[op_family(name)] += d
        steps = sorted((s, s + d) for name, s, d in norm["modules"].get(p, [])
                       if sre.search(name))
        if len(steps) >= 3:
            steps = steps[1:-1]
        n_steps += len(steps)
        step_time += sum(e - s for s, e in steps)
        for name, s, d in leaf:
            if kre.search(name) and any(a <= s < b for a, b in steps):
                kern_time += d
                n_kernel += 1
        # idle gaps between this chip's busy intervals, named by what the
        # host was doing at their middle
        cur = t0
        for s, e in sorted((s, s + d) for _, s, d in evs):
            if s - cur >= gap_floor_ns:
                gaps[_host_activity(host, (s + cur) / 2)] += s - cur
                longest_gap = max(longest_gap, s - cur)
            cur = max(cur, e)
        if t1 - cur >= gap_floor_ns:
            gaps[_host_activity(host, (t1 + cur) / 2)] += t1 - cur
    n = len(planes)
    return {
        "chips": n,
        "span_s": (t1 - t0) / 1e9,
        "busy_s": busy / n / 1e9,
        "kernel_s": kern_time / n / 1e9,
        "kernel_calls": n_kernel // n,
        "step_s": step_time / n / 1e9,
        "step_calls": n_steps // n,
        "longest_gap_s": longest_gap / 1e9,
        "device_ops": [[k, v / n / 1e9] for k, v in
                       sorted(ops.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[k, v / n / 1e9] for k, v in
                      sorted(gaps.items(), key=lambda kv: -kv[1])[:top]],
    }
