"""Collectives: device time per chip per fused step in which data moves
between chips, inside the fused-step programs (``names.STEP_MODULE``)
that the traced window cuts no edge of.

A tensor-parallel step exchanges activations after every row-parallel
product (the all-reduces after ``wo`` and ``w_down``), after each
column-parallel NBL map (an all-gather), around the norms and at the
embedding and head. A trace shows each exchange in one of three forms:

- a synchronous collective (``%all-reduce.N = ...``), counted by its
  duration;
- a fusion that calls one (``%fusion.N = ... kind=kCustom,
  calls=%all-reduce-scatter...``), counted by its duration;
- an async pair (``%collective-permute-start`` ...
  ``%collective-permute-done``), counted from the start of its
  ``-start`` to the end of its ``-done``: the transfer runs between the
  two markers, so their own lengths leave it out. A ``-done`` closes the
  oldest open ``-start`` of its kind on its chip.

The compiled tp4 step shows the NBL layers' reduce-scatter as a
``kCustom`` fusion; the chip's trace shows it as five permute pairs a
layer, beside 53 all-reduces and 25 all-gathers a step.

Intervals that overlap count once. A step with no collective (one chip)
gives nothing to read."""
import re

from bench.lib import names
from bench.lib.stats import union_length

KIND = r"(?:all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
# the instruction is a collective, or a fusion that calls one
COLLECTIVE = re.compile(rf"^%{KIND}[-.\w]* = |calls=%{KIND}")
ASYNC = re.compile(rf"^%({KIND})-(start|done)[.\d]* = ")


def intervals(evs: list) -> list:
    """(start, end) of each collective among one chip's device ops."""
    out, open_ = [], {}
    for name, s, d in sorted(evs, key=lambda e: e[1]):
        if not COLLECTIVE.search(name):
            continue
        m = ASYNC.match(name)
        if m is None:
            out.append((s, s + d))
        elif m.group(2) == "start":
            open_.setdefault(m.group(1), []).append(s)
        elif open_.get(m.group(1)):
            out.append((open_[m.group(1)].pop(0), s + d))
    return out


def read(ctx, name):
    trace = ctx["trace"]
    if not trace:
        return None
    step = re.compile(names.STEP_MODULE)
    total, n_steps = 0.0, 0
    for plane, evs in trace["device"].items():
        steps = sorted((s, s + d) for n, s, d in trace["modules"].get(plane, [])
                       if step.search(n))[1:-1]
        n_steps += len(steps)
        spans = intervals(evs)
        for a, b in steps:
            total += union_length([(max(s, a), min(e, b)) for s, e in spans
                                   if s < b and e > a])
    if not n_steps or not total:
        return None
    return total / n_steps / 1e6          # mean over (chip, step) pairs
