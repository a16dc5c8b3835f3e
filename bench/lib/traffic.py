"""Traffic generator: one general generator that every traffic file feeds.

A traffic file (``bench/traffic/<name>.json``) holds only parameters:

    loop              "open" (arrivals on a schedule) or "closed" (a fixed
                      number of requests in flight)
    rate_per_s        open loop: mean Poisson arrival rate
    in_flight_per_slot  closed loop: requests in flight per engine slot
    first_wave        closed loop: "residual" gives the first wave the
                      remaining lengths of requests already in progress
                      (the output distribution's residual), so completions
                      are spread through the window from its start
    prompt_len, output_len   {"median", "sigma", "min", "max"}: lognormal,
                      clipped
    tokens            {"zipf_a", "p_copy"}: the Zipf-Markov token source
    block             size of the fixed sample of draws (see below)
    draw_seed         seed of that fixed sample
    order             "shuffle" (the default) or "rotate" (see below)
    ramp_s            seconds of load before the measured window opens
    trace_s           length of the profiled sub-window of a traced run
    check_requests    finished requests the output check compares

Every seed gets the same work in another order: ``block`` (prompt, output,
gap) draws are made once from ``draw_seed``. With ``order`` "shuffle" each
consecutive block of requests is a fresh shuffle of them by ``--seed``
(lengths and gaps shuffled apart). With "rotate" the draws keep the order
they were drawn in and repeat as one cycle, its gaps scaled to span
exactly ``block / rate_per_s`` seconds, and ``--seed`` picks the draw the
cycle starts at: any window one cycle long then holds every draw once,
in the same arrangement, so the seed moves neither the work nor the
collisions of long prompts with a full batch. Token contents come from
``--seed``.
"""
from __future__ import annotations

import math

import numpy as np

MAX_REQUESTS = 20_000


class ZipfMarkov:
    """Seeded token source: each token is the fixed successor of the
    previous one (probability ``p_copy``) or a Zipf draw. Copied from the
    program's ``repro.data.synthetic.ZipfMarkov`` so the yardstick cannot
    change under a later change to the program."""

    def __init__(self, vocab_size: int, *, zipf_a: float = 1.2,
                 p_copy: float = 0.6, seed: int = 0):
        self.vocab = vocab_size
        self.p_copy = p_copy
        rng = np.random.default_rng(seed)
        self.succ = rng.permutation(vocab_size)
        ranks = np.arange(1, vocab_size + 1, dtype=np.float64)
        probs = ranks ** -zipf_a
        self.marginal = probs / probs.sum()

    def sample(self, batch: int, seq: int, seed: int) -> np.ndarray:
        rng = np.random.default_rng((seed, 0xC0FFEE))
        iid = rng.choice(self.vocab, size=(batch, seq), p=self.marginal)
        copy = rng.random((batch, seq)) < self.p_copy
        out = np.empty((batch, seq), np.int32)
        out[:, 0] = iid[:, 0]
        for t in range(1, seq):
            out[:, t] = np.where(copy[:, t], self.succ[out[:, t - 1]],
                                 iid[:, t])
        return out


def lognormal_lengths(rng, n: int, dist: dict) -> np.ndarray:
    x = rng.lognormal(math.log(dist["median"]), dist["sigma"], n)
    return np.clip(np.rint(x), dist["min"], dist["max"]).astype(np.int64)


def in_flight(traffic: dict, n_slots: int) -> int:
    return max(1, int(round(traffic["in_flight_per_slot"] * n_slots)))


def n_requests(traffic: dict, seconds: float, n_slots: int,
               grace_s: float) -> int:
    """Requests the plan holds: enough for the ramp, the window and the
    grace after it at the offered load."""
    span = traffic["ramp_s"] + seconds + grace_s
    if traffic["loop"] == "open":
        n = math.ceil(traffic["rate_per_s"] * span * 1.25) + traffic["block"]
    else:
        # closed: each slot turns over at most once per min output length
        # at a generous 100 decode steps a second
        n = in_flight(traffic, n_slots) * (
            2 + math.ceil(span * 100 / traffic["output_len"]["min"]))
    return min(n, MAX_REQUESTS)


def plan(traffic: dict, *, vocab: int, seed: int, seconds: float,
         n_slots: int, grace_s: float = 60.0) -> list[dict]:
    """The run's requests in send order: ``{"i", "t", "prompt",
    "max_new"}`` with ``t`` the send offset in seconds (open loop) or
    None (closed loop: sent when a slot of the loop frees)."""
    n = n_requests(traffic, seconds, n_slots, grace_s)
    blk = int(traffic["block"])
    base = np.random.default_rng(traffic["draw_seed"])
    p_lens = lognormal_lengths(base, blk, traffic["prompt_len"])
    o_lens = lognormal_lengths(base, blk, traffic["output_len"])
    gaps = (base.exponential(1.0 / traffic["rate_per_s"], blk)
            if traffic["loop"] == "open" else np.zeros(blk))
    rng = np.random.default_rng(seed)
    if traffic.get("order", "shuffle") == "rotate":
        if traffic["loop"] == "open":
            gaps = gaps * (blk / traffic["rate_per_s"]) / gaps.sum()
        order_len = order_gap = (int(rng.integers(blk)) + np.arange(n)) % blk
    else:
        order_len = np.concatenate([rng.permutation(blk)
                                    for _ in range(-(-n // blk))])[:n]
        order_gap = np.concatenate([rng.permutation(blk)
                                    for _ in range(-(-n // blk))])[:n]
    prompt_len = p_lens[order_len]
    max_new = o_lens[order_len].copy()
    if traffic["loop"] == "closed" and traffic.get("first_wave") == \
            "residual":
        # residual life of a request in progress: a length-biased draw of
        # the output distribution times the share still to go, both taken
        # at evenly spaced quantiles so every seed gets the same wave
        k = in_flight(traffic, n_slots)
        srt = np.sort(o_lens)
        cdf = np.cumsum(srt) / srt.sum()
        q = (np.arange(k) + 0.5) / k
        biased = srt[np.searchsorted(cdf, q)]
        share = base.permutation(q)
        resid = np.maximum(1, np.rint(biased * share)).astype(np.int64)
        max_new[:k] = rng.permutation(resid)
    t = np.concatenate([[0.0], np.cumsum(gaps[order_gap])[:-1]])
    src = ZipfMarkov(vocab, zipf_a=traffic["tokens"]["zipf_a"],
                     p_copy=traffic["tokens"]["p_copy"], seed=seed)
    toks = src.sample(n, int(prompt_len.max()), seed)
    open_loop = traffic["loop"] == "open"
    return [{"i": i, "t": float(t[i]) if open_loop else None,
             "prompt": toks[i, :prompt_len[i]], "max_new": int(max_new[i])}
            for i in range(n)]
