"""The traffic generator: deterministic in the seed, the same work for
every seed in another order, the parameters as the file states them."""
import json
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

import pytest  # noqa: E402

from bench.lib import traffic as tgen  # noqa: E402


def load(name):
    return json.loads((ROOT / "bench" / "traffic" / f"{name}.json")
                      .read_text())


@pytest.mark.parametrize("name,n_slots", [("chat-poisson", 48),
                                          ("batch-decode", 8)])
def test_deterministic_in_seed(name, n_slots):
    t = load(name)
    a = tgen.plan(t, vocab=1000, seed=2**33 + 7, seconds=5, n_slots=n_slots)
    b = tgen.plan(t, vocab=1000, seed=2**33 + 7, seconds=5, n_slots=n_slots)
    c = tgen.plan(t, vocab=1000, seed=8, seconds=5, n_slots=n_slots)
    key = lambda p: [(r["t"], r["max_new"], r["prompt"].tolist()) for r in p]  # noqa: E731
    assert key(a) == key(b)
    assert key(a) != key(c)


@pytest.mark.parametrize("name,n_slots", [("chat-poisson", 48),
                                          ("batch-decode", 8)])
def test_same_work_every_seed(name, n_slots):
    t = load(name)
    blk = t["block"]
    plans = [tgen.plan(t, vocab=1000, seed=s, seconds=5, n_slots=n_slots)
             for s in (1, 2)]
    skip = tgen.in_flight(t, n_slots) if t["loop"] == "closed" else 0
    for lo in range(blk, len(plans[0]) - blk, blk):
        x, y = ([r["max_new"] for r in p[lo:lo + blk]] for p in plans)
        assert sorted(x) == sorted(y)
        x, y = (sorted(len(r["prompt"]) for r in p[lo:lo + blk])
                for p in plans)
        assert x == y
    if skip:                                  # the residual first wave too
        assert sorted(r["max_new"] for r in plans[0][:skip]) == \
            sorted(r["max_new"] for r in plans[1][:skip])
    if t["loop"] == "open":                   # the same gaps per block
        g = [np.diff([r["t"] for r in p[:blk + 1]]) for p in plans]
        assert np.sort(g[0]) == pytest.approx(np.sort(g[1]))


def test_lengths_and_rate_as_stated():
    t = load("chat-poisson")
    p = tgen.plan(t, vocab=1000, seed=3, seconds=45, n_slots=48)
    pl = np.array([len(r["prompt"]) for r in p])
    ol = np.array([r["max_new"] for r in p])
    assert pl.min() >= 64 and pl.max() <= 2048
    assert ol.min() >= 16 and ol.max() <= 512
    assert 250 < np.median(pl) < 550 and 90 < np.median(ol) < 180
    span = p[-1]["t"] - p[0]["t"]
    assert (len(p) - 1) / span == pytest.approx(t["rate_per_s"], rel=0.25)
    assert all(0 <= r["prompt"].min() and r["prompt"].max() < 1000
               for r in p)


def test_closed_first_wave_is_residual():
    t = load("batch-decode")
    k = tgen.in_flight(t, 8)
    assert k == 12
    p = tgen.plan(t, vocab=1000, seed=3, seconds=45, n_slots=8)
    first = [r["max_new"] for r in p[:k]]
    rest = [r["max_new"] for r in p[k:]]
    assert min(rest) >= 512 and max(first) <= 1024
    # the residual spreads the first wave's ends from the window's start
    assert min(first) < 100 and sorted(first) != first
    assert all(r["t"] is None for r in p)


def test_rotated_cycle_holds_every_draw_once():
    t = load("chat-poisson")
    assert t["order"] == "rotate"
    blk, cycle = t["block"], t["block"] / t["rate_per_s"]
    plans = [tgen.plan(t, vocab=1000, seed=s, seconds=50, n_slots=48)
             for s in (1, 2, 2**40 + 5)]
    starts = {(len(p[0]["prompt"]), p[0]["max_new"]) for p in plans}
    assert len(starts) > 1                    # the seed moves the start
    work = set()
    for p in plans:
        t_ = np.array([r["t"] for r in p])
        assert t_[blk] - t_[0] == pytest.approx(cycle)
        for w0 in (t["ramp_s"], 33.3):        # any window one cycle long
            inw = [r for r, x in zip(p, t_) if w0 <= x < w0 + cycle]
            assert len(inw) == blk
            work.add((tuple(sorted(len(r["prompt"]) for r in inw)),
                      tuple(sorted(r["max_new"] for r in inw))))
        # the same arrangement: request k + blk repeats request k
        assert [(len(a["prompt"]), a["max_new"]) for a in p[:blk]] == \
            [(len(b["prompt"]), b["max_new"]) for b in p[blk:2 * blk]]
    assert len(work) == 1
