"""The output check that decides ``correct``.

After the window closes, a sample of finished requests drawn from the
seed (the one with the most served tokens always in it) goes to the plain
reference, which recomputes every served position's logits from the
prompt and the served tokens in float32. The number compared is the
widest gap by which a served token's logit lies below the reference's
best logit at its position: 0 where the served token is the reference's
own greedy choice, and within rounding of 0 for a sound low-precision
program, since greedy serving picks its own best logit.
"""
from __future__ import annotations

import numpy as np

# how each number compared is held to its limit
RULES = {"max_logit_gap": "at most", "served_tokens_checked": "at least"}


def choose_sample(done: list[dict], k: int, seed: int) -> list[dict]:
    """``k`` finished requests: the longest generation, then a seeded
    draw of the rest."""
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r["tokens"]), -r["i"]))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng((seed, 0x5A3))
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [longest] + [rest[j] for j in sorted(pick)]


def served_gaps(ref_logits: np.ndarray, tokens: np.ndarray) -> np.ndarray:
    """Per position: the reference's best logit minus its logit of the
    token chosen there. ``ref_logits`` (n, V), ``tokens`` (n,)."""
    ref = np.asarray(ref_logits, np.float64)
    at = ref[np.arange(len(tokens)), np.asarray(tokens)]
    return ref.max(axis=1) - at


def compare(gaps: list, limits: dict) -> tuple[bool, dict]:
    """Judge a run's gaps against the cell's limits; returns (correct,
    {number: {"value", "limit"}})."""
    n_tok = int(sum(len(g) for g in gaps))
    widest = float(max((float(np.max(g)) for g in gaps if len(g)),
                       default=float("inf")))
    lim = limits["max_logit_gap"]["limit"]
    checks = {"max_logit_gap": {"value": widest, "limit": lim},
              "served_tokens_checked": {"value": n_tok,
                                        "limit": limits["min_tokens"]}}
    ok = widest <= lim and n_tok >= limits["min_tokens"]
    return ok, checks
