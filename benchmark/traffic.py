"""The one traffic generator: a mix file's parameters and a seed -> requests.

A mix file (``traffic/<mix>.json``) fixes how many requests a run offers,
the multiset of (prompt length, answer length) pairs and, for an open
loop, the multiset of gaps between arrivals.  Lengths are a grid of
quantiles of the stated distribution and gaps a grid of quantiles of the
exponential distribution at the stated rate: nothing is drawn.  The file's
``order_seed`` fixes one order of them, bursts and lulls included; the
run's seed turns that sequence round to another starting point and fills
the token ids.  So two runs differ in phase and content, never in the
tokens offered, in how arrivals are spread, or in which requests crowd
together: with a free shuffle, one seed in six put its long answers in one
burst and read a quarter more at the 90th percentile (PERF.md, PR 26).
"""

from __future__ import annotations

from dataclasses import dataclass
from statistics import NormalDist
from typing import Dict, List, Optional

import numpy as np


@dataclass
class Offered:
    """One request of a run.  ``due_s`` is seconds after traffic starts
    (None in a closed loop); ``measured`` marks the open loop's sample:
    the requests due inside the window."""
    prompt: List[int]
    max_tokens: int
    due_s: Optional[float]
    measured: bool


def length_grid(spec: Dict, n: int) -> np.ndarray:
    """``n`` lengths at the mid-point quantiles of ``spec``'s distribution,
    clipped to its limits, in ascending order."""
    q = (np.arange(n) + 0.5) / n
    if spec["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(x)) for x in q])
        vals = spec["median"] * np.exp(spec["sigma"] * z)
    elif spec["dist"] == "uniform":
        vals = spec["min"] + q * (spec["max"] - spec["min"])
    elif spec["dist"] == "fixed":
        vals = np.full(n, spec["value"], float)
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    lo = spec.get("min", 1)
    hi = spec.get("max", max(lo, int(vals.max()) + 1))
    return np.clip(np.rint(vals), lo, hi).astype(np.int64)


def gap_grid(rate_per_s: float, n: int, span_s: float) -> np.ndarray:
    """``n`` gaps at the mid-point quantiles of the exponential
    distribution, scaled so that they sum to ``span_s`` exactly."""
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q) / rate_per_s
    return gaps * (span_s / gaps.sum())


def _pairs(mix: Dict, n: int) -> np.ndarray:
    """The mix's ``n`` (prompt, answer) pairs: both grids, the answers
    put in an order that the mix file fixes."""
    prompts = length_grid(mix["prompt_tokens"], n)
    answers = length_grid(mix["answer_tokens"], n)
    order = np.random.default_rng(mix["pairing_seed"]).permutation(n)
    return np.stack([prompts, answers[order]], axis=1)


def _section(mix: Dict, n: int, span: Optional[float], start: float,
             measured: bool, order: np.random.Generator,
             rng: np.random.Generator, vocab: int) -> List[Offered]:
    """``n`` requests in the file's order, turned round by the seed."""
    pairs = _pairs(mix, n)[order.permutation(n)]
    turn = int(rng.integers(n))
    pairs = np.roll(pairs, turn, axis=0)
    if span is None:
        due = [None] * n
    else:
        gaps = gap_grid(mix["rate_per_s"], n, span)[order.permutation(n)]
        due = start + np.cumsum(np.roll(gaps, turn))    # one arrival a gap
    return [Offered(rng.integers(1, vocab, int(p)).tolist(), int(a),
                    None if d is None else float(d), measured)
            for (p, a), d in zip(pairs, due)]


def build(mix: Dict, seed: int, seconds: float, vocab: int) -> List[Offered]:
    """The requests of one run, in the order they are offered."""
    rng = np.random.default_rng(seed)
    order = np.random.default_rng(mix["order_seed"])
    if mix["loop"] == "closed":
        return _section(mix, mix["requests"], None, 0.0, True, order, rng,
                        vocab)
    if mix["loop"] != "open":
        raise ValueError(f"unknown loop {mix['loop']!r}")
    rate, lead = mix["rate_per_s"], mix["lead_s"]
    # The lead-in and the window each have grids of their own, so the
    # window's sample is the same multiset whatever the seed.
    return (_section(mix, max(1, round(rate * lead)), lead, 0.0, False,
                     order, rng, vocab)
            + _section(mix, max(1, round(rate * seconds)), seconds, lead,
                       True, order, rng, vocab))


def buckets_used(mix: Dict) -> List[int]:
    """The engine's prefill buckets that this mix's prompts fall in."""
    buckets = sorted(mix["engine_options"]["prefill_buckets"])
    lengths = length_grid(mix["prompt_tokens"], 512)
    used = {next(b for b in buckets if n <= b) for n in lengths}
    return sorted(used)


def offered_totals(reqs: List[Offered]) -> Dict[str, int]:
    sample = [r for r in reqs if r.measured]
    return {"requests": len(sample),
            "prompt_tokens": sum(len(r.prompt) for r in sample),
            "answer_tokens": sum(r.max_tokens for r in sample)}


def percentile(values, q: float) -> float:
    """Linear-interpolated percentile of a non-empty sample."""
    return float(np.percentile(np.asarray(values, float), q))
