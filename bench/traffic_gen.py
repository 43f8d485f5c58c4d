"""The one generator that turns a traffic file into requests.

Every seed gets the same amount of work: the same multiset of inter-arrival
gaps, prompt lengths and output lengths, drawn as quantiles of the traffic's
distributions and put in another order by the seed.  So two seeds differ in
which request meets which, not in how much there is to do.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import Dict, List

import numpy as np


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([int(seed), stream]))


def _midpoints(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def _counts(probs: List[float], n: int) -> List[int]:
    """Largest-remainder split of ``n`` by ``probs``."""
    raw = [p * n / sum(probs) for p in probs]
    out = [int(math.floor(r)) for r in raw]
    for i in sorted(range(len(raw)), key=lambda i: out[i] - raw[i])[: n - sum(out)]:
        out[i] += 1
    return out


@dataclasses.dataclass
class Arrival:
    index: int
    due_s: float            # seconds after the window opens
    prompt: np.ndarray      # (P,) int32, ids in [1, vocab)
    max_new_tokens: int
    temperature: float


def serve_schedule(t: Dict, seed: int, seconds: float, vocab: int) -> List[Arrival]:
    """Open-loop arrivals for one window: ``floor(rate * seconds)`` requests,
    all due inside it.  The first ``backlog_at_open`` of them are already
    waiting when the window opens; the others arrive at the rate."""
    n = max(1, int(math.floor(t["rate_per_s"] * seconds)))
    b = min(int(t.get("backlog_at_open", 0)), n - 1)
    rng = _rng(seed, 1)
    gaps = rng.permutation(-np.log1p(-_midpoints(n - b)) / t["rate_per_s"])
    due = np.concatenate([np.zeros(b), [0.0], np.cumsum(gaps[:-1])])
    lens = np.repeat(t["prompt_buckets"], _counts(t["prompt_probs"], n))
    lens = rng.permutation(lens)
    z = np.array([NormalDist().inv_cdf(q) for q in _midpoints(n)])
    outs = np.clip(np.rint(t["output_median"] * np.exp(t["output_sigma"] * z)),
                   t["output_min"], t["output_max"]).astype(int)
    outs = rng.permutation(outs)
    return [
        Arrival(
            index=i,
            due_s=float(due[i]),
            prompt=rng.integers(1, vocab, size=int(lens[i]), dtype=np.int32),
            max_new_tokens=int(outs[i]),
            temperature=float(t["sampled_temperature"]) if i % 2 else 0.0,
        )
        for i in range(n)
    ]

