"""The one traffic generator: turns a mix file of parameters and a seed
into requests or batches.

The arithmetic is copied, so that no later change to the program moves
the yardstick: exponential inter-arrival gaps as in
``repro.launch.serve.build_trace``, clipped lognormal lengths as in
``repro.core.simulator.production_trace`` and the linear-interpolation
percentile of ``repro.serve.metrics.percentile``.

Every seed gets the same multiset of sizes and gaps, drawn once from the
mix's ``shape_seed``; the run's seed only permutes their order and picks
the token ids. Runs with different seeds therefore do the same amount of
work, and their spread is the system's, not the sampler's.
"""

from __future__ import annotations

import dataclasses
import math
from typing import List

import numpy as np


def percentile(xs, q: float) -> float:
    """Percentile with linear interpolation between closest ranks; ``q``
    in [0, 1]; nan on empty input."""
    if not len(xs):
        return float("nan")
    if not 0.0 <= q <= 1.0:
        raise ValueError(f"q must be in [0, 1], got {q}")
    s = sorted(xs)
    pos = q * (len(s) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(s) - 1)
    frac = pos - lo
    return s[lo] * (1.0 - frac) + s[hi] * frac


def exponential_gaps(rng: np.random.RandomState, n: int, rate: float):
    """Poisson arrivals: ``n`` exponential gaps of mean ``1 / rate``."""
    return np.array([rng.exponential(1.0 / rate) for _ in range(n)])


def lognormal_lengths(rng: np.random.RandomState, n: int, *, median: float,
                      sigma: float, lo: int, hi: int, round_to: int = 1):
    """Lengths ``median * exp(sigma * N(0, 1))``, clipped to [lo, hi] and
    rounded up to a multiple of ``round_to``."""
    out = []
    for _ in range(n):
        x = max(lo, min(int(median * math.exp(sigma * rng.normal())), hi))
        out.append(-(-x // round_to) * round_to)
    return np.array(out, np.int64)


@dataclasses.dataclass
class ServeRequest:
    rid: int
    arrival_s: float          # due time, seconds after the window opens
    prompt: List[int]
    max_new_tokens: int


def n_requests(mix: dict, seconds: float) -> int:
    return max(1, int(round(mix["arrivals"]["rate_per_s"] * seconds)))


def serve_requests(mix: dict, seed: int, seconds: float,
                   vocab: int) -> List[ServeRequest]:
    """All requests due in a window of ``seconds``: ``rate * seconds`` of
    them, their gaps scaled to fill the window exactly."""
    n = n_requests(mix, seconds)
    shape = np.random.RandomState(mix["shape_seed"])
    gaps = exponential_gaps(shape, n + 1, mix["arrivals"]["rate_per_s"])
    gaps *= seconds / gaps.sum()
    p, o = mix["prompt"], mix["output"]
    plens = lognormal_lengths(shape, n, median=p["median"],
                              sigma=p["sigma"], lo=p["min"], hi=p["max"],
                              round_to=p.get("round_to", 1))
    olens = lognormal_lengths(shape, n, median=o["median"],
                              sigma=o["sigma"], lo=o["min"], hi=o["max"])
    rng = np.random.default_rng(seed)
    arrivals = np.cumsum(gaps[rng.permutation(n + 1)])[:n]
    order = rng.permutation(n)
    reqs = []
    for i in range(n):
        plen, olen = int(plens[order[i]]), int(olens[order[i]])
        prompt = rng.integers(0, vocab, size=plen).tolist()
        reqs.append(ServeRequest(i, float(arrivals[i]), prompt, olen))
    return reqs


def warmup_prompt_lengths(mix: dict) -> List[int]:
    """Prompt lengths that make warm-up compile every prefill program the
    window will use: each distinct last-chunk length the mix can produce,
    alone and after a whole first chunk (a later chunk takes the carry the
    chunk before it returned, a first one a fresh carry)."""
    chunk = mix["engine"]["prefill_chunk"]
    step = mix["prompt"].get("round_to", 1)
    lo, hi = mix["prompt"]["min"], mix["prompt"]["max"]
    rems = sorted({((n - 1) % chunk) + 1 for n in range(lo, hi + 1, step)
                   if n % step == 0})
    return rems + [chunk + r for r in rems]
