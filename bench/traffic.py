"""The one traffic generator: reads a mix's data file, yields episodes.

A mix (``bench/traffic/<name>.json``) is data only::

    {"arrivals": {"kind": "poisson" | "bursty", "rate_hz": mean rate,
                  "peak_factor": f, "period_s": p},   # bursty only
     "episode_s": virtual seconds per episode,
     "prompt": {"median": tokens, "sigma": lognormal sigma,
                "min": tokens, "max": tokens, "grid": [widths] | null},
     "max_new": tokens out per request,
     "serve": {...}}                                  # read by the driver

An episode is ``episode_s`` seconds of virtual time.  Every episode of a
mix holds the same number of requests, the same set of gaps between
arrivals and the same set of prompt lengths: the seed changes only their
order and the prompts' token ids.  So two seeds give the same work in
another order, and a run's spread is the system's, not the draw's.

* Gaps between arrivals: the exponential distribution's quantiles at
  ``(i + 1/2)/n``, in an order drawn from the seed, scaled so that the
  episode's n arrivals end at its end.  The gaps are taken on the rate's
  integral, so a Poisson mix has exponential gaps and a bursty one
  (raised-cosine rate between the trough ``2 rate/(f+1)`` and ``f`` times
  that, period ``period_s``, as in ``repro.core.arrivals.bursty_arrivals``)
  crowds them at its peaks.
* Prompt lengths: the lognormal's quantiles at ``(i + 1/2)/n``, clipped to
  [min, max] and, with a grid, rounded up to the next grid width.
* Prompt tokens: uniform ids in [3, vocab), clear of PAD, BOS and EOS.
"""

from __future__ import annotations

import json
import math
from pathlib import Path
from statistics import NormalDist
from typing import Dict, List, Tuple

import numpy as np

HERE = Path(__file__).resolve().parent
FIRST_ID = 3        # ids 0-2 are PAD, BOS and EOS


def load(name: str) -> Dict:
    path = HERE / "traffic" / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no traffic mix {name!r} at {path}")
    return json.loads(path.read_text())


def per_episode(mix: Dict) -> int:
    return max(1, round(mix["arrivals"]["rate_hz"] * mix["episode_s"]))


def prompt_lengths(mix: Dict, n: int) -> np.ndarray:
    """The episode's n prompt lengths, ascending."""
    p = mix["prompt"]
    q = NormalDist(math.log(p["median"]), p["sigma"])
    lens = np.array([math.exp(q.inv_cdf((i + 0.5) / n)) for i in range(n)])
    lens = np.clip(np.ceil(lens), p["min"], p["max"]).astype(np.int64)
    grid = p.get("grid")
    if grid:
        g = np.asarray(sorted(grid))
        lens = g[np.searchsorted(g, lens)]
    return lens


def _cumulative_rate(a: Dict, t: np.ndarray) -> np.ndarray:
    """Integral of the normalized rate over [0, t]."""
    if a["kind"] == "poisson":
        return t
    f, p = a["peak_factor"], a["period_s"]
    base = 2.0 / (f + 1.0)          # trough, in units of the mean rate
    amp = (f - 1.0) * base / 2.0    # rate = base + amp (1 - cos(2 pi t/p))
    return (base + amp) * t - amp * p / (2 * math.pi) * np.sin(
        2 * math.pi * t / p)


def arrival_times(mix: Dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """n arrival times in (0, episode_s], ascending; the last at the end."""
    a, span = mix["arrivals"], float(mix["episode_s"])
    if a["kind"] not in ("poisson", "bursty"):
        raise ValueError(f"unknown arrival kind {a['kind']!r}")
    gaps = rng.permutation(-np.log1p(-(np.arange(n) + 0.5) / n))
    grid = np.linspace(0.0, span, 4097)
    cum = _cumulative_rate(a, grid)
    u = np.cumsum(gaps) / gaps.sum() * cum[-1]
    return np.interp(u, cum, grid)


def episode(mix: Dict, seed: int, index: int, vocab: int
            ) -> Tuple[np.ndarray, List[np.ndarray]]:
    """Episode ``index`` of a run: absolute virtual arrival times and the
    prompt of each request, in arrival order."""
    n = per_episode(mix)
    rng = np.random.default_rng([int(seed), int(index)])
    t = arrival_times(mix, n, rng) + index * float(mix["episode_s"])
    lens = rng.permutation(prompt_lengths(mix, n))
    prompts = [rng.integers(FIRST_ID, vocab, size=int(l), dtype=np.int64)
               .astype(np.int32) for l in lens]
    return t, prompts
