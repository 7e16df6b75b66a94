"""The modelled link between tiers, from a data file under ``bench/links``.

A copy of the Ornstein-Uhlenbeck RTT trace of ``repro.core.profiles``
(slow mean-reverting drift plus lognormal congestion spikes that decay
over 10-45 s), kept with the benchmark so that a change to the program's
profiles cannot move the latency a cell measures.  The trace is fixed by
the file's own seed, not by the run's: every run sees the same link.
"""

from __future__ import annotations

import json
import zlib
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent


class Link:
    """RTT (seconds) at a virtual time, interpolated and wrapped around
    the trace's end, plus the link's constant bandwidth."""

    def __init__(self, name: str):
        path = HERE / "links" / f"{name}.json"
        if not path.is_file():
            raise FileNotFoundError(f"no link {name!r} at {path}")
        c = json.loads(path.read_text())
        self.name = name
        self.bandwidth_bps = float(c["bandwidth_bps"])
        rng = np.random.default_rng(
            np.uint32(zlib.crc32(f"{name}:{c['seed']}".encode()) % (2 ** 32)))
        dt, dur = float(c["dt_s"]), float(c["duration_s"])
        n = int(dur / dt) + 1
        x = np.empty(n)
        x[0] = c["mean"]
        sq = c["vol"] * np.sqrt(dt)
        noise = rng.standard_normal(n - 1)
        for i in range(1, n):
            x[i] = (x[i - 1] + c["reversion"] * (c["mean"] - x[i - 1]) * dt
                    + sq * noise[i - 1])
        t_grid = np.arange(n) * dt
        for _ in range(rng.poisson(c["spike_rate_hz"] * dur)):
            t0 = rng.uniform(0, dur)
            amp = c["spike_scale"] * rng.lognormal(0.0, 0.75)
            tau = rng.uniform(10.0, 45.0)
            x += amp * np.exp(-np.maximum(t_grid - t0, 0.0) / tau) * (
                t_grid >= t0)
        self.times_s = t_grid
        self.rtt_s = np.maximum(x, c["floor"])

    def rtt_at(self, t: float) -> float:
        period = float(self.times_s[-1])
        return float(np.interp(np.mod(t, period), self.times_s, self.rtt_s))
