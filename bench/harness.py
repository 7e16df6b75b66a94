"""Pieces every serving driver shares: host spans, the compile watch, the
device's identity and peak memory, percentiles, and the logit-gap check.

Nothing here imports the program; drivers under ``bench/serve`` do.
"""

from __future__ import annotations

import contextlib
import json
import time
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


def load_json(path: Path) -> Dict:
    if not path.is_file():
        raise FileNotFoundError(f"missing {path}")
    return json.loads(path.read_text())


def load_config(name: str) -> Dict:
    return load_json(HERE / "configs" / f"{name}.json")


class Spans:
    """Host spans of the benchmark's own wrappers around program calls.

    ``span(name)`` times the block on the host clock and, while a
    profiler trace is on, also writes it into the trace as a
    ``TraceAnnotation`` so idle gaps on the device can be named by what
    the host was doing.  ``intervals[name]`` holds (start, end) pairs in
    ``time.perf_counter`` seconds.
    """

    def __init__(self):
        self.intervals: Dict[str, List[Tuple[float, float]]] = {}
        self.tracing = False

    @contextlib.contextmanager
    def span(self, name: str):
        ann = contextlib.nullcontext()
        if self.tracing:
            import jax
            ann = jax.profiler.TraceAnnotation(f"bench.{name}")
        t0 = time.perf_counter()
        with ann:
            yield
        self.intervals.setdefault(name, []).append((t0, time.perf_counter()))

    def clear(self) -> None:
        self.intervals.clear()

    def durations(self, name: str) -> np.ndarray:
        return np.asarray([b - a for a, b in self.intervals.get(name, [])])


class CompileWatch:
    """Counts backend compiles and their seconds (JAX's monitoring event)."""

    def __init__(self):
        import jax
        self.count = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.seconds += duration


def device_info(chips: int) -> Dict:
    import jax
    devs = jax.devices()[:chips]
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devs]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": int(max(peaks))}


def p95(values: Sequence[float]) -> Optional[float]:
    v = np.asarray(values, np.float64)
    return float(np.percentile(v, 95)) if v.size else None


def logit_gaps(ref_logits: np.ndarray, tokens: np.ndarray) -> np.ndarray:
    """Per position, how far the served token's reference logit lies below
    the reference's best: 0 where the served token is the reference's
    greedy choice.  ref_logits (T, V) float32, tokens (T,)."""
    ref = np.asarray(ref_logits, np.float64)
    t = np.asarray(tokens, np.int64)
    return ref.max(-1) - ref[np.arange(t.size), t]


def control_gaps(ref_logits: np.ndarray, low_logits: np.ndarray) -> np.ndarray:
    """The gap of the token a lower-precision run puts first."""
    return logit_gaps(ref_logits, np.argmax(low_logits, -1))


def sample_indices(lengths: Sequence[int], k: int, seed: int) -> List[int]:
    """k requests drawn from the seed, the longest always among them."""
    n = len(lengths)
    if n == 0:
        return []
    longest = int(np.argmax(lengths))
    rng = np.random.default_rng([int(seed), 0xC0FFEE])
    rest = [i for i in rng.permutation(n).tolist() if i != longest]
    return sorted([longest] + rest[:max(k - 1, 0)])
