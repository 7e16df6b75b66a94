"""Reduce a profiler trace of the window to what the per-layer metrics read.

Reads the ``.xplane.pb`` that ``jax.profiler`` wrote, with JAX alone
(``jax.profiler.ProfileData``):

* device planes (``/device:TPU:<n>``): their op line ("XLA Ops") gives
  every operation's interval, name and stats; their module line ("XLA
  Modules") every program run;
* the host plane: the benchmark's own spans (``bench.<name>``
  ``TraceAnnotation`` events), the outermost of which is
  ``bench.window``.

The traced window is the part of ``bench.window`` that the device trace
covers, from its first op to its last: the profiler's device tracer
starts some hundreds of milliseconds after ``start_trace`` returns, and
stops recording after some millions of ops (on one TPU v5e about 6.2
million, 9 s of a Marian decode loop), so the annotated window can
reach past both ends of what the device recorded.  Busy time is the
union of the op intervals inside the traced window, averaged over the
chips used; idle gaps are the rest of it, each named by the innermost
benchmark span that covers its middle ("harness" when none does).
Kernel time sums the ops whose name names the kernel.
"""

from __future__ import annotations

import dataclasses
from pathlib import Path
from typing import Dict, List, Tuple

OP_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"
SPAN_PREFIX = "bench."


@dataclasses.dataclass
class Event:
    name: str             # for an op, its HLO name without the text after it
    start: float          # seconds, the trace's clock
    dur: float
    stats: Dict[str, str] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class Summary:
    window: Tuple[float, float]        # traced: what the device trace covers
    annotated: Tuple[float, float]     # the bench.window span
    ops: List[List[Event]]             # per chip
    modules: List[Event]
    spans: List[Event]
    busy_s: float
    window_s: float
    breakdown: Dict

    def kernel_seconds(self, name: str) -> float:
        """Device seconds of the Pallas kernel ``name`` (its custom call is
        named after the function that calls ``pallas_call``), all chips."""
        return sum(e.dur for chip in self.ops for e in chip
                   if e.name.split(".")[0] == name and self._inside(e))

    def module_seconds(self, pattern: str) -> float:
        return sum(e.dur for e in self.modules
                   if pattern in e.name and self._inside(e))

    def _inside(self, e: Event) -> bool:
        return self.window[0] <= e.start + e.dur / 2 <= self.window[1]


def _events(line, keep=None, stats: bool = False) -> List[Event]:
    """The line's events (those whose raw name ``keep`` accepts), with
    their stats only where asked: reading stats is most of the cost."""
    out = []
    for e in line.events:
        raw = e.name
        if keep is not None and not keep(raw):
            continue
        st = {}
        if stats:
            try:
                st = {str(k): str(v) for k, v in e.stats}
            except Exception:      # some events carry stats no reader knows
                pass
        # an op's event name is its whole HLO line: keep "%name.N" -> "name.N"
        name = raw.split(" = ", 1)[0].lstrip("%")
        out.append(Event(name, e.start_ns * 1e-9, e.duration_ns * 1e-9, st))
    return out


def _host_kept(name: str) -> bool:
    return name.startswith(SPAN_PREFIX) or name == "CompleteCallbacks"


def _device_offset(modules: List[Event], host: List[Event]) -> float:
    """Seconds to add to device times to put them on the host's clock.

    The device planes keep their own clock.  A program run ends on the
    device before the host's ``CompleteCallbacks`` for the same
    ``run_id`` starts, so the tightest such pair bounds the offset."""
    done = {e.stats["run_id"]: e.start for e in host
            if e.name == "CompleteCallbacks" and "run_id" in e.stats}
    gaps = [done[m.stats["run_id"]] - (m.start + m.dur) for m in modules
            if m.stats.get("run_id") in done]
    return min(gaps) if gaps else 0.0


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def _clip(iv, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in iv if b > lo and a < hi]


def _name_gaps(spans: List[Event], gaps: List[Tuple[float, float]]
               ) -> Dict[str, float]:
    """Idle seconds by the innermost span that covers each gap's middle.

    The benchmark's spans come from one host thread and nest, so one
    sweep over the gaps in time order with a stack of open spans finds
    each innermost span."""
    inner = sorted((s for s in spans if s.name != SPAN_PREFIX + "window"),
                   key=lambda s: (s.start, -s.dur))
    out: Dict[str, float] = {}
    stack: List[Event] = []
    i = 0
    for a, b in sorted(gaps):
        mid = (a + b) / 2
        while i < len(inner) and inner[i].start <= mid:
            span = inner[i]
            i += 1
            while stack and stack[-1].start + stack[-1].dur < span.start:
                stack.pop()
            stack.append(span)
        while stack and stack[-1].start + stack[-1].dur < mid:
            stack.pop()
        name = stack[-1].name[len(SPAN_PREFIX):] if stack else "harness"
        out[name] = out.get(name, 0.0) + (b - a)
    return out


def find_xplane(trace_dir: Path) -> Path:
    files = sorted(Path(trace_dir).glob("**/*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def profile_options():
    """What ``jax.profiler.start_trace`` records for a traced run: device
    ops and host annotations, without the Python tracer (on by default),
    which would trace every Python call of a host-bound window."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


def reduce(trace_dir, *, chips: int = 1, top: int = 10) -> Summary:
    import jax
    data = jax.profiler.ProfileData.from_file(str(find_xplane(trace_dir)))
    return reduce_planes(data.planes, chips=chips, top=top)


def reduce_planes(planes, *, chips: int = 1, top: int = 10) -> Summary:
    dev_ops: Dict[str, List[Event]] = {}
    modules: List[Event] = []
    spans: List[Event] = []
    host: List[Event] = []
    for plane in planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == OP_LINE:
                    dev_ops[plane.name] = _events(line)
                elif line.name == MODULE_LINE:
                    modules += _events(line, stats=True)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += _events(line, _host_kept, stats=True)
    spans = [e for e in host if e.name.startswith(SPAN_PREFIX)]
    shift = _device_offset(modules, host)
    for e in modules + [e for ops in dev_ops.values() for e in ops]:
        e.start += shift
    windows = [s for s in spans if s.name == SPAN_PREFIX + "window"]
    if not windows:
        raise ValueError("trace has no bench.window span")
    a0 = windows[0].start
    a1 = a0 + windows[0].dur
    used = [dev_ops[k] for k in sorted(dev_ops,
                                       key=lambda n: int(n.rsplit(":", 1)[1]))
            ][:chips]
    seen = [(e.start, e.start + e.dur) for ops in used for e in ops
            if e.start < a1 and e.start + e.dur > a0]
    if not seen:
        raise ValueError("no device op inside the bench.window span")
    w0 = max(a0, min(a for a, _ in seen))
    w1 = min(a1, max(b for _, b in seen))
    busy, gaps = [], []
    for ops in used:
        merged = _clip(_union([(e.start, e.start + e.dur) for e in ops]),
                       w0, w1)
        busy.append(sum(b - a for a, b in merged))
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    by_op: Dict[str, float] = {}
    for ops in used:
        for e in ops:
            if w0 <= e.start + e.dur / 2 <= w1:
                by_op[e.name] = by_op.get(e.name, 0.0) + e.dur
    by_gap = _name_gaps(spans, gaps)
    n = max(len(used), 1)
    breakdown = {
        "device_ops": [[k, v / n] for k, v in sorted(
            by_op.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[k, v / n] for k, v in sorted(
            by_gap.items(), key=lambda kv: -kv[1])[:top]],
    }
    return Summary(window=(w0, w1), annotated=(a0, a1), ops=used,
                   modules=modules, spans=spans,
                   busy_s=sum(busy) / n, window_s=w1 - w0,
                   breakdown=breakdown)

