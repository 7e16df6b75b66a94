"""The program's own spans (``repro.tracing``) as the per-layer readers
see them: those of the benchmark's window and, in a traced run, the
device's idle gaps named by them on one clock.

The recorder and ``bench.harness.Spans`` both read ``time.perf_counter``,
so the window's records are those inside the ``window`` interval.  The
device planes are put on that clock in two steps.  The coarse shift is
the one the run already made (``run.trace.window[0] -
run.traced_window[0]``, trace time minus host time).  A bracket over the
traced translates refines it: they run one at a time on this path, so
each ``jit_nmt_translate`` program run lies in one ``exec.translate``
span, starting after its ``exec.dispatch`` starts and ending before its
``exec.wait`` ends.  With trace time = host time + shift,

    max(module_end - wait_end) <= shift <= min(module_start - dispatch_start)

and the midpoint is used; the bracket's width goes to stderr, with the
idle seconds named by the innermost program span over each gap's middle.

Every function returns None where the program has no recorder (the
import of ``repro.tracing`` fails), and the device ones where the run
has no trace.
"""

from __future__ import annotations

import bisect
import sys
from typing import Dict, List, Optional, Tuple

from bench.trace import _clip, _union

TRANSLATE_MODULE = "jit_nmt_translate"
NONE = "(none)"


def window_records(run) -> Optional[list]:
    """The recorder's spans and compile records inside the window, read
    once per run (which prints ``report`` to stderr)."""
    if "_records" not in run.__dict__:
        run.__dict__["_records"] = _read_window(run)
        if run.__dict__["_records"] is not None:
            report(run.__dict__["_records"])
    return run.__dict__["_records"]


def _read_window(run) -> Optional[list]:
    try:
        from repro import tracing
    except ImportError:
        return None
    windows = run.spans.intervals.get("window")
    if not windows:
        return None
    a, b = windows[-1]
    lo, hi = a * 1e9, b * 1e9
    if tracing.dropped():
        print(f"[bench] the recorder dropped {tracing.dropped()} records",
              file=sys.stderr, flush=True)
    return [r for r in tracing.spans() if lo <= r.t0_ns and r.t1_ns <= hi]


def report(records) -> None:
    """Per span name: count, mean and longest (ms); the children of the
    longest translate and decision, with any compile under them."""
    by: Dict[str, list] = {}
    for r in records:
        by.setdefault(r.name, []).append(r)
    rows = []
    for k, v in sorted(by.items()):
        d = [r.dur_ns / 1e6 for r in v]
        rows.append(f"{k} {len(d)} {sum(d) / len(d):.3f} {max(d):.3f}")
    print("[bench] program spans in window (count, mean ms, longest ms): "
          + ", ".join(rows), file=sys.stderr, flush=True)
    kids = children(records)
    for name in ("exec.translate", "sched.decide"):
        if name not in by:
            continue
        top = max(by[name], key=lambda r: r.dur_ns)
        parts = [f"{c.name} {c.dur_ns / 1e6:.3f}"
                 + (f" ({c.attrs.get('seconds', 0):.3f} s)"
                    if c.name == "jit.compile" else "")
                 for c in kids.get(top.id, ())]
        print(f"[bench] longest {name} {top.dur_ns / 1e6:.3f} ms "
              f"(trace {top.attrs.get('trace')}): " + ", ".join(parts),
              file=sys.stderr, flush=True)


def children(records) -> Dict[int, list]:
    out: Dict[int, list] = {}
    for r in records:
        if r.parent is not None:
            out.setdefault(r.parent, []).append(r)
    return out


def child_ns(kids: Dict[int, list], rec, names) -> int:
    """Summed duration of ``rec``'s children named in ``names``."""
    return sum(c.dur_ns for c in kids.get(rec.id, ()) if c.name in names)


def translate_modules(trace) -> list:
    """The traced window's translate program runs (trace clock)."""
    lo, hi = trace.window
    return [m for m in trace.modules
            if m.name.startswith(TRANSLATE_MODULE)
            and lo <= m.start + m.dur / 2 <= hi]


def pair_translates(records, modules, shift0: float
                    ) -> List[Tuple[float, float, float, float]]:
    """(dispatch start, wait end, module start, module end), seconds, for
    each translate module whose middle lies in one ``exec.translate``
    span under the coarse shift; host times on the host clock."""
    kids = children(records)
    spans = sorted((r for r in records if r.name == "exec.translate"),
                   key=lambda r: r.t0_ns)
    starts = [r.t0_ns * 1e-9 + shift0 for r in spans]
    out = []
    for m in modules:
        mid = m.start + m.dur / 2
        i = bisect.bisect_right(starts, mid) - 1
        if i < 0 or spans[i].t1_ns * 1e-9 + shift0 < mid:
            continue
        sub = {c.name: c for c in kids.get(spans[i].id, ())}
        if "exec.dispatch" in sub and "exec.wait" in sub:
            out.append((sub["exec.dispatch"].t0_ns * 1e-9,
                        sub["exec.wait"].t1_ns * 1e-9,
                        m.start, m.start + m.dur))
    return out


def bracket(pairs) -> Optional[Tuple[float, float]]:
    """(lowest, highest) shift, trace time minus host time, that keeps
    every program run inside its host call."""
    if not pairs:
        return None
    lo = max(m1 - w1 for _, w1, _, m1 in pairs)
    hi = min(m0 - d0 for d0, _, m0, _ in pairs)
    return lo, hi


def idle_gaps(trace) -> List[Tuple[float, float]]:
    """The device's idle intervals in the traced window (trace clock), of
    every chip used."""
    w0, w1 = trace.window
    gaps = []
    for ops in trace.ops:
        merged = _clip(_union([(e.start, e.start + e.dur) for e in ops]),
                       w0, w1)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    return gaps


def innermost(records, points_ns) -> list:
    """For each point (host ns, ascending) the innermost span containing
    it, or None.  The serving path's spans come from one thread and
    nest, so one sweep with a stack of open spans finds each."""
    spans = sorted((r for r in records if r.t1_ns > r.t0_ns),
                   key=lambda r: (r.t0_ns, -r.t1_ns))
    out, stack, i = [], [], 0
    for p in points_ns:
        while i < len(spans) and spans[i].t0_ns <= p:
            while stack and stack[-1].t1_ns <= spans[i].t0_ns:
                stack.pop()
            stack.append(spans[i])
            i += 1
        while stack and stack[-1].t1_ns < p:
            stack.pop()
        out.append(stack[-1] if stack else None)
    return out


class Aligned:
    """The window's records and the device's idle gaps on the host clock."""

    def __init__(self, records, gaps, shift: float, chips: int):
        self.shift, self.chips = shift, chips
        self.gaps = sorted((a - shift, b - shift) for a, b in gaps)
        mids = [(a + b) / 2 * 1e9 for a, b in self.gaps]
        self.at = innermost(records, mids)
        self._by_id = {r.id: r for r in records}

    def idle_by_span(self) -> Dict[str, float]:
        """Idle seconds by the innermost span over each gap's middle,
        averaged over the chips."""
        out: Dict[str, float] = {}
        for (a, b), r in zip(self.gaps, self.at):
            k = r.name if r is not None else NONE
            out[k] = out.get(k, 0.0) + (b - a) / self.chips
        return out

    def idle_within(self, name: str) -> float:
        """Idle seconds, averaged over the chips, whose middle lies in a
        span ``name`` or in one of its descendants."""
        total = 0.0
        for (a, b), r in zip(self.gaps, self.at):
            while r is not None and r.name != name:
                r = self._by_id.get(r.parent)
            if r is not None:
                total += (b - a) / self.chips
        return total


def aligned(run) -> Optional[Aligned]:
    """The run's ``Aligned``, made once per run (it prints the bracket and
    the idle table to stderr); None without a trace or a recorder."""
    if run.trace is None or run.traced_window is None:
        return None
    if "_aligned" in run.__dict__:
        return run.__dict__["_aligned"]
    records = window_records(run)
    if records is None:
        run.__dict__["_aligned"] = None
        return None
    shift0 = run.trace.window[0] - run.traced_window[0]
    pairs = pair_translates(records, translate_modules(run.trace), shift0)
    b = bracket(pairs)
    shift = shift0
    if b is not None and b[0] <= b[1]:
        shift = (b[0] + b[1]) / 2
        print(f"[bench] program clock: bracket width {(b[1] - b[0]) * 1e6:.1f}"
              f" us over {len(pairs)} translates, midpoint "
              f"{(shift - shift0) * 1e6:+.1f} us from the coarse shift",
              file=sys.stderr, flush=True)
    else:
        print(f"[bench] program clock: no bracket ({len(pairs)} translates"
              f" paired, bounds {b}); coarse shift kept",
              file=sys.stderr, flush=True)
    al = Aligned(records, idle_gaps(run.trace), shift,
                 max(len(run.trace.ops), 1))
    table = sorted(al.idle_by_span().items(), key=lambda kv: -kv[1])
    print("[bench] idle by program span: "
          + ", ".join(f"{k} {v:.4f} s" for k, v in table),
          file=sys.stderr, flush=True)
    run.__dict__["_aligned"] = al
    return al
