"""In-process spans of the serving path, on ``time.perf_counter``.

``span(name, **attrs)`` times a block and records
``Record(id, parent, name, t0_ns, t1_ns, attrs)``: ``perf_counter_ns``
on entry and exit, the parent from a per-thread stack of open spans.
While a ``jax.profiler`` trace is on, the block is also written into the
trace as a ``TraceAnnotation`` of the same name, beside the device
planes.  A request's trace id (``bind(trace)``, or ``trace=`` on a span)
is carried into the attrs of every span opened under it.

Every backend compile becomes an instant record ``jit.compile`` whose
parent is the innermost open span of the compiling thread and whose
``seconds`` attr is the compile's duration: it says which step compiled.

Records go into a bounded deque of ``CAPACITY``; ``dropped()`` counts
the oldest ones pushed out.  ``spans()`` and ``reset()`` are for
readers, ``enable()`` for tests and for timing the recorder itself.
The module needs only the standard library; JAX is imported on the first
span, to register the compile listener and find the profiler.
"""

from __future__ import annotations

import collections
import itertools
import threading
import time
from typing import Dict, List, NamedTuple, Optional

CAPACITY = 65536
COMPILE = "jit.compile"
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class Record(NamedTuple):
    id: int
    parent: Optional[int]
    name: str
    t0_ns: int
    t1_ns: int
    attrs: Dict

    @property
    def dur_ns(self) -> int:
        return self.t1_ns - self.t0_ns


_records: collections.deque = collections.deque(maxlen=CAPACITY)
_lock = threading.Lock()
_ids = itertools.count(1)
_local = threading.local()
_enabled = True
_dropped = 0
_annotation = None          # jax.profiler.TraceAnnotation once installed
_installed = False


def _thread():
    """This thread's open span ids and bound trace id."""
    try:
        return _local.state
    except AttributeError:
        _local.state = state = [[], None]
        return state


def _append(rec: Record) -> None:
    global _dropped
    with _lock:
        if len(_records) == CAPACITY:
            _dropped += 1
        _records.append(rec)


def _on_duration(event: str, duration: float, **_) -> None:
    if event != _COMPILE_EVENT or not _enabled:
        return
    stack, trace = _thread()
    attrs = {"seconds": duration}
    if trace is not None:
        attrs["trace"] = trace
    now = time.perf_counter_ns()
    _append(Record(next(_ids), stack[-1] if stack else None, COMPILE,
                   now, now, attrs))


def _install() -> None:
    """Once per process: the compile listener and the profiler's
    annotation class (none where JAX cannot be imported)."""
    global _installed, _annotation
    with _lock:
        if _installed:
            return
        _installed = True
    try:
        import jax
    except ImportError:
        return
    _annotation = jax.profiler.TraceAnnotation
    jax.monitoring.register_event_duration_secs_listener(_on_duration)


class _Span:
    __slots__ = ("name", "attrs", "id", "parent", "t0", "ann", "outer")

    def __init__(self, name: str, attrs: Dict):
        self.name, self.attrs = name, attrs

    def __enter__(self):
        if not _installed:
            _install()
        state = _thread()
        stack = state[0]
        self.outer = state[1]
        if "trace" in self.attrs:
            state[1] = self.attrs["trace"]
        elif self.outer is not None:
            self.attrs["trace"] = self.outer
        self.parent = stack[-1] if stack else None
        self.id = next(_ids)
        stack.append(self.id)
        self.ann = None
        if _annotation is not None and _annotation.is_enabled():
            self.ann = _annotation(self.name)
            self.ann.__enter__()
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter_ns()
        if self.ann is not None:
            self.ann.__exit__(*exc)
        state = _thread()
        state[0].pop()
        state[1] = self.outer
        _append(Record(self.id, self.parent, self.name, self.t0, t1,
                       self.attrs))
        return False

    def set(self, **attrs) -> None:
        """Add attrs known only inside the span."""
        self.attrs.update(attrs)


class _Bind:
    __slots__ = ("trace", "outer")

    def __init__(self, trace):
        self.trace = trace

    def __enter__(self):
        state = _thread()
        self.outer, state[1] = state[1], self.trace
        return self

    def __exit__(self, *exc):
        _thread()[1] = self.outer
        return False


class _Off:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs) -> None:
        pass


_OFF = _Off()


def span(name: str, **attrs):
    """Context manager recording one span of ``name`` (see the module
    docstring); ``trace=`` binds a trace id for the spans under it."""
    return _Span(name, attrs) if _enabled else _OFF


def bind(trace):
    """Context manager: spans opened inside carry ``trace`` in their
    attrs, without a span of its own."""
    return _Bind(trace) if _enabled else _OFF


def spans(name: Optional[str] = None,
          since_ns: Optional[int] = None) -> List[Record]:
    """Closed spans and compile records, oldest first; only those named
    ``name`` and starting at or after ``since_ns`` where given."""
    with _lock:
        out = list(_records)
    if name is not None:
        out = [r for r in out if r.name == name]
    if since_ns is not None:
        out = [r for r in out if r.t0_ns >= since_ns]
    return out


def dropped() -> int:
    """Records pushed out of the full deque since the last ``reset``."""
    return _dropped


def reset() -> None:
    """Forget every record and the dropped count."""
    global _dropped
    with _lock:
        _records.clear()
        _dropped = 0


def enable(flag: bool = True) -> None:
    """Turn recording on (the default) or off; off, ``span`` and
    ``bind`` return a no-op context and compiles are not recorded."""
    global _enabled
    _enabled = bool(flag)
