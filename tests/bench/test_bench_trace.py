"""The benchmark's trace reduction: busy time, idle gaps named by the
host span around them, kernel and module time, and the kernels' cost
functions.  On a hand-built trace whose answers are known, and on a
small trace recorded on one TPU v5e and kept with the benchmark."""

from pathlib import Path

import pytest

from bench import trace
from bench.costs import load

FIXTURE = Path(__file__).resolve().parent / "data" / "v5e_kernels"


class _Ev:
    def __init__(self, name, start_us, dur_us, stats=()):
        self.name, self.start_ns, self.duration_ns = (
            name, start_us * 1e3, dur_us * 1e3)
        self.stats = list(stats)


class _Line:
    def __init__(self, name, events):
        self.name, self.events = name, events


class _Plane:
    def __init__(self, name, lines):
        self.name, self.lines = name, lines


def _planes():
    host = _Plane("/host:CPU", [_Line("python", [
        _Ev("bench.window", 0, 100),
        _Ev("bench.engine", 0, 100),
        _Ev("bench.step", 10, 30),
        _Ev("bench.admit", 60, 20),
    ])])
    dev = _Plane("/device:TPU:0", [
        _Line("XLA Ops", [
            _Ev("fusion.1", 15, 10),
            _Ev("fusion.1", 20, 10),          # overlaps the first
            _Ev("fusion.3", 35, 3),
            _Ev("%rwkv6_wkv.3 = f32[4,64,64] custom-call(%copy.1)", 65, 5),
            _Ev("%bitcast.4 = f32[4,64,64] bitcast(%rwkv6_wkv.3)", 70, 0),
            _Ev("fusion.2", 150, 10),         # after the window
        ]),
        _Line("XLA Modules", [_Ev("jit_prefill_step(7)", 64, 8)]),
    ])
    return [host, dev]


def test_busy_idle_and_named_gaps():
    s = trace.reduce_planes(_planes(), chips=1)
    # the device trace covers 15-70 of the annotated 0-100
    assert s.annotated == pytest.approx((0.0, 100e-6))
    assert s.window == pytest.approx((15e-6, 70e-6))
    assert s.window_s == pytest.approx(55e-6)
    assert s.busy_s == pytest.approx(23e-6)     # 15-30, 35-38 and 65-70
    # gap 30-35 lies inside bench.step (10-40); gap 38-65 has its middle
    # in bench.engine alone
    gaps = dict(s.breakdown["idle_gaps"])
    assert gaps == {"engine": pytest.approx(27e-6),
                    "step": pytest.approx(5e-6)}
    ops = dict(s.breakdown["device_ops"])
    assert ops["fusion.1"] == pytest.approx(20e-6)
    assert "fusion.2" not in ops


def test_kernel_and_module_time():
    s = trace.reduce_planes(_planes(), chips=1)
    # by the op's own name, not by an operand that names the kernel
    assert s.kernel_seconds("rwkv6_wkv") == pytest.approx(5e-6)
    assert s.kernel_seconds("flash_decode") == 0.0
    assert s.module_seconds("prefill_step") == pytest.approx(8e-6)


def test_window_span_is_required():
    planes = _planes()
    planes[0].lines[0].events.pop(0)
    with pytest.raises(ValueError):
        trace.reduce_planes(planes)


@pytest.mark.parametrize("kernel,args,flops,nbytes", [
    # one row of 8 heads x 64 over 17 cached positions
    ("flash_decode", (8, 8, 64, [17]), 4 * 17 * 8 * 64,
     4 * (2 * 8 * 64 + 2 * 17 * 8 * 64)),
    # two rows, 4 query heads sharing 2 kv heads of 32, 5 and 3 positions
    ("flash_decode", (4, 2, 32, [5, 3]), 4 * 8 * 4 * 32,
     4 * (2 * 2 * 4 * 32 + 2 * 8 * 2 * 32)),
])
def test_kernel_costs_from_shapes(kernel, args, flops, nbytes):
    f, b = load(kernel).cost(*args)
    assert f == pytest.approx(flops)
    assert b == pytest.approx(nbytes)


def test_recorded_v5e_trace():
    """A flash-decode call in a ``bench.step`` span, a 2 ms sleep, then a
    wkv call in a ``bench.admit`` span, recorded on one TPU v5e.  The
    device keeps its own clock: unshifted, the flash-decode call would
    fall before the window."""
    if not any(FIXTURE.glob("**/*.xplane.pb")):
        pytest.fail(f"recorded trace missing under {FIXTURE}")
    s = trace.reduce(FIXTURE, chips=1)
    assert 0 < s.busy_s < s.window_s
    assert s.kernel_seconds("flash_decode") > 0
    assert s.kernel_seconds("rwkv6_wkv") > 0
    gaps = dict(s.breakdown["idle_gaps"])
    assert gaps.get("harness", 0.0) >= 1.5e-3     # the sleep
