"""The readers of the program's own spans (``bench/program_trace.py`` and
the metrics built on it): the clock bracket from known dispatch, program
and wait times, idle gaps named by the innermost program span at a known
shift, each metric's value on hand-built records, and None where a run
has no trace or the program no recorder."""

import sys

import pytest

from bench import harness, program_trace, run
from bench.trace import Event, Summary
from repro import tracing
from repro.tracing import Record

CELL = "marian-en-zh.sentences-poisson"
S = 1000.0              # trace clock = host clock + S
COARSE = S + 0.002      # what the run's own alignment would give


def _ns(t):
    return int(round(t * 1e9))


def _records():
    """Two requests at host seconds 100.0 and 100.1, each a decision
    (6 ms of prediction in 10) then a 50-ms translate whose wait is 46 ms;
    one compile in the first dispatch; one span before the window."""
    out = [Record(1, None, "sched.decide", _ns(99.5), _ns(99.6), {})]
    nid = 10
    for i, t in enumerate((100.0, 100.1)):
        ids = range(nid, nid + 9)
        b, d, mh, t0, t1, tr, dp, wt, cc = ids
        nid += 9
        out += [
            Record(mh, d, "sched.m_hat", _ns(t + .001), _ns(t + .003), {}),
            Record(t0, d, "sched.t_exe", _ns(t + .004), _ns(t + .006),
                   {"tier": 0}),
            Record(t1, d, "sched.t_exe", _ns(t + .007), _ns(t + .009),
                   {"tier": 1}),
            Record(d, b, "sched.decide", _ns(t), _ns(t + .010), {}),
            Record(dp, tr, "exec.dispatch", _ns(t + .011), _ns(t + .013), {}),
            Record(wt, tr, "exec.wait", _ns(t + .013), _ns(t + .059), {}),
            Record(tr, b, "exec.translate", _ns(t + .010), _ns(t + .060),
                   {"b": 1, "w": 16}),
            Record(b, None, "engine.submit_batch", _ns(t), _ns(t + .060),
                   {"trace": i}),
        ]
        if i == 0:
            out.append(Record(cc, dp, tracing.COMPILE, _ns(t + .012),
                              _ns(t + .012), {"seconds": 0.5}))
    return sorted(out, key=lambda r: r.t1_ns)


def _summary():
    """Trace clock: each translate program starts 2.5 ms after its
    dispatch starts and ends 2.5 ms before its wait ends; one 0.5-ms
    device op in each decision."""
    ops, modules = [], []
    for t in (100.0, 100.1):
        m0, m1 = t + .011 + S + .0025, t + .059 + S - .0025
        modules.append(Event("jit_nmt_translate(3)", m0, m1 - m0))
        modules.append(Event("jit_multiply", t + .005 + S, .0005))
        ops += [Event("fusion.1", t + .005 + S, .0005),
                Event("while.47", m0, m1 - m0)]
    w = (100.0 + S, 100.2 + S)
    busy = sum(e.dur for e in ops)
    return Summary(window=w, annotated=w, ops=[ops], modules=modules,
                   spans=[], busy_s=busy, window_s=w[1] - w[0],
                   breakdown={})


def _run(trace=True):
    spans = harness.Spans()
    spans.intervals["window"] = [(100.0, 101.0)]
    s = _summary() if trace else None
    return run.Run(spans=spans, trace=s,
                   traced_window=(s.window[0] - COARSE, s.window[1] - COARSE)
                   if trace else None)


@pytest.fixture
def recorded(monkeypatch):
    recs = _records()
    monkeypatch.setattr(tracing, "spans", lambda *a, **k: list(recs))
    monkeypatch.setattr(tracing, "dropped", lambda: 0)
    return recs


def _reader(name):
    return run._load_module(run.HERE / "metrics" / f"{name}.py",
                            f"bench_metric_{name}")


def test_bracket_from_known_times(recorded):
    pairs = program_trace.pair_translates(
        recorded, program_trace.translate_modules(_summary()), COARSE)
    assert len(pairs) == 2
    lo, hi = program_trace.bracket(pairs)
    assert lo == pytest.approx(S - .0025, abs=1e-9)
    assert hi == pytest.approx(S + .0025, abs=1e-9)
    assert program_trace.bracket([]) is None


def test_idle_named_by_innermost_program_span(recorded):
    gaps = program_trace.idle_gaps(_summary())
    assert sum(b - a for a, b in gaps) == pytest.approx(0.2 - 2 * .0435)
    al = program_trace.Aligned(recorded, gaps, S, 1)
    idle = al.idle_by_span()
    # host ms 0-5 of the first request lies in sched.m_hat (1-3) by its
    # middle; 5.5-13.5 of each in the decision's own time (middle 9.5,
    # after the last t_exe); 56.5-105 and 156.5-200 in no span
    assert idle == {
        "sched.m_hat": pytest.approx(.005, abs=1e-9),
        "sched.decide": pytest.approx(.008 + .008, abs=1e-9),
        program_trace.NONE: pytest.approx(.0485 + .0435, abs=1e-9)}
    assert al.idle_within("sched.decide") == pytest.approx(.021, abs=1e-9)
    assert al.idle_within("exec.translate") == 0.0


def test_aligned_run_uses_the_bracket_midpoint(recorded, capsys):
    r = _run()
    al = program_trace.aligned(r)
    assert al.shift == pytest.approx(S, abs=1e-9)
    assert program_trace.aligned(r) is al          # made once per run
    err = capsys.readouterr().err
    assert "bracket width 5000.0 us over 2 translates" in err
    assert "idle by program span" in err
    # at the coarse shift the first gap's middle would fall in the
    # decision itself, not in sched.m_hat
    coarse = program_trace.Aligned(recorded,
                                   program_trace.idle_gaps(r.trace),
                                   COARSE, 1)
    assert coarse.idle_by_span() != al.idle_by_span()


@pytest.mark.parametrize("name,value", [
    ("decide_predict_us", 6000.0),
    ("translate_host_ms", 4.0),
    ("window_compiles", 1),
    ("translate_device_ms", 43.0),
    ("idle_decide_pct", 100 * .021 / .2),
])
def test_metric_values(recorded, name, value):
    assert _reader(name).read(_run()) == pytest.approx(value, abs=1e-6)


def _metrics(source=None):
    bench = harness.load_json(run.ROOT / "BENCHMARK.json")
    return [m["name"] for m in bench["per_layer"]
            if CELL in m.get("workloads", [CELL])
            and (source is None or m["source"] == source)]


@pytest.mark.parametrize("name", _metrics("device_trace"))
def test_device_readers_return_none_without_a_trace(recorded, name):
    r = _run(trace=False)
    r.peak = None
    assert _reader(name).read(r) is None


@pytest.mark.parametrize("name", ["decide_predict_us", "translate_host_ms",
                                  "idle_decide_pct", "window_compiles"])
def test_readers_return_none_without_the_recorder(monkeypatch, name):
    """A program without ``repro.tracing`` (an older tree) gives no value
    and raises nothing."""
    import repro
    monkeypatch.delattr(repro, "tracing")
    monkeypatch.setitem(sys.modules, "repro.tracing", None)
    assert _reader(name).read(_run()) is None


def test_translate_device_ms_needs_the_named_program():
    """Where the translate program has another name (``jit_run`` on an
    older tree) there is nothing to read."""
    r = _run()
    for m in r.trace.modules:
        m.name = m.name.replace("nmt_translate", "run")
    assert _reader("translate_device_ms").read(r) is None
