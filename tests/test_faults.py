"""PR 8: fault-tolerant collaborative serving.

The load-bearing pins:

* ZERO-FAULT PARITY — arming the fault machinery with an empty
  schedule changes nothing, bit for bit, in either the engine or the
  DES (the machinery must cost nothing when nothing fails);
* circuit-breaker state machine: CLOSED -k failures-> OPEN -cooldown->
  HALF_OPEN -probe success-> CLOSED (and probe failure -> OPEN again);
* failover strictly beats the no-retry baseline under an injected
  outage, losing zero requests;
* split-plan decode-leg failover re-homes the decode from the SHIPPED
  EncoderStates (exactness: any decode-capable tier resumes to the
  fused output, pinned at the executor level);
* estimator/calibrator hygiene: link state invalidates on breaker
  recovery, failed samples never reach the N->M / plane feedback;
* property (hypothesis shim): under arbitrary outage schedules every
  request is EITHER served or shed, never both, never neither.
"""

import dataclasses
import math

import numpy as np
import pytest

from _hypothesis_compat import given, settings, st
from repro.core.calibration import OnlineCalibrator
from repro.core.faults import (
    CLOSED,
    HALF_OPEN,
    OPEN,
    CircuitBreaker,
    FaultSchedule,
    LinkFault,
    RetryPolicy,
    Straggler,
    TierOutage,
)
from repro.core.latency_model import DeviceProfile, LinearLatencyModel
from repro.core.length_regressor import LinearN2M
from repro.core.profiles import make_profile
from repro.core.scheduler import MultiTierScheduler, SchedTier
from repro.core.simulator import SimTier, make_poisson_stream, simulate_des
from repro.core.tx_estimator import LinkModel, TxEstimator
from repro.runtime.engine import CollaborativeEngine, Tier
from repro.runtime.serving import TierFaultError, build_executor


# ------------------------------------------------------ fault schedule --
def test_schedule_queries():
    f = FaultSchedule(
        outages=(TierOutage(1, 10.0, 20.0),),
        link_faults=(LinkFault(2, 5.0, 15.0, rtt_factor=3.0,
                               bandwidth_factor=0.5),
                     LinkFault(2, 12.0, 14.0, blackhole=True)),
        stragglers=(Straggler(0, 0.0, 4.0, slowdown=2.5),))
    assert not f.empty and FaultSchedule().empty
    assert f.tier_down(1, 15.0) and not f.tier_down(1, 20.0)  # end-exclusive
    assert not f.tier_down(2, 15.0)
    assert f.link_blackhole(2, 13.0) and not f.link_blackhole(2, 11.0)
    assert f.link_factors(2, 10.0) == (3.0, 0.5)
    assert f.link_factors(2, 30.0) == (1.0, 1.0)
    assert f.slowdown(0, 2.0) == 2.5 and f.slowdown(0, 5.0) == 1.0
    ev = f.outage_events()
    assert [e[1] for e in ev if e[2] == 1] == ["down", "up"]
    assert ev == sorted(ev, key=lambda e: e[0])
    assert f.horizon_s() >= 20.0


def test_random_schedule_deterministic_and_protects_tiers():
    a = FaultSchedule.random(3, 600.0, seed=4, outage_rate_hz=1 / 60.0)
    b = FaultSchedule.random(3, 600.0, seed=4, outage_rate_hz=1 / 60.0)
    assert a == b
    assert all(o.tier != 0 for o in a.outages)   # protect_tiers=(0,)
    assert FaultSchedule.random(3, 600.0, seed=5) \
        != FaultSchedule.random(3, 600.0, seed=6) or True  # seeds may tie


# ----------------------------------------------------- circuit breaker --
def test_breaker_transitions():
    b = CircuitBreaker(failure_threshold=3, reset_timeout_s=1.0)
    assert b.state == CLOSED and b.allow(0.0)
    assert not b.record_failure(0.1) and not b.record_failure(0.2)
    assert b.record_failure(0.3)                 # third consecutive: opens
    assert b.state == OPEN and b.n_opens == 1
    assert not b.allow(0.5)                      # cooling down
    assert b.time_to_probe(0.5) == pytest.approx(0.8)
    assert b.allow(1.5)                          # cooldown passed: probe
    assert b.state == HALF_OPEN and b.n_probes == 1
    assert b.record_failure(1.6)                 # probe failed: re-open NOW
    assert b.state == OPEN and b.n_opens == 2
    assert b.allow(2.7)                          # second probe
    assert b.record_success()                    # True exactly on recovery
    assert b.state == CLOSED
    assert not b.record_success()                # steady state: no signal
    assert not b.record_failure(3.0)             # counter was reset
    assert b.state == CLOSED


def test_retry_policy_backoff_bounded_and_seeded():
    p = RetryPolicy(backoff_base_s=0.1, backoff_factor=2.0,
                    backoff_max_s=0.5, jitter_frac=0.1)
    r1 = np.random.default_rng(0)
    r2 = np.random.default_rng(0)
    seq = [p.backoff(a, r1) for a in range(6)]
    assert seq == [p.backoff(a, r2) for a in range(6)]   # deterministic
    for a, v in enumerate(seq):
        assert 0.0 < v <= 0.5 * 1.1 + 1e-12
    assert p.detect_s(False) == p.fail_fast_s
    assert p.detect_s(True) == p.timeout_s       # blackhole = full timeout


# --------------------------------------------- estimator / calibrator --
def test_tx_estimator_invalidate_bootstraps_next_sample():
    est = TxEstimator(init_rtt_s=0.05)
    est.observe(0.0, 0.2)
    est.observe(1.0, 0.2)
    assert est.rtt(1.0) == pytest.approx(0.2)
    est.invalidate()
    assert est.n_invalidations == 1
    assert est.rtt(1.0) == pytest.approx(0.2)    # estimate kept as guess
    est.observe(2.0, 0.01)                       # first post-recovery sample
    assert est.rtt(2.0) == pytest.approx(0.01)   # replaces wholesale
    # and the causal guard restarted too (old timestamps accepted again)


def test_link_model_invalidate_touches_both_directions():
    links = LinkModel(3)
    links.add_link(0, 1, TxEstimator(init_rtt_s=0.01))
    links.add_link(1, 2, TxEstimator(init_rtt_s=0.02))
    assert links.invalidate(1) == 4              # 0->1, 1->0, 1->2, 2->1
    assert links.invalidate(0) == 2


def test_calibrator_excludes_failed_samples():
    cal = OnlineCalibrator(1, interval=2, min_samples=3)
    assert not cal.record(0, 10.0, 9.0, 0.5, ok=False)
    assert cal.n_excluded == 1 and cal.n_recorded == 0
    assert not cal.record(0, 10.0, 9.0, 0.01)
    assert not cal.record(0, 12.0, 11.0, 1e9, ok=False)  # timeout artifact
    assert cal.record(0, 20.0, 18.0, 0.02)       # 2 good ones: refit due
    assert cal.n_excluded == 2 and cal.n_recorded == 2


def test_faulty_executor_wrapper():
    calls = []
    wrapped = build_executor(lambda t: calls.append(1) or (1, t),
                             kind="raw", faults={1})
    assert wrapped(np.zeros(2, np.int32))[0] == 1
    with pytest.raises(TierFaultError):
        wrapped(np.zeros(2, np.int32))
    assert wrapped(np.zeros(2, np.int32))[0] == 1
    assert wrapped.calls == {"n": 3, "faults": 1}
    assert len(calls) == 2                       # the crash pre-empted work


# ------------------------------------------------------ engine parity --
def _engine(**kw):
    edge = Tier(DeviceProfile("e", LinearLatencyModel(2e-3, 8e-3, 0.01),
                              0.0))
    cloud = Tier(DeviceProfile("c", LinearLatencyModel(4e-4, 1.6e-3, 2e-3),
                               0.0))
    profile = make_profile("cp2", seed=7)
    cloud = dataclasses.replace(
        cloud, rtt_fn=lambda t: float(profile.rtt_at(t)))
    return CollaborativeEngine(tiers=[edge, cloud],
                               n2m=LinearN2M(1.0, 0.0), seed=0, **kw)


def _drive(eng, k=300, rate_hz=20.0):
    rng = np.random.default_rng(3)
    return [eng.submit(np.zeros(int(rng.integers(2, 200)), np.int32),
                       now_s=i / rate_hz) for i in range(k)]


def test_engine_zero_fault_parity_is_bitwise():
    plain = _drive(_engine())
    armed = _drive(_engine(faults=FaultSchedule(), retry=RetryPolicy()))
    for a, b in zip(plain, armed):
        assert a.device == b.device
        assert a.latency_s == b.latency_s        # bit-for-bit
        assert a.m_out == b.m_out
        assert b.attempts == 1 and b.failed_tiers == ()


def test_engine_failover_beats_no_retry_under_outage():
    faults = FaultSchedule(outages=(TierOutage(1, 3.0, 9.0),))
    nr = _engine(faults=faults)
    _drive(nr)
    fo = _engine(faults=faults, retry=RetryPolicy())
    results = _drive(fo)
    s_nr, s_fo = nr.stats(), fo.stats()
    assert s_nr["fault_lost"] > 0 and s_nr["availability"] < 1.0
    assert s_fo["fault_lost"] == 0 and s_fo["availability"] == 1.0
    assert s_fo["availability"] > s_nr["availability"]
    assert s_fo["failovers"] == s_fo["retries"] > 0
    retried = [r for r in results if r.attempts > 1]
    assert retried and all(1 in r.failed_tiers for r in retried)
    assert all(r.device == 0 for r in retried)   # degraded to edge
    # detection + backoff is real latency, not hidden
    assert all(r.latency_s > 0 for r in retried)


def test_engine_all_tiers_dark_sheds_with_retry_after():
    faults = FaultSchedule(outages=(TierOutage(0, 0.0, 50.0),
                                    TierOutage(1, 0.0, 50.0)))
    eng = _engine(faults=faults, retry=RetryPolicy(max_retries=1))
    results = _drive(eng, k=40)
    assert all(r.shed for r in results)
    assert eng.stats()["availability"] == 0.0
    # a shed response tells the client when to come back (ROADMAP 5c)
    assert all(r.retry_after_s is not None and r.retry_after_s >= 0.0
               for r in results)


def test_engine_real_executor_crash_fails_over():
    crashing = build_executor(lambda t: (len(t), t), kind="raw",
                              faults={0})
    edge = Tier(DeviceProfile("e", LinearLatencyModel(2e-3, 8e-3, 0.01),
                              0.0), executor=crashing)
    cloud = Tier(DeviceProfile("c", LinearLatencyModel(4e-4, 1.6e-3, 2e-3),
                               0.0))
    cloud = dataclasses.replace(cloud, rtt_fn=lambda t: 5.0)
    eng = CollaborativeEngine(tiers=[edge, cloud],   # WAN: edge always wins
                              n2m=LinearN2M(1.0, 0.0),
                              seed=0, retry=RetryPolicy())
    r0 = eng.submit(np.zeros(4, np.int32), now_s=0.0)
    r1 = eng.submit(np.zeros(4, np.int32), now_s=1.0)
    assert r0.device == 1 and r0.attempts == 2 and r0.failed_tiers == (0,)
    assert r1.device == 0 and r1.attempts == 1   # executor healthy again
    assert crashing.calls["faults"] == 1         # call 1 never happened at 0


def test_engine_real_executor_other_error_propagates():
    """Only TierFaultError means "tier down": any other error from a real
    executor (a compile failure, device OOM, a bug) surfaces to the
    caller and is never served by failover, even with retries armed."""
    calls = []

    def broken(tokens):
        calls.append(1)
        raise RuntimeError("device out of memory")

    edge = Tier(DeviceProfile("e", LinearLatencyModel(2e-3, 8e-3, 0.01),
                              0.0), executor=broken)
    cloud = Tier(DeviceProfile("c", LinearLatencyModel(4e-4, 1.6e-3, 2e-3),
                               0.0), rtt_fn=lambda t: 5.0)
    eng = CollaborativeEngine(tiers=[edge, cloud],   # WAN: edge always wins
                              n2m=LinearN2M(1.0, 0.0),
                              seed=0, retry=RetryPolicy())
    with pytest.raises(RuntimeError, match="out of memory"):
        eng.submit(np.zeros(4, np.int32), now_s=0.0)
    assert calls == [1]                          # no retry, no second tier
    assert eng.retry_count == 0 and eng.failover_count == 0
    assert eng.fault_failures.sum() == 0 and not eng.results


# --------------------------------------------------------- DES parity --
def _des_setup(seed=5):
    npu = DeviceProfile("npu", LinearLatencyModel(4e-4, 1.6e-3, 4e-3), 0.05)
    edge = DeviceProfile("edge", LinearLatencyModel(1.5e-4, 6e-4, 8e-3),
                         0.05)
    cloud = DeviceProfile("cloud", LinearLatencyModel(2e-5, 9e-5, 2e-3),
                          0.08)
    lan, wan = make_profile("cp2", seed=seed), make_profile("cp1", seed=seed)
    tiers = [SimTier("npu", npu, servers=1, queue_capacity=16),
             SimTier("edge", edge, servers=2, queue_capacity=64, link=lan),
             SimTier("cloud", cloud, servers=8, link=wan)]
    sched = MultiTierScheduler(
        [SchedTier("npu", dataclasses.replace(npu.model), None),
         SchedTier("edge", dataclasses.replace(edge.model),
                   TxEstimator(init_rtt_s=float(lan.rtt_at(0.0)))),
         SchedTier("cloud", dataclasses.replace(cloud.model),
                   TxEstimator(init_rtt_s=float(wan.rtt_at(0.0))))],
        LinearN2M(0.9, 2.0))
    return sched, tiers


def _des_stream(k=1500, rate=15.0, seed=2, slo_s=None):
    rng = np.random.default_rng(seed)
    n = rng.integers(2, 200, k).astype(np.float64)
    m = np.maximum(0.9 * n + rng.normal(0, 3, k), 1.0)
    return make_poisson_stream(n, m, m, rate_hz=rate, seed=seed, slo_s=slo_s)


_ARRAYS = ("tier", "t_start_s", "t_finish_s", "wait_s", "tx_s", "exec_s",
           "latency_s", "shed", "overflow")


def test_des_zero_fault_parity_is_bitwise():
    sched0, tiers0 = _des_setup()
    base = simulate_des(sched0, _des_stream(), tiers0, seed=0)
    sched1, tiers1 = _des_setup()
    armed = simulate_des(sched1, _des_stream(), tiers1, seed=0,
                         faults=FaultSchedule())
    for f in _ARRAYS:
        assert np.array_equal(getattr(base, f), getattr(armed, f),
                              equal_nan=True), f
    assert base.fault_stats is None and armed.fault_stats is not None
    assert np.all(armed.attempts == 1)


def test_des_failover_beats_no_retry_under_outage():
    faults = FaultSchedule(outages=(TierOutage(2, 10.0, 50.0),))
    s0, t0 = _des_setup()
    nr = simulate_des(s0, _des_stream(), t0, seed=0, faults=faults)
    s1, t1 = _des_setup()
    fo = simulate_des(s1, _des_stream(), t1, seed=0, faults=faults,
                      retry=RetryPolicy(), collect_events=True)
    assert nr.fault_stats["fault_lost"] > 0
    assert fo.fault_stats["fault_lost"] == 0
    assert fo.fault_stats["availability"] > nr.fault_stats["availability"]
    assert fo.fault_stats["retries"] > 0
    assert fo.fault_stats["breaker_opens"] >= 1
    assert nr.fault_stats["breaker_opens"] == 0   # baseline: no breakers
    # retried-and-served requests landed on a healthy tier
    served_retried = ~fo.shed & (fo.attempts > 1)
    assert served_retried.any()
    assert np.all(fo.tier[served_retried] != 2)
    kinds = {e[1] for e in fo.events}
    assert {"tier_down", "tier_up", "fault", "retry"} <= kinds
    s = fo.summary()
    for key in ("availability", "retries", "fault_lost", "goodput_rps"):
        assert key in s


def test_des_fault_run_is_deterministic():
    faults = FaultSchedule(outages=(TierOutage(2, 10.0, 50.0),),
                           link_faults=(LinkFault(1, 30.0, 40.0,
                                                  rtt_factor=5.0),))
    runs = []
    for _ in range(2):
        s, t = _des_setup()
        runs.append(simulate_des(s, _des_stream(), t, seed=0, faults=faults,
                                 retry=RetryPolicy()))
    for f in _ARRAYS:
        assert np.array_equal(getattr(runs[0], f), getattr(runs[1], f),
                              equal_nan=True), f


def test_des_degraded_link_prices_the_episode():
    """Non-blackhole degradation: served requests on the degraded link
    pay the inflated tx during the episode, and nothing is lost."""
    faults = FaultSchedule(link_faults=(LinkFault(2, 10.0, 60.0,
                                                  rtt_factor=4.0,
                                                  bandwidth_factor=0.25),))
    s0, t0 = _des_setup()
    base = simulate_des(s0, _des_stream(), t0, seed=0)
    s1, t1 = _des_setup()
    deg = simulate_des(s1, _des_stream(), t1, seed=0, faults=faults,
                       retry=RetryPolicy())
    assert deg.fault_stats["fault_lost"] == 0
    in_ep = (deg.t_start_s >= 10.0) & (deg.t_start_s < 60.0) \
        & (deg.tier == 2) & ~deg.shed
    if in_ep.any():
        assert np.nanmean(deg.tx_s[in_ep]) > np.nanmean(
            base.tx_s[(base.tier == 2) & ~base.shed])


def test_des_backpressure_replay_with_deadline():
    """ROADMAP 5c: a deadline shed under retry.replay_shed becomes a
    delayed re-submission carrying retry_after_s; replays are counted."""
    faults = FaultSchedule(outages=(TierOutage(2, 5.0, 40.0),))
    s0, t0 = _des_setup()
    stream = _des_stream(k=1500, rate=40.0, slo_s=0.6)
    r = simulate_des(s0, stream, t0, seed=0, faults=faults,
                     retry=RetryPolicy(), collect_events=True)
    assert r.retry_after_s is not None
    hinted = ~np.isnan(r.retry_after_s)
    assert np.all(r.retry_after_s[hinted] >= 0.0)
    if r.fault_stats["replays"] > 0:
        assert any(e[1] == "backpressure" for e in r.events)


# ------------------------------------- split decode-leg failover ------
@pytest.mark.slow
def test_split_decode_failover_exact_and_engine_rehomes():
    """The shipped EncoderStates are the recovery unit: ANY decode-
    capable tier resumes them to the fused output (executor-level
    exactness), and the engine re-homes a split plan's decode leg when
    its tier dies mid-flight (attempts/failed_tiers recorded)."""
    import jax

    from repro.core.latency_model import ActivationCostModel
    from repro.nmt import GRUSeq2Seq, RNNConfig

    model = GRUSeq2Seq(RNNConfig(vocab_src=64, vocab_tgt=64, embed=32,
                                 hidden=32, layers=2, max_decode_len=24))
    params = model.init(jax.random.PRNGKey(0))
    fused = model.make_translate_batched(params)
    enc, dec = build_executor(model, kind="split", params=params)

    rng = np.random.default_rng(3)
    toks = rng.integers(3, 64, 9).astype(np.int32)
    mask = np.ones((1, 9), np.float32)
    lens_f, toks_f = fused(toks[None, :], mask)
    # exactness: the SAME states decode identically wherever they land
    states = enc(toks)
    m1, out1 = dec(states)
    m2, out2 = dec(states)                        # "another tier" = same fn
    assert m1 == m2 == int(np.asarray(lens_f)[0])
    assert np.array_equal(out1, out2)
    assert np.array_equal(out1, np.asarray(toks_f)[0, :max(m1, 1)])

    # engine: kill the decode tier exactly while states are in flight
    dev = (3e-4, 5e-3, 2e-3)
    edge = (2e-5, 2.5e-3, 4e-3)
    cloud = (1e-5, 1e-4, 2e-3)
    links = LinkModel(3)
    links.add_link(1, 2, TxEstimator(init_rtt_s=4e-3, bandwidth_bps=1e9))
    tiers = [
        Tier(DeviceProfile("dev", LinearLatencyModel(*dev), 0.05),
             name="dev"),
        Tier(DeviceProfile("edge", LinearLatencyModel(*edge), 0.05),
             name="edge", rtt_fn=lambda t: 5e-3, bandwidth_bps=200e6,
             encode_executor=enc, decode_executor=dec),
        Tier(DeviceProfile("cloud", LinearLatencyModel(*cloud), 0.05),
             name="cloud", rtt_fn=lambda t: 90e-3, bandwidth_bps=20e6,
             decode_executor=dec),
    ]
    faults = FaultSchedule(outages=(TierOutage(2, 2.0, 8.0),))
    eng = CollaborativeEngine(
        n2m=LinearN2M(1.0, 0.0), tiers=tiers, seed=0,
        links=links, activation=ActivationCostModel(512, 4),
        inter_rtt_fns={(1, 2): lambda t: 4e-3}, allow_split=True,
        faults=faults, retry=RetryPolicy())
    rng = np.random.default_rng(11)
    for i in range(60):
        eng.submit(rng.integers(3, 64, int(rng.integers(8, 200)))
                   .astype(np.int32), now_s=float(i) * 0.2)
    assert eng.decode_failovers > 0
    # a re-homed decode leg may land back on the encode tier itself
    # (degenerate split(1, 1), not is_split) or on another decode-capable
    # tier; either way the failed tier is recorded and never the device
    rehomed = [r for r in eng.results
               if r.plan is not None and not r.shed and r.attempts > 1
               and r.failed_tiers == (2,)]
    assert len(rehomed) >= eng.decode_failovers
    for r in rehomed:
        assert r.device != 2
        assert r.plan.decode_tier == r.device
        assert r.m_out >= 1                      # decoded from the states


# ------------------------------------------------------- property -----
@settings(max_examples=12, deadline=None)
@given(start=st.floats(0.0, 40.0), dur=st.floats(0.5, 40.0),
       tier=st.integers(1, 2), use_retry=st.booleans(),
       blackhole=st.booleans())
def test_property_served_xor_shed(start, dur, tier, use_retry, blackhole):
    """No request is ever both served and shed, or neither, under any
    outage/blackhole window, with or without retries."""
    if blackhole:
        faults = FaultSchedule(link_faults=(LinkFault(tier, start,
                                                      start + dur,
                                                      blackhole=True),))
    else:
        faults = FaultSchedule(outages=(TierOutage(tier, start,
                                                   start + dur),))
    sched, tiers = _des_setup()
    r = simulate_des(sched, _des_stream(k=400), tiers, seed=0,
                     faults=faults,
                     retry=RetryPolicy() if use_retry else None)
    served = ~r.shed & (r.tier >= 0)
    assert np.all(served ^ r.shed)               # exactly one of the two
    assert np.all(np.isfinite(r.latency_s[served]))
    assert np.all(np.isnan(r.latency_s[r.shed]))
    assert np.all(r.attempts >= 1)
    st_ = r.fault_stats
    assert 0.0 <= st_["availability"] <= 1.0
    assert int(served.sum()) + int(r.shed.sum()) == 400
