"""Unit + property tests for the N->M length estimators (paper Fig. 3),
and the host evaluation of their predictions and of the latency plane."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core.latency_model import LinearLatencyModel
from repro.core.length_regressor import (
    BucketN2M,
    HuberN2M,
    LinearN2M,
    MeanN2M,
    RidgeN2M,
    prefilter_pairs,
)
from repro.data.synthetic import LANGUAGE_PAIRS, make_corpus


def test_linear_recovers_exact_line():
    n = np.arange(1, 100, dtype=float)
    m = 0.7 * n + 3.0
    r = LinearN2M().fit(n, m)
    assert r.gamma == pytest.approx(0.7, abs=1e-4)
    assert r.delta == pytest.approx(3.0, abs=1e-3)
    assert r.r2(n, m) == pytest.approx(1.0, abs=1e-5)


@pytest.mark.parametrize("pair", list(LANGUAGE_PAIRS))
def test_fig3_r2_on_synthetic_corpora(pair):
    """Paper Fig. 3: linear N->M fit reaches R^2 ~ 0.99 on all 3 pairs.

    (R^2 computed on bucket-averaged M as in the figure, which plots the
    average M for a given N.)
    """
    corpus = make_corpus(pair, 20000, seed=1)
    n, m = prefilter_pairs(corpus.n, corpus.m_real)
    reg = LinearN2M().fit(n, m)
    # recovered slope close to the generating verbosity factor
    assert reg.gamma == pytest.approx(LANGUAGE_PAIRS[pair].gamma, rel=0.1)
    # bucket-averaged R^2 as plotted in Fig. 3 (buckets with enough support;
    # the figure's dots are averages over all outputs of the same length)
    uniq = np.unique(n)
    uniq = np.array([u for u in uniq if (n == u).sum() >= 5])
    avg_m = np.array([m[n == u].mean() for u in uniq])
    assert reg.r2(uniq, avg_m) > 0.97
    if pair in ("fr-en", "en-zh"):
        assert reg.gamma < 1.0  # paper: EN less verbose than FR, ZH than EN


def test_prefilter_removes_mismatched_pairs():
    n = np.array([10.0, 20.0, 5.0, 50.0])
    m = np.array([11.0, 90.0, 4.0, 1.0])  # 2nd and 4th are misaligned
    nf, mf = prefilter_pairs(n, m, max_ratio=3.0)
    assert len(nf) == 2
    assert set(nf.tolist()) == {10.0, 5.0}


def test_huber_resists_outliers():
    rng = np.random.default_rng(0)
    n = rng.uniform(1, 100, 500)
    m = 0.8 * n + 2 + rng.normal(0, 0.5, 500)
    m[:50] = rng.uniform(150, 200, 50)  # 10% gross outliers
    ols = LinearN2M().fit(n, m)
    hub = HuberN2M(huber_delta=2.0).fit(n, m)
    assert abs(hub.gamma - 0.8) < abs(ols.gamma - 0.8)
    assert hub.gamma == pytest.approx(0.8, abs=0.05)


def test_ridge_shrinks_towards_zero():
    n = np.array([1.0, 2.0, 3.0, 4.0])
    m = 2.0 * n
    big_lam = RidgeN2M(lam=1e6).fit(n, m)
    assert abs(big_lam.gamma) < 0.1
    small_lam = RidgeN2M(lam=1e-6).fit(n, m)
    assert small_lam.gamma == pytest.approx(2.0, abs=1e-3)


def test_mean_estimator_ignores_n():
    n = np.array([1.0, 100.0])
    m = np.array([10.0, 20.0])
    r = MeanN2M().fit(n, m)
    pred = np.asarray(r.predict(np.array([5.0, 500.0])))
    assert pred[0] == pred[1] == pytest.approx(15.0)


def test_bucket_estimator_captures_nonlinearity():
    rng = np.random.default_rng(0)
    n = rng.uniform(1, 100, 5000)
    m = 0.5 * n + 0.004 * n**2  # mildly super-linear
    b = BucketN2M(n_buckets=25).fit(n, m)
    lin = LinearN2M().fit(n, m)
    grid = np.linspace(5, 95, 50)
    truth = 0.5 * grid + 0.004 * grid**2
    err_b = np.abs(np.asarray(b.predict(grid)) - truth).mean()
    err_l = np.abs(np.asarray(lin.predict(grid)) - truth).mean()
    assert err_b < err_l


def test_bucket_quantile_is_monotone_in_quantile():
    rng = np.random.default_rng(1)
    n = rng.uniform(1, 50, 2000)
    m = n + rng.normal(0, 3, 2000)
    lo = BucketN2M(n_buckets=10, quantile=0.25).fit(n, m)
    hi = BucketN2M(n_buckets=10, quantile=0.9).fit(n, m)
    grid = np.linspace(5, 45, 20)
    assert np.all(np.asarray(hi.predict(grid)) >= np.asarray(lo.predict(grid)) - 1e-6)


@settings(max_examples=25, deadline=None)
@given(
    gamma=st.floats(0.2, 2.0),
    delta=st.floats(-5.0, 5.0),
    scale=st.floats(0.5, 4.0),
)
def test_property_linear_fit_equivariance(gamma, delta, scale):
    """Scaling M scales gamma/delta identically (fit is linear in targets)."""
    n = np.linspace(1, 80, 200)
    m = gamma * n + delta
    base = LinearN2M().fit(n, m)
    scaled = LinearN2M().fit(n, scale * m)
    assert scaled.gamma == pytest.approx(scale * base.gamma, rel=1e-3, abs=1e-4)
    assert scaled.delta == pytest.approx(scale * base.delta, rel=1e-3, abs=1e-3)



# ------------------------------------- host path == jnp path, bit for bit ---
INPUTS = {
    "py_int": lambda r: int(r.integers(1, 300)),
    "py_float": lambda r: float(r.uniform(0.0, 300.0)),
    "np0d_int": lambda r: np.asarray(r.integers(1, 300)),
    "np0d_float": lambda r: np.asarray(r.uniform(0.0, 300.0)),
    "np1d_int": lambda r: r.integers(1, 300, size=7),
    "np1d_float": lambda r: r.uniform(0.0, 300.0, size=7),
}
KINDS = ["linear", "ridge", "mean", "bucket", "plane"]


def _predictor(kind, r):
    """``kind``'s ``predict`` with random coefficients, and its arity."""
    if kind == "plane":
        coef = r.uniform(-1e-2, 1e-1, size=3).tolist()
        return LinearLatencyModel(*coef).predict, 2
    if kind == "mean":
        return MeanN2M(float(r.uniform(1.0, 200.0))).predict, 1
    if kind == "bucket":
        n = r.uniform(20.0, 250.0, 400)       # inputs below/above fall back
        m = r.uniform(0.3, 2.0) * n + r.normal(0.0, 3.0, n.size)
        return BucketN2M(n_buckets=8).fit(n, m).predict, 1
    cls = {"linear": LinearN2M, "ridge": RidgeN2M}[kind]
    return cls(gamma=float(r.uniform(-2.0, 3.0)),
               delta=float(r.uniform(-20.0, 20.0))).predict, 1


@pytest.mark.parametrize("kind,inp,x64", [
    *[(k, i, False) for k in KINDS for i in INPUTS],
    ("linear", "py_float", True),
    ("plane", "np1d_float", True),
])
def test_host_predict_is_bit_identical_to_jnp(kind, inp, x64):
    """A host input is evaluated in NumPy and returns a NumPy value with
    the bits the ``jnp`` path (``jnp.asarray`` input) returns; the ``jnp``
    input still returns a ``jax.Array``."""
    r = np.random.default_rng([KINDS.index(kind), list(INPUTS).index(inp)])
    with jax.enable_x64(x64):
        for _ in range(40):
            predict, arity = _predictor(kind, r)
            args = [INPUTS[inp](r) for _ in range(arity)]
            host = predict(*args)
            dev = predict(*map(jnp.asarray, args))
            assert isinstance(host, (np.ndarray, np.generic))
            assert isinstance(dev, jax.Array)
            dev = np.asarray(dev)
            assert host.dtype == dev.dtype and host.shape == dev.shape
            assert np.asarray(host).tobytes() == dev.tobytes()
