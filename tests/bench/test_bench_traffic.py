"""The one traffic generator: every seed gets the same work in another
order, and a mix's parameters shape it as its data file says."""

import numpy as np
import pytest

from bench import traffic

SEEDS = [2 ** 31 + 5, 7, 2 ** 40 + 3]


def _mix(kind="poisson", **prompt):
    arrivals = {"kind": kind, "rate_hz": 40.0}
    if kind == "bursty":
        arrivals.update(peak_factor=3.0, period_s=5.0)
    return {"arrivals": arrivals, "episode_s": 5.0,
            "prompt": {"median": 18.174, "sigma": 0.6, "min": 1,
                       "max": 128, "grid": None, **prompt},
            "max_new": 128}


@pytest.mark.parametrize("kind", ["poisson", "bursty"])
def test_seeds_share_the_work_in_another_order(kind):
    mix = _mix(kind)
    n = traffic.per_episode(mix)
    eps = [traffic.episode(mix, s, 1, 300) for s in SEEDS]
    lens = [sorted(len(p) for p in prompts) for _, prompts in eps]
    assert all(l == lens[0] for l in lens)
    orders = [[len(p) for p in prompts] for _, prompts in eps]
    assert orders[0] != orders[1]
    for t, prompts in eps:
        assert len(t) == len(prompts) == n
        assert np.all(np.diff(t) > 0)
        assert 5.0 < t[0] and t[-1] == pytest.approx(10.0)
        for p in prompts:
            assert p.min() >= traffic.FIRST_ID and p.max() < 300
    if kind == "poisson":
        gaps = [np.sort(np.diff(np.concatenate([[5.0], t]))) for t, _ in eps]
        for g in gaps[1:]:
            np.testing.assert_allclose(g, gaps[0], rtol=1e-6, atol=1e-9)
        assert np.mean(gaps[0]) == pytest.approx(1 / 40.0)


def test_same_seed_same_inputs():
    mix = _mix()
    a = traffic.episode(mix, SEEDS[0], 0, 300)
    b = traffic.episode(mix, SEEDS[0], 0, 300)
    np.testing.assert_array_equal(a[0], b[0])
    for x, y in zip(a[1], b[1]):
        np.testing.assert_array_equal(x, y)


def test_bursts_crowd_arrivals_at_the_peak():
    t, _ = traffic.episode(_mix("bursty"), SEEDS[0], 0, 300)
    # rate = trough (1 - cos(2 pi t / 5)) around the peak at 2.5 s
    peak = np.sum(np.abs(t - 2.5) < 1.25)
    assert peak > 0.7 * len(t)


def test_lengths_are_lognormal_quantiles_clipped_and_gridded():
    lens = traffic.prompt_lengths(_mix(), 1000)
    assert np.median(lens) in (18, 19)
    assert lens.min() >= 1 and lens.max() <= 128
    grid = traffic.prompt_lengths(_mix(grid=[16, 32, 64, 128]), 1000)
    assert set(np.unique(grid)) <= {16, 32, 64, 128}
    assert np.all(grid >= lens)
