"""Traffic from the seed alone: determinism, fidelity to the generators it copies, arrivals."""

import numpy as np
import pytest

from perfharness import traffic
from repro.data.snn_datasets import dvs_like, mnist_like

SEED = 2**31 + 123  # seeds past 2**31 do not fit 32 signed bits

FAMILIES = [
    ({"family": "mnist_like", "max_rate": 0.35}, 25),
    ({"family": "dvs_like", "n_classes": 11, "class_seed": 125}, 70),
    ({"family": "bernoulli", "density": 0.03}, 25),
]


@pytest.mark.parametrize("spec, T", FAMILIES)
def test_same_seed_same_rasters(spec, T):
    a, la = traffic.rasters(spec, 48, T, 256, SEED)
    b, lb = traffic.rasters(spec, 48, T, 256, SEED)
    c, _ = traffic.rasters(spec, 48, T, 256, SEED + 1)
    assert a.shape == (48, T, 256) and a.dtype == np.uint8
    assert np.array_equal(a, b) and np.array_equal(la, lb)
    assert not np.array_equal(a, c)
    assert len({r.tobytes() for r in a}) == len(a)  # no two requests share a raster


@pytest.mark.parametrize(
    "spec, T, original, spatial",
    [
        ({"family": "mnist_like", "max_rate": 0.35}, 25, lambda n, T: mnist_like(n, T, 0), True),
        # drifting gratings fire evenly over the grid: no spatial profile to compare
        (
            {"family": "dvs_like", "n_classes": 11, "class_seed": 125},
            70,
            lambda n, T: dvs_like(n, T, 2),
            False,
        ),
    ],
)
def test_copy_keeps_the_original_density_and_shape(spec, T, original, spatial):
    n = 256
    ours, labels = traffic.rasters(spec, n, T, 256, SEED)
    theirs = original(n, T)
    assert ours.shape == theirs.spikes.shape and ours.dtype == theirs.spikes.dtype
    d_ours, d_theirs = ours.mean(), theirs.spikes.mean()
    assert abs(d_ours - d_theirs) < 0.05 * d_theirs
    # how density varies from sample to sample
    s_ours, s_theirs = ours.mean(axis=(1, 2)).std(), theirs.spikes.mean(axis=(1, 2)).std()
    assert abs(s_ours - s_theirs) < 0.25 * s_theirs
    if spatial:  # which channels fire, averaged over samples and steps
        prof = np.corrcoef(ours.mean(axis=(0, 1)), theirs.spikes.mean(axis=(0, 1)))[0, 1]
        assert prof > 0.95
    # the temporal profile: density per step
    assert np.allclose(ours.mean(axis=(0, 2)), theirs.spikes.mean(axis=(0, 2)), rtol=0.15)
    assert set(np.unique(labels)) <= set(range(theirs.n_classes))


def test_bernoulli_density():
    r, _ = traffic.rasters({"family": "bernoulli", "density": 0.03}, 64, 25, 256, SEED)
    assert abs(r.mean() - 0.03) < 0.003


def test_poisson_schedule_mean_rate_and_gaps():
    rate, seconds = 2500.0, 20.0
    a = traffic.poisson_arrivals(rate, seconds, SEED)
    assert len(a) == round(rate * seconds)  # every seed offers the same work
    assert len(a) / seconds == rate
    assert np.all(np.diff(a) >= 0) and a[0] >= 0 and a[-1] < seconds
    gaps = np.diff(a)
    assert abs(gaps.mean() * rate - 1) < 0.02
    assert abs(gaps.std() / gaps.mean() - 1) < 0.05  # exponential gaps
    b = traffic.poisson_arrivals(rate, seconds, SEED)
    assert np.array_equal(a, b)
    assert not np.array_equal(a, traffic.poisson_arrivals(rate, seconds, SEED + 1))


def test_poisson_rejects_a_zero_rate():
    with pytest.raises(ValueError):
        traffic.poisson_arrivals(0.0, 10.0, SEED)
