"""Determinism + interface contracts for the synthetic dataset generators.

The three generators are the reproduction's stand-ins for MNIST / SHD /
DVS-Gesture; everything downstream (training, DSE scoring, the paper
scripts and the benchmark) assumes they are bit-reproducible from their
seed and expose the documented raster interface.  These tests pin that
contract, plus the ``batches`` iteration rules (full coverage including the
ragged tail batch -- silently dropping ``len % batch_size`` samples per
epoch was a real bug).
"""

import numpy as np
import pytest

from repro.data.snn_datasets import SpikeDataset, dvs_like, mnist_like, rate_encode, shd_like

GENERATORS = [
    (mnist_like, dict(n=96, T=8, seed=3), 10, 256),
    (shd_like, dict(n=96, T=8, seed=3), 20, 140),
    (dvs_like, dict(n=96, T=8, seed=3), 11, 256),
]


@pytest.mark.parametrize("gen,kwargs,n_classes,channels", GENERATORS)
def test_same_seed_same_rasters(gen, kwargs, n_classes, channels):
    a = gen(**kwargs)
    b = gen(**kwargs)
    assert np.array_equal(a.spikes, b.spikes)
    assert np.array_equal(a.labels, b.labels)
    c = gen(**{**kwargs, "seed": kwargs["seed"] + 1})
    assert not np.array_equal(a.spikes, c.spikes)


@pytest.mark.parametrize("gen,kwargs,n_classes,channels", GENERATORS)
def test_interface_shapes_and_ranges(gen, kwargs, n_classes, channels):
    ds = gen(**kwargs)
    n, T = kwargs["n"], kwargs["T"]
    assert ds.spikes.shape == (n, T, channels)
    assert ds.spikes.dtype == np.uint8
    assert set(np.unique(ds.spikes)) <= {0, 1}
    assert ds.labels.shape == (n,)
    assert ds.n_classes == n_classes
    assert ds.labels.min() >= 0 and ds.labels.max() < n_classes
    # every class represented (decodability floor for the accuracy benches)
    assert len(np.unique(ds.labels)) == n_classes


@pytest.mark.parametrize("gen,kwargs,n_classes,channels", GENERATORS)
def test_split_partitions_without_overlap(gen, kwargs, n_classes, channels):
    ds = gen(**kwargs)
    train, test = ds.split(0.75)
    assert len(train.labels) + len(test.labels) == len(ds.labels)
    assert np.array_equal(
        np.concatenate([train.spikes, test.spikes]), ds.spikes
    )
    assert np.array_equal(np.concatenate([train.labels, test.labels]), ds.labels)
    assert train.n_classes == test.n_classes == ds.n_classes


def _toy_dataset(n: int) -> SpikeDataset:
    spikes = np.arange(n * 2 * 3, dtype=np.uint8).reshape(n, 2, 3) % 2
    return SpikeDataset(spikes, np.arange(n, dtype=np.int32), n_classes=n, name="toy")


def test_batches_yields_ragged_tail():
    ds = _toy_dataset(10)
    got = list(ds.batches(4))
    assert [len(labels) for _, labels in got] == [4, 4, 2]
    seen = np.concatenate([labels for _, labels in got])
    assert sorted(seen.tolist()) == list(range(10))  # every sample, exactly once
    for spikes, labels in got:
        assert spikes.shape == (2, len(labels), 3)  # time-major [T, B, C]


def test_batches_shuffled_epoch_still_covers_every_sample():
    ds = _toy_dataset(11)
    rng = np.random.default_rng(0)
    seen = np.concatenate([labels for _, labels in ds.batches(4, rng)])
    assert sorted(seen.tolist()) == list(range(11))


def test_batches_batch_larger_than_dataset_and_empty():
    ds = _toy_dataset(3)
    got = list(ds.batches(64))
    assert len(got) == 1 and len(got[0][1]) == 3
    empty = SpikeDataset(
        np.zeros((0, 2, 3), np.uint8), np.zeros((0,), np.int32), 1, "empty"
    )
    assert list(empty.batches(4)) == []


def test_batches_pairs_spikes_with_their_labels_under_shuffle():
    n = 9
    # encode the sample id in the raster so shuffling misalignment is visible
    spikes = np.zeros((n, 1, 16), np.uint8)
    for i in range(n):
        spikes[i, 0, i] = 1
    ds = SpikeDataset(spikes, np.arange(n, dtype=np.int32), n, "aligned")
    for batch, labels in ds.batches(4, np.random.default_rng(1)):
        for j, lab in enumerate(labels):
            assert batch[0, j, lab] == 1


def test_rate_encode_probability_bounds():
    rng = np.random.default_rng(0)
    intensity = np.linspace(0.0, 1.0, 64)
    raster = rate_encode(intensity, T=400, rng=rng, max_rate=0.5)
    assert raster.shape == (400, 64)
    assert raster[:, 0].sum() == 0  # zero intensity never spikes
    rates = raster.mean(axis=0)
    assert abs(rates[-1] - 0.5) < 0.1  # full intensity ~ max_rate
