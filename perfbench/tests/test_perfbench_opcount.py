"""Synaptic-op and byte counts against hand counts, and the table of peaks."""

import json

import pytest

from perfharness import BENCH_DIR, opcount
from perfharness.peaks import PEAKS, peaks

INPUT = [5, 0, 2]  # events into core 0 at steps 0..2 (7 in all)
EMITTED = [[3, 1], [0, 0], [4, 2]]  # spikes of core 0 and core 1 at each step


def _layers(name):
    return json.loads((BENCH_DIR / "configs" / f"{name}.json").read_text())["layers"]


@pytest.mark.parametrize(
    "name, ops, nbytes",
    [
        # 2*7*128 into the hidden core + 2*(3+0+4)*10 into the output core
        ("mnist-lif-ff-256-128-10", 1792 + 140, 768 + 3584 + 9216 + 280 + 720),
        # 2*7*200 + ATA-F self-weights for the 3 spikes of steps 0..1, + 2*7*11
        ("dvs-lif-ataf-256-200-11", 2800 + 6 + 154, 768 + 12 + 5600 + 14400 + 308 + 792),
    ],
)
def test_counts_match_hand_counts(name, ops, nbytes):
    layers = _layers(name)
    assert opcount.synaptic_ops(layers, INPUT, EMITTED) == ops
    # raster 3x256 bytes; weight rows x n_out x 4 B; 3 int32 registers read and written
    assert opcount.bytes_moved(layers, 1, 3, INPUT, EMITTED) == nbytes


def test_dense_traffic_counts_every_synapse():
    layers = _layers("mnist-lif-ff-256-128-10")
    T = 4
    ops = opcount.synaptic_ops(layers, [256] * T, [[128, 10]] * T)
    assert ops == 2 * T * (256 * 128 + 128 * 10)


def test_ata_t_feeds_back_a_row_per_spike():
    layers = [{"n_in": 4, "n_out": 3, "topology": "ata_t"}]
    assert opcount.synaptic_ops(layers, [1, 1], [[2], [1]]) == 2 * 2 * 3 + 2 * 2 * 3


def test_peak_table_names_its_source():
    v5e = peaks("TPU v5 lite")
    assert v5e["int8_ops_per_s"] == 393e12
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert all(p["source"] for p in PEAKS.values())


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks("TPU v9 imaginary")
