"""Shared set-up for the fault tests: a cell driven on the CPU at a size a test can hold."""

import json

import jax

from perfharness import ROOT, harness

SEED = 2**31 + 11
PEAKS = {"int8_ops_per_s": 393e12}  # the run's numbers are not read here, only `correct`


def small_cell(workload: str):
    bench = harness.benchmark()
    w = harness.cell(bench, workload)
    entry = next(c for c in bench["configs"] if c["name"] == w["config"])
    config = json.loads((ROOT / entry["file"]).read_text())
    mix = harness.load_json("traffic", w["traffic"])
    if mix["runner"] == "serve_open":
        # windows longer than the 32-step chunk cap, and load enough to fill the lanes
        config["n_steps"] = 40
        mix["arrivals"]["rate_per_s"] = 2000.0
    else:
        config["n_steps"] = 8
        mix.update(samples=64, batch_size=32)
        mix["grid"] = {"w_bits": [4, 8], "w_rec_bits": [8], "leak_bits": [3, 8]}
    return config, mix


def run(workload: str, seconds: float = 1.0, devices=None) -> dict:
    """One run of the cell without the chip check; returns its result line."""
    config, mix = small_cell(workload)
    return harness.run_cell(
        workload, SEED, seconds, False, 0.0,
        devices=devices or jax.devices(), peaks=PEAKS, config=config, traffic=mix,
    )  # fmt: skip
