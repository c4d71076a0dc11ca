"""Paper Fig. 11: DSE cost landscape for an ATA-F network on DVS.

User spec from the figure caption: LIF, ATA-F, layers [256, 200->(128), 11],
ff bits {4, 8, 12, 16}, rec bits {4, 8, 12, 16}, leak precision {3, 8};
weights HW=0.5 / ACC=0.5, LUT=0.33 / BRAM=0.34 / FF=0.33.
(Hidden width reduced 200 -> 128 to respect the 256-neuron/core cap with
margin at smoke scale; grid kept identical.)

Emits the full candidate list sorted by total cost (the figure's x-axis) to
``experiments/fig11_dse.csv`` plus the annealer's chosen point.
"""

from __future__ import annotations

import csv
import pathlib
import time

from repro.core.flexplorer import cost as cost_lib
from repro.core.flexplorer import strategies as strategies_lib
from repro.core.flexplorer.explorer import EvalSpec, SearchSpec, SNNSearchSpace, explore_snn
from repro.core.network import NetworkConfig
from repro.core.snn_layer import LayerConfig, NeuronModel, Topology
from repro.data.snn_datasets import dvs_like
from repro.snn.train import train_snn

OUT = pathlib.Path(__file__).resolve().parent.parent / "experiments" / "fig11_dse.csv"


def run(epochs: int = 5, T: int = 20, backend: str = "reference", population: int = 0) -> list[tuple[str, float, str]]:
    t0 = time.time()
    ds = dvs_like(n=1200, T=T, seed=2)
    train, test = ds.split()
    net = NetworkConfig(
        layers=(
            LayerConfig(n_in=256, n_out=128, neuron=NeuronModel.LIF, topology=Topology.ATA_F, w_bits=8, u_bits=16),
            LayerConfig(n_in=128, n_out=11, neuron=NeuronModel.LIF, topology=Topology.FF, w_bits=8, u_bits=16),
        ),
        n_steps=T,
        name="fig11-ataf-dvs",
    )
    res_train = train_snn(net, train, epochs=epochs, batch_size=128, lr=2e-3)
    weights = cost_lib.CostWeights(c_hw=0.5, c_acc=0.5, c_lut=0.33, c_ff=0.33, c_bram=0.34)
    result = explore_snn(
        net,
        res_train.params,
        test,
        search=SearchSpec(
            space=SNNSearchSpace(ff_bits=(4, 8, 12, 16), rec_bits=(4, 8, 12, 16), leak_bits=(3, 8)),
            weights=weights,
            config=strategies_lib.AnnealConfig(t_start=1.0, t_min=0.02, alpha=0.6, eval_divisor=3, seed=0),
            population=population,
        ),
        evaluate=EvalSpec(backend=backend),
    )
    # figure data: every evaluated candidate, sorted by total cost
    rows = sorted(result.search.trace, key=lambda r: r["total"])
    OUT.parent.mkdir(parents=True, exist_ok=True)
    with OUT.open("w", newline="") as f:
        w = csv.writer(f)
        w.writerow(["ff_bits", "rec_bits", "leak_bits", "total_cost", "hw_cost", "acc_cost", "accuracy"])
        for r in rows:
            w.writerow([r["cfg"].get("ff_bits"), r["cfg"].get("rec_bits"), r["cfg"].get("leak_bits"),
                        f"{r['total']:.5f}", f"{r['hw']:.5f}", f"{r['acc_cost']:.5f}", f"{r['accuracy']:.4f}"])
    chosen = result.search.best_breakdown
    us = (time.time() - t0) * 1e6
    derived = (
        f"chosen_ff={chosen['ff_bits']};rec={chosen.get('rec_bits')};leak={chosen['leak_bits']}"
        f";acc={chosen['accuracy']:.4f};evals={result.search.evaluations}"
        f";paper_choice=ff8_rec8_leak8;csv={OUT.name}"
    )
    return [("fig11/dse-ataf-dvs", us, derived)]
