"""Flex-plorer end-to-end: train -> anneal -> QAT-refine -> deployment package.

    PYTHONPATH=src python examples/flexplorer_dse.py

The paper's full flow (Fig. 10) plus this repo's train-in-the-loop second
phase: the Learning stage trains an ATA-F LIF network on the DVS stand-in;
the Explorer anneals (ff bits, rec bits, leak precision) against the
weighted LUT/FF/BRAM + bit-exact-accuracy cost; ``RefineSpec(top_k=2)`` then
QAT-fine-tunes the two best finalists at their own precisions (epoch 0 is
post-training quantization, so refinement never loses accuracy on the
scoring set); the "RTL Configurator" stage emits the deployment package our
framework's runtime consumes: chosen design-time parameters + quantized
weight tables + encoded dataset sample, under ``runs/flexplorer_pkg/`` --
from the best *refined* candidate when one dominates the annealer's pick.
"""

import json
import pathlib

import numpy as np

from repro.core import hw_model
from repro.core.flexplorer import cost as cost_lib
from repro.core.flexplorer import strategies as strategies_lib
from repro.core.flexplorer.explorer import RefineSpec, SearchSpec, SNNSearchSpace, explore_snn
from repro.core.network import NetworkConfig
from repro.core.snn_layer import LayerConfig, NeuronModel, Topology
from repro.data.snn_datasets import dvs_like
from repro.snn.train import train_snn


def _net_resources(net):
    res = hw_model.network_resources(net)
    return {
        "lut": float(res.lut),
        "ff": float(res.ff),
        "bram": float(res.bram),
        "logic_cells": float(res.logic_cells),
    }


def main():
    ds = dvs_like(n=1408, T=20, seed=2)
    train, test = ds.split()
    net = NetworkConfig(
        layers=(
            LayerConfig(n_in=256, n_out=128, neuron=NeuronModel.LIF, topology=Topology.ATA_F, u_bits=16),
            LayerConfig(n_in=128, n_out=11, neuron=NeuronModel.LIF, topology=Topology.FF, u_bits=16),
        ),
        n_steps=20,
        name="dvs-ataf",
    )
    print("Learning stage: training ATA-F LIF on DVS stand-in...")
    res = train_snn(net, train, epochs=6, batch_size=128, lr=2e-3, log_every=2)

    print("Explorer stage: simulated annealing over (ff, rec, leak) precision...")
    result = explore_snn(
        net,
        res.params,
        test,
        search=SearchSpec(
            space=SNNSearchSpace(ff_bits=(3, 4, 6, 8), rec_bits=(3, 4, 6, 8), leak_bits=(3, 8)),
            weights=cost_lib.CostWeights(c_hw=0.5, c_acc=0.5, c_lut=0.33, c_ff=0.33, c_bram=0.34),
            config=strategies_lib.AnnealConfig(t_start=1.0, t_min=0.05, alpha=0.6, eval_divisor=3, seed=0),
        ),
        refine=RefineSpec(top_k=2, train_ds=train, epochs=3, lr=1.5e-3),
    )
    report = result.report()
    print("chosen configuration:", json.dumps(report["chosen"], indent=2, default=float))
    print("explored (PTQ) Pareto front:", json.dumps(result.explored_front(), default=float))
    print("refined Pareto front:      ", json.dumps(result.refined_front(), default=float))
    for r in result.refined:
        print(f"  refined {r.breakdown}: {r.base_accuracy:.4f} -> {r.accuracy:.4f}")

    # deploy the best refined candidate when one beats the annealer's pick
    # at no higher total cost; the PTQ incumbent otherwise
    best_refined = min(result.refined, key=lambda r: r.total_cost, default=None)
    if best_refined is not None and best_refined.total_cost <= result.search.best_cost:
        deploy_net, deploy_qparams = best_refined.net, best_refined.qparams
        print(f"deploying refined candidate {best_refined.breakdown}")
    else:
        deploy_net, deploy_qparams = result.best_net, result.best_qparams
        print("deploying the unrefined annealer incumbent")

    out = pathlib.Path("runs/flexplorer_pkg")
    out.mkdir(parents=True, exist_ok=True)
    # deployment package: design-time params, quantized weights, encoded data
    (out / "design.json").write_text(json.dumps({
        "layers": [
            {"n_in": lc.n_in, "n_out": lc.n_out, "neuron": lc.neuron.value,
             "topology": lc.topology.value, "w_bits": lc.w_bits,
             "w_rec_bits": lc.w_rec_bits, "leak_bits": lc.leak_bits,
             "decay_register": lc.beta_code().decay_rate_register}
            for lc in deploy_net.layers
        ],
        # resources of the *deployed* net (refined candidates can differ
        # from the annealer incumbent the report above describes)
        "resources": _net_resources(deploy_net),
    }, indent=2))
    np.savez(out / "weights_q.npz", **{
        f"layer{i}_wff": np.asarray(q.w_ff) for i, q in enumerate(deploy_qparams)
    })
    np.save(out / "encoded_sample.npy", test.spikes[:16])
    print(f"deployment package written to {out}/ (design.json, weights_q.npz, encoded_sample.npy)")


if __name__ == "__main__":
    main()
