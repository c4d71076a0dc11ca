"""The one module that reaches into the program: its network type and entry points.

The runners hand the program a ``NetworkConfig`` built from a configuration
file and the benchmark's own integer weights, and call its public entry
points (``SNNServeEngine``, ``eval_int_population``).
"""

from __future__ import annotations

import sys

from perfharness import ROOT

if str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))

from repro.core.network import NetworkConfig  # noqa: E402
from repro.core.snn_layer import (  # noqa: E402
    IntLayerParams,
    LayerConfig,
    NeuronModel,
    ResetMode,
    Topology,
)

_LAYER_KEYS = (
    "n_in", "n_out", "neuron", "topology", "reset", "w_bits", "w_rec_bits",
    "u_bits", "i_bits", "leak_bits", "beta", "alpha", "threshold",
)  # fmt: skip


def network(config: dict, n_steps: int | None = None) -> NetworkConfig:
    """The program's ``NetworkConfig`` for a configuration file."""
    enums = {"neuron": NeuronModel, "topology": Topology, "reset": ResetMode}
    layers = tuple(
        LayerConfig(**{k: enums[k](c[k]) if k in enums else c[k] for k in _LAYER_KEYS})
        for c in config["layers"]
    )
    return NetworkConfig(layers=layers, n_steps=n_steps or config["n_steps"], name=config["name"])


def qparams(weights: list[dict]) -> list[IntLayerParams]:
    """The program's parameter type over the benchmark's integer weights."""
    return [IntLayerParams(w_ff=w["w_ff"], w_rec=w["w_rec"], theta_q=w["theta_q"]) for w in weights]
