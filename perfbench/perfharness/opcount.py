"""Useful synaptic operations and bytes of a core stack, from shapes and event counts.

The work is what the paper's event-driven core does: every spike that
reaches a core adds one weight row, ``n_out`` multiply-accumulates, counted
as 2 operations each. A recurrent core also integrates its own spikes of
the previous step: one self-weight per spike for ATA-F, a row of ``n_out``
for ATA-T. A dense lowering that multiplies zeros does more arithmetic
than this; the count is the same whatever lowering runs, so no lowering
can read above the peak.
"""

from __future__ import annotations

import numpy as np


def synaptic_ops(layers: list[dict], input_events, layer_events) -> int:
    """Operations for spike traffic summed over samples.

    ``input_events`` int ``[T]``: events into core 0 at each step;
    ``layer_events`` int ``[T, n_layers]``: spikes each core emitted at each
    step (core ``l``'s are core ``l+1``'s input at the same step and its own
    recurrent input at the next).
    """
    input_events = np.asarray(input_events, np.int64)
    layer_events = np.asarray(layer_events, np.int64)
    ops = 0
    for l, c in enumerate(layers):
        into = input_events if l == 0 else layer_events[:, l - 1]
        ops += 2 * int(into.sum()) * c["n_out"]
        fed_back = int(layer_events[:-1, l].sum())  # the last step's spikes feed no step
        if c["topology"] == "ata_f":
            ops += 2 * fed_back
        elif c["topology"] == "ata_t":
            ops += 2 * fed_back * c["n_out"]
    return ops


def bytes_moved(layers: list[dict], n_samples: int, n_steps: int, input_events, layer_events,
                weight_bytes: int = 4) -> int:  # fmt: skip
    """Bytes the event-driven traversal touches: weight rows, registers, raster.

    Each event reads one weight row of ``n_out`` weights at ``weight_bytes``
    each (the int32 the program stores); each step of each sample reads and
    writes three int32 registers per neuron (membrane, synaptic current,
    previous spike); the input raster is one byte per channel and step.
    """
    input_events = np.asarray(input_events, np.int64)
    layer_events = np.asarray(layer_events, np.int64)
    total = n_samples * n_steps * layers[0]["n_in"]
    for l, c in enumerate(layers):
        into = input_events if l == 0 else layer_events[:, l - 1]
        rows = int(into.sum())
        fed_back = int(layer_events[:-1, l].sum())
        if c["topology"] == "ata_f":
            total += fed_back * weight_bytes
        elif c["topology"] == "ata_t":
            rows += fed_back
        total += rows * c["n_out"] * weight_bytes
        total += n_samples * n_steps * c["n_out"] * 3 * 4 * 2
    return total
