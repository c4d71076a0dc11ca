"""Useful synaptic operations per second over the chips' int8 peak, in percent.

The operations are counted from the spike traffic (``perfharness.opcount``)
of the work completed in the window; the peak is the published int8 figure
of the chip times the chips used.
"""


def read(run):
    ops = run.data.get("synaptic_ops")
    if not ops:
        return None
    return 100.0 * ops / run.window_s / (run.chips * run.peaks["int8_ops_per_s"])
