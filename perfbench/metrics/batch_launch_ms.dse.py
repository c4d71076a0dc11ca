"""Mean host time to launch one data batch's population program (``neura.dse.launch`` span).

The batch's copy to the device, on a mesh its padding and placement, and the
program's enqueue.
"""

from perfharness import spans


def read(run):
    return spans.mean_ms(run.trace, "neura.dse.launch")
