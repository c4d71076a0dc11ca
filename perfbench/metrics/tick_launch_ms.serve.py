"""Mean host time to launch a tick's lane-window program (``neura.serve.launch`` span).

Argument conversion, the host-to-device copies and the enqueue; the device
work itself runs after it.
"""

from perfharness import spans


def read(run):
    return spans.mean_ms(run.trace, "neura.serve.launch")
