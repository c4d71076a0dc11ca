"""Per tick, the end of the ``neura.serve.readback`` span less the end of its lane window on
the device, clipped at 0; mean. The transfer back and the host's delay, not the wait."""

from perfharness import spans

PROGRAMS = ("_lane_window_packed",)


def read(run):
    return spans.readback_tail_ms(run.trace, "neura.serve.launch", "neura.serve.readback", PROGRAMS)
