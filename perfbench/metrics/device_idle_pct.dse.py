"""Share of the traced window in which no op ran on the device, mean over the chips used."""

from perfharness import trace


def read(run):
    return trace.idle_pct(run.trace) if run.trace is not None else None
