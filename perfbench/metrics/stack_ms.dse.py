"""Mean host time to stack the population before a pass (``neura.dse.stack`` span, one per pass)."""

from perfharness import spans


def read(run):
    return spans.mean_ms(run.trace, "neura.dse.stack")
