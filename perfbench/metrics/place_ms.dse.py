"""Mean host time to place a sweep's rasters on the device (``neura.dse.place`` span, one per pass).

One transfer of the whole held-out set in its stored layout, on a mesh to
every chip, until it has arrived.
"""

from perfharness import spans


def read(run):
    return spans.mean_ms(run.trace, "neura.dse.place")
