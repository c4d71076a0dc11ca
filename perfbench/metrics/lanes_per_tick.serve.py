"""Mean lanes a tick advances: the ``active`` argument of the ``neura.serve.tick`` span."""

from perfharness import spans


def read(run):
    return spans.arg_mean(run.trace, "neura.serve.tick", "active")
