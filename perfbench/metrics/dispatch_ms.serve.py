"""Mean host time of the engine's dispatch round, from its own ``neura.serve.dispatch`` span."""

from perfharness import spans


def read(run):
    return spans.mean_ms(run.trace, "neura.serve.dispatch")
