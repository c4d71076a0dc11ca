"""Device time per population program call in ops whose op metadata carries
``neura.core.recurrent``: the ATA-T recurrent product of every scan step, with whatever
XLA fused under it. Union of those ops' intervals inside each call, mean over calls and chips.
"""

from perfharness import opmeta

PROGRAMS = ("_population_fwd", "_population_sharded_jit")


def read(run):
    return opmeta.scoped_ms(run.trace, "neura.core.recurrent", PROGRAMS)
