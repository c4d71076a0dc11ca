"""Per batch, the end of the ``neura.dse.readback`` span less the latest end, over the chips
used, of its population program on the device, clipped at 0; mean."""

from perfharness import spans

PROGRAMS = ("_population_fwd", "_population_sharded_jit")


def read(run):
    return spans.readback_tail_ms(run.trace, "neura.dse.launch", "neura.dse.readback", PROGRAMS)
