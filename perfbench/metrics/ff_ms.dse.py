"""Device time per population program call in ops whose op metadata carries ``neura.core.ff``.

The union of those ops' intervals inside each call, mean over calls and the
chips used: every layer's feed-forward product (``jax.named_scope`` in the
program), with whatever XLA fused under it.
"""

from perfharness import opmeta

PROGRAMS = ("_population_fwd", "_population_sharded_jit")


def read(run):
    return opmeta.scoped_ms(run.trace, "neura.core.ff", PROGRAMS)
