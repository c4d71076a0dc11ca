"""Device time per population program call (one data batch), from the profiler trace.

On one chip the program is ``_population_fwd``; over a mesh it is the
sharded population program, and the time is the mean over the chips.
"""

from perfharness import trace

PROGRAMS = ("_population_fwd", "_population_sharded_jit")


def read(run):
    return trace.program_ms(run.trace, PROGRAMS) if run.trace is not None else None
