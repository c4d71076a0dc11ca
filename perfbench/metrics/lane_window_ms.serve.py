"""Device time per execution of the lane-window program, from the profiler trace."""

from perfharness import trace

PROGRAMS = ("_lane_window_packed",)


def read(run):
    return trace.program_ms(run.trace, PROGRAMS) if run.trace is not None else None
