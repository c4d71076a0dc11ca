"""Host time per tick: the span around each ``engine.poll()`` less the engine's ``tick_s``.

``tick_s`` is the engine's host clock around the tick's dispatch and its
blocking readback; what is left is scheduling, packing and bookkeeping.
Summed over the polls of the traced part of the window, divided by its ticks.
"""


def read(run):
    polls = run.data.get("polls")
    if polls is None or polls[:, 3].sum() == 0:
        return None
    host = (polls[:, 1] - polls[:, 0]) - polls[:, 2]
    return float(host.sum() / polls[:, 3].sum()) * 1e3
