"""Requests completed inside the window, over the window's seconds (host clock)."""


def read(run):
    if "in_window" not in run.data:
        return None
    return float(run.data["in_window"].sum()) / run.window_s
