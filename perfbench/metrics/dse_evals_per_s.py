"""Evaluations per second: candidates x samples x passes over the window (host clock)."""


def read(run):
    if "evals" not in run.data:
        return None
    return run.data["evals"] / run.data["elapsed_s"]
