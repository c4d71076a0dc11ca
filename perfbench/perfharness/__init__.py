"""The benchmark's own code: traffic, weights, references, op counts, trace reduction.

Nothing here is imported by the program under test, and nothing here imports
the program except ``program`` and the two runners (``serve_open``, ``dse_sweep``), which call
its public entry points.
"""

import pathlib

BENCH_DIR = pathlib.Path(__file__).resolve().parent.parent
ROOT = BENCH_DIR.parent
