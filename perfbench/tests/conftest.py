"""Put the benchmark's own code and the program on the import path for its tests."""

import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parent.parent
for path in (BENCH, BENCH / "references", BENCH.parent / "src"):
    if str(path) not in sys.path:
        sys.path.insert(0, str(path))
