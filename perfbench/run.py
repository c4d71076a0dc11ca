"""Run one benchmark cell on the chip and print its result as the last line.

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine that holds the chips the cell
asks for (see BENCHMARK.json). Without a TPU, or with fewer chips than the
cell needs, it exits non-zero and prints no result.
"""

import time

T_START = time.perf_counter()  # set-up is timed from here: imports count

import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # the TPU runtime would log under /tmp
sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

from perfharness.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(t_start=T_START))
