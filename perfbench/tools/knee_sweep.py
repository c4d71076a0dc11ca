"""Find a serving cell's knee on the chip: the highest offered rate it keeps up with.

    python3 perfbench/tools/knee_sweep.py <workload> [<workload> ...] [--seconds 5] [--start 1000]

Each step is one whole run of the cell through ``harness.run_cell``: the
engine, traffic, window and check that the benchmark times, with only the
traffic file's ``rate_per_s`` replaced. The rate doubles from ``--start``
until a run no longer keeps up (or halves until one does), then bisects
between the highest rate that kept up and the lowest that did not. A run
keeps up when it is correct and completed inside its window at least 99%
of the requests it offered: above the knee the queue grows through the
window, and what is due near its close finishes after it.

Each line gives the offered rate, ``samples_per_s``, ``p95_latency_ms`` and
the requests never answered; the last line per cell names the knee and
four fifths of it, the rate the cell's traffic file then fixes.
"""

import argparse
import copy
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

from perfharness import harness  # noqa: E402

KEEP_UP = 0.99


def _step(workload: str, rate: float, seconds: float, seed: int, devices) -> bool:
    name = harness.cell(harness.benchmark(), workload)["traffic"]
    mix = copy.deepcopy(harness.load_json("traffic", name))
    mix["arrivals"]["rate_per_s"] = rate
    t_start = time.perf_counter()
    res = harness.run_cell(workload, seed, seconds, False, t_start, devices, traffic=mix)
    done = res["metrics"].get("samples_per_s", {}).get("value", 0.0)
    p95 = res["metrics"].get("p95_latency_ms", {}).get("value")
    kept = bool(res["correct"]) and done >= KEEP_UP * rate
    print(
        f"STEP {workload} offered={rate:.1f}/s samples_per_s={done:.1f} p95_latency_ms={p95}"
        f" missing={res['checks']['missing_answers']['value']} correct={res['correct']}"
        f" kept_up={kept} setup_s={res['metrics']['setup_s']['value']:.2f}",
        flush=True,
    )
    return kept


def sweep(workload: str, seconds: float, seed: int, start: float, bisect: int, devices) -> float:
    steps = iter(range(seed, seed + 1000))
    lo = hi = None
    rate = start
    while lo is None or hi is None:
        if _step(workload, rate, seconds, next(steps), devices):
            lo = rate
            if hi is None:
                rate *= 2
        else:
            hi = rate
            if lo is None:
                rate /= 2
        if lo is None and rate < 1:
            raise RuntimeError(f"{workload} keeps up with no rate down to 1/s")
    for _ in range(bisect):
        mid = round((lo + hi) / 2)
        if _step(workload, mid, seconds, next(steps), devices):
            lo = mid
        else:
            hi = mid
    print(f"KNEE {workload} {lo} (first miss {hi}); 0.8 x knee = {0.8 * lo:.0f}/s", flush=True)
    return lo


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("workloads", nargs="+")
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--seed", type=int, default=2**31 + 101)
    ap.add_argument("--start", type=float, default=1000.0)
    ap.add_argument("--bisect", type=int, default=3)
    args = ap.parse_args()
    harness.enable_cache()
    devices = harness.check_device(1)
    for wl in args.workloads:
        sweep(wl, args.seconds, args.seed, args.start, args.bisect, devices)
    return 0


if __name__ == "__main__":
    sys.exit(main())
