"""Print the host spans of a traced run: count, mean and total per name, and how they add up.

    python3 perfbench/tools/span_table.py [path to .xplane.pb]

Without a path it reads the trace the last ``--trace 1`` run wrote under
``perfbench_out/trace``. Besides the table it prints two sums: the means of
the tick's four children against the mean tick, and the share of the
``bench.pass`` seconds that the population spans cover.
"""

import pathlib
import sys

import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

from perfharness import spans, trace  # noqa: E402

TICK_STEPS = ("neura.serve.pack", "neura.serve.launch", "neura.serve.readback",
              "neura.serve.complete")  # fmt: skip
PASS_PARTS = ("neura.dse.stack", "neura.dse.batch")
PASS_CHILDREN = ("neura.dse.launch", "neura.dse.readback")


def main(argv) -> int:
    path = pathlib.Path(argv[0]) if argv else spans.trace_file()
    if path is None:
        sys.exit("no trace: pass a path, or run a cell with --trace 1 first")
    t = trace.load(path)
    total = {}
    for name in sorted(set(t.host_names)):
        s = spans.spans(t, name)
        if len(s):
            d = (s[:, 1] - s[:, 0]) * 1e-9
            total[name] = float(d.sum())
            print(f"{name:48s} n={len(s):6d} mean_ms={1e3 * d.mean():.6f} total_s={d.sum():.6f}")
    if "neura.serve.tick" in total:
        parts = sum(spans.mean_ms(t, n) or 0.0 for n in TICK_STEPS)
        tick = spans.mean_ms(t, "neura.serve.tick")
        print(f"tick children mean sum {parts:.6f} ms of tick mean {tick:.6f} ms:"
              f" {100 * parts / tick:.2f}%")  # fmt: skip
    if "bench.pass" in total and "neura.dse.batch" in total:
        passes = total["bench.pass"]
        covered = sum(total.get(n, 0.0) for n in PASS_PARTS)
        kids = total["neura.dse.stack"] + sum(total.get(n, 0.0) for n in PASS_CHILDREN)
        print(f"of bench.pass: stack + batch {100 * covered / passes:.2f}%,"
              f" stack + launch + readback {100 * kids / passes:.2f}%")  # fmt: skip
    print(f"window_s {t.window_s:.6f}; devices {sorted(t.devices)}; "
          f"host events {len(t.host_names)}; mean over chips of device busy "
          f"{np.mean(list(trace.busy_s(t).values())) if t.devices else 0.0:.6f} s")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
