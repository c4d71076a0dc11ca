"""Read a cell's checked numbers over many seeds in one process, for the program or its control.

    python3 perfbench/tools/readings.py --workload <name> --seeds 1,2,3 --seconds 20 [--control]

Each seed is one whole run of the cell (traffic, window, reference, checks)
at the cell's own size and load. With ``--control`` the program is fed the
same weights held at int4 (``control_weights`` of the configuration's
reference): the lower precision a later change might be tempted by, which
the checks have to refuse. Prints one line per seed with every checked
number beside its limit. The limits in PERF.md were set from these lines.
"""

import argparse
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

from perfharness import harness  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args()
    harness.enable_cache()
    bench = harness.benchmark()
    devices = harness.check_device(harness.cell(bench, args.workload)["chips"])
    if args.control:
        from perfharness import program

        w = harness.cell(bench, args.workload)
        entry = next(c for c in bench["configs"] if c["name"] == w["config"])
        config = json.loads((harness.ROOT / entry["file"]).read_text())
        ref = harness.reference(config["reference"])
        orig = program.qparams

        def int4(weights, _layers=config["layers"]):
            return orig(ref.control_weights(_layers, weights))

        program.qparams = int4
    for seed in (int(s) for s in args.seeds.split(",")):
        t_start = time.perf_counter()
        res = harness.run_cell(args.workload, seed, args.seconds, False, t_start, devices)
        checks = " ".join(f"{k}={c['value']}/limit {c['limit']}" for k, c in res["checks"].items())
        kind = "control" if args.control else "program"
        ok = res["correct"]
        print(f"READING {args.workload} {kind} seed={seed} correct={ok} {checks}", flush=True)
        print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
