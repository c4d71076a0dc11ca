"""Reproduce the paper's tables and figure: one module per artifact.

Prints ``name,us_per_call,derived`` CSV (one line per row).

  table1    -- paper Table 1 (neuron x topology x dataset accuracy sweep)
  table2    -- paper Table 2 (MNIST design point: resources/latency/energy)
  fig11     -- paper Fig. 11 (precision-DSE cost landscape, ATA-F on DVS)
  cg_error  -- section 4.1.2 CG approximation-error claims

These report accuracy, modeled FPGA resources and approximation error; the
``us_per_call`` column is the host wall time of the row, not a speed
measurement.  System speed on the chip is measured by ``perfbench/run.py``.

Usage: python -m benchmarks.run [--only table2,cg_error] [--fast]

The persistent jit compilation cache is on: ``JAX_COMPILATION_CACHE_DIR``
when set, else ``.jax_cache/`` at the checkout root
(``repro.distributed.compat.enable_compilation_cache``).
"""

import argparse
import sys
import traceback

MODULES = ["cg_error", "table2", "table1", "fig11"]


def _rows(name: str, fast: bool):
    if name == "table1":
        from benchmarks import table1_accuracy

        return table1_accuracy.run(epochs=2 if fast else 8)
    if name == "table2":
        from benchmarks import table2_resources

        return table2_resources.run(epochs=3 if fast else 8)
    if name == "fig11":
        from benchmarks import fig11_dse

        return fig11_dse.run(epochs=2 if fast else 5)
    if name == "cg_error":
        from benchmarks import cg_error

        return cg_error.run()
    raise KeyError(name)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument("--fast", action="store_true")
    args = ap.parse_args()

    from repro.distributed.compat import enable_compilation_cache

    enable_compilation_cache()

    names = args.only.split(",") if args.only else MODULES
    print("name,us_per_call,derived")
    failed = False
    for name in names:
        try:
            for row_name, us, derived in _rows(name, args.fast):
                print(f"{row_name},{us:.1f},{derived}")
                sys.stdout.flush()
        except Exception as e:
            failed = True
            print(f"{name},0.0,EXCEPTION:{type(e).__name__}:{e}")
            traceback.print_exc(file=sys.stderr)
    if failed:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
