"""The reader of the rasters' placement in a population sweep (``place_ms.dse``).

Checked on a synthetic trace whose answer is worked out by hand, and shown
to read nothing on the two traces recorded on the chip before the program
had the span, and on an untraced run.
"""

import gzip
import pathlib
import types

import numpy as np
import pytest

from perfharness import harness, trace

DATA = pathlib.Path(__file__).resolve().parent / "data"


def _sweep_trace():
    # two passes, each a stack, one placement and one batch; a third placement after the window
    host = [
        ("bench.pass", 0, 5_000),
        ("neura.dse.stack", 10, 110),
        ("neura.dse.place", 120, 1_120),
        ("neura.dse.batch", 1_200, 4_000),
        ("bench.pass", 5_000, 9_000),
        ("neura.dse.stack", 5_010, 5_110),
        ("neura.dse.place", 5_120, 5_720),
        ("neura.dse.batch", 5_800, 8_000),
        ("neura.dse.place", 9_500, 19_500),
    ]
    mods = np.asarray([[1_300, 3_000], [5_900, 7_000]], float)
    dev = trace.Device(ops=mods.copy(), op_names=["op"] * 2, modules=mods,
                       module_names=["jit__population_fwd(1)"] * 2)  # fmt: skip
    return trace.Trace({0: dev}, np.asarray([h[1:] for h in host], float), [h[0] for h in host])


def test_place_reads_the_mean_placement_in_the_window():
    run = types.SimpleNamespace(trace=_sweep_trace(), data={})
    # (1000 + 600) / 2 ns
    assert harness.reader("place_ms.dse").read(run) == pytest.approx(800e-6)


@pytest.mark.parametrize("which", ["mnist_small", "dse_small", "untraced"])
def test_place_reads_nothing_before_the_span(which, tmp_path):
    t = None
    if which != "untraced":
        out = tmp_path / f"{which}.xplane.pb"
        out.write_bytes(gzip.decompress((DATA / f"{which}.xplane.pb.gz").read_bytes()))
        t = trace.load(out)
    run = types.SimpleNamespace(trace=t, data={})
    assert harness.reader("place_ms.dse").read(run) is None
