"""The paper-reproduction scripts under ``benchmarks/`` run end to end.

Each case calls a module's ``run()`` at its smallest arguments (one epoch, a
short ``T``) and checks the rows it returns: ``(name, finite float, str)``,
with the module's name prefix.  Figures are written to ``tmp_path``.  Table 1
runs three of its nine rows, one per dataset, neuron model and topology kind:
all nine take about 28 s on CPU, which would double this file's time.
"""

import importlib
import math
import pathlib
import sys

import pytest

_ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(_ROOT))  # benchmarks/ is a plain directory, not installed

# module, run() kwargs, row-name prefix
CASES = [
    ("cg_error", {}, "cg_error/"),
    ("table2_resources", {"epochs": 1, "T": 5}, "table2/"),
    ("fig11_dse", {"epochs": 1, "T": 5}, "fig11/"),
    ("table1_accuracy", {"epochs": 1, "T": 5}, "table1/"),
]


@pytest.mark.parametrize("module, kwargs, prefix", CASES, ids=[c[0] for c in CASES])
def test_paper_script_rows(module, kwargs, prefix, tmp_path, monkeypatch):
    mod = importlib.import_module(f"benchmarks.{module}")
    if hasattr(mod, "OUT"):
        monkeypatch.setattr(mod, "OUT", tmp_path / mod.OUT.name)
    if module == "table1_accuracy":
        monkeypatch.setattr(mod, "ROWS", [mod.ROWS[i] for i in (0, 6, 8)])
    rows = mod.run(**kwargs)
    assert rows
    for name, us, derived in rows:
        assert isinstance(name, str) and name.startswith(prefix), name
        assert isinstance(us, float) and math.isfinite(us), (name, us)
        assert isinstance(derived, str) and derived, name
    if module == "cg_error":
        assert all("claim_1_512=PASS" in d for _, _, d in rows)
    if module == "table1_accuracy":
        assert len(rows) == 3
    if hasattr(mod, "OUT"):
        lines = (tmp_path / mod.OUT.name).read_text().splitlines()
        assert lines[0].startswith("ff_bits,rec_bits,leak_bits")
        assert len(lines) > 1
