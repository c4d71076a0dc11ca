"""A cell, a configuration, a traffic mix and a metric are found by name from their own files.

A copy of the benchmark gains one of each by added files and one added
entry per list of BENCHMARK.json; no file that was there is edited, and the
harness in the copy finds them all.
"""

import hashlib
import json
import os
import shutil
import subprocess
import sys

from perfharness import BENCH_DIR, ROOT

_PROBE = """
import json, sys
sys.path.insert(0, {bench!r})
from perfharness import harness
bench = harness.benchmark()
w = harness.cell(bench, "added-cell")
entry = next(c for c in bench["configs"] if c["name"] == w["config"])
config = json.loads((harness.ROOT / entry["file"]).read_text())
mix = harness.load_json("traffic", w["traffic"])
names = [m["name"] for m in harness.metrics_of(bench, "added-cell", True)]
value = harness.reader("added_metric.serve").read(None)
print(json.dumps({{"config": config["n_steps"], "rate": mix["arrivals"]["rate_per_s"],
                  "metrics": names, "value": value,
                  "ref": harness.reference(config["reference"]).__name__}}))
"""


def _digest(root):
    return {
        str(p.relative_to(root)): hashlib.sha256(p.read_bytes()).hexdigest()
        for p in sorted(root.rglob("*"))
        if p.is_file() and "__pycache__" not in p.parts
    }


def test_an_added_cell_is_added_files_and_entries(tmp_path):
    shutil.copytree(BENCH_DIR, tmp_path / "perfbench", ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    before = _digest(tmp_path / "perfbench")

    bench_dir = tmp_path / "perfbench"
    config = json.loads((bench_dir / "configs" / "mnist-lif-ff-256-128-10.json").read_text())
    config.update(name="mnist-lif-ff-256-128-10-t100", n_steps=100)
    (bench_dir / "configs" / "mnist-lif-ff-256-128-10-t100.json").write_text(json.dumps(config))
    mix = json.loads((bench_dir / "traffic" / "serve-open-mnist.json").read_text())
    mix["arrivals"]["rate_per_s"] = 123.0
    (bench_dir / "traffic" / "serve-open-mnist-slow.json").write_text(json.dumps(mix))
    reader = bench_dir / "metrics" / "added_metric.serve.py"
    reader.write_text("def read(run):\n    return 42.0\n")

    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bench["configs"].append(
        {"name": config["name"], "source": "https://arxiv.org/abs/2602.18140",
         "file": "perfbench/configs/mnist-lif-ff-256-128-10-t100.json", "reduced": [],
         "why": "T=100"}
    )  # fmt: skip
    bench["workloads"].append(
        {"name": "added-cell", "config": config["name"], "traffic": "serve-open-mnist-slow",
         "chips": 1, "why": "an added cell"}
    )  # fmt: skip
    bench["per_layer"].append(
        {"name": "added_metric.serve", "unit": "ms", "better": "lower", "source": "host_clock",
         "layer": "scheduler", "moves": "p95_latency_ms", "workloads": ["added-cell"]}
    )  # fmt: skip
    for m in bench["end_to_end"]:
        if m["name"] in ("samples_per_s", "p95_latency_ms"):
            m["workloads"].append("added-cell")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    after = _digest(tmp_path / "perfbench")
    assert {k: after[k] for k in before} == before  # nothing that was there changed

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-c", _PROBE.format(bench=str(bench_dir))],
        env=env, capture_output=True, text=True, timeout=120, cwd=tmp_path,
    )  # fmt: skip
    assert out.returncode == 0, out.stderr[-2000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert got["config"] == 100 and got["rate"] == 123.0 and got["value"] == 42.0
    assert got["metrics"] == ["added_metric.serve"]
    assert got["ref"].endswith("lif_int")


def test_a_missing_file_is_named():
    import pytest

    from perfharness import harness

    with pytest.raises(FileNotFoundError, match="no_such_mix"):
        harness.load_json("traffic", "no_such_mix")
    with pytest.raises(FileNotFoundError, match="no_such_metric"):
        harness.reader("no_such_metric")
