"""The benchmark's own tests run on the CPU: they never take the chip."""

import json
import os
import shutil
import sys

import pytest

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
for p in (BENCH_DIR, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

TINY_CELL = {"name": "ref-tiny", "config": "raft-ref", "traffic": "tiny",
             "chips": 1, "why": "a test cell: two shallow checks"}


@pytest.fixture
def tiny_root(tmp_path):
    """A checkout-like root with one extra cell, traffic mix, metric and
    peak row, all added as data and a reader file: no code edited."""
    bench = tmp_path / "benchmark"
    shutil.copytree(BENCH_DIR, bench,
                    ignore=shutil.ignore_patterns("_build", "__pycache__"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["workloads"].append(dict(TINY_CELL))
    for m in spec["end_to_end"] + spec["per_layer"]:
        if m["name"] in ("states_per_s", "setup_s", "host_ms_per_level.deep"):
            m.setdefault("workloads", [])
            m["workloads"].append("ref-tiny")
    spec["per_layer"].append({
        "name": "levels_in_window", "unit": "levels", "better": "higher",
        "source": "program_span", "layer": "level loop (engine/bfs.py)",
        "moves": "states_per_s", "workloads": ["ref-tiny"],
    })
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    (bench / "traffic" / "tiny.json").write_text(json.dumps(
        {"kind": "repeat_checks", "depths": [4, 5], "shuffle": True}
    ))
    (bench / "metrics" / "levels_in_window.py").write_text(
        "def read(ctx):\n    return ctx.in_window.levels\n"
    )
    peaks = json.loads((bench / "peaks.json").read_text())
    peaks["devices"]["cpu"] = dict(peaks["devices"]["TPU v5 lite"])
    (bench / "peaks.json").write_text(json.dumps(peaks))
    return tmp_path
