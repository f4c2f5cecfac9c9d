"""Cells, configurations, traffic mixes and metrics are found by name."""

import os

import pytest

from harness import spec, traffic

from conftest import ROOT


def test_every_cell_of_the_benchmark_loads():
    import json

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        doc = json.load(f)
    for w in doc["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.end_to_end and cell.per_layer
        assert "setup_s" in [m.name for m in cell.end_to_end]
        for m in cell.end_to_end + cell.per_layer:
            assert callable(cell.reader(m))
        # the answers cover every depth the traffic asks for
        for d in traffic.warmup(cell.traffic):
            assert str(d) in cell.answers["depths"]


def test_an_added_cell_needs_only_data_and_a_reader(tiny_root):
    cell = spec.load_cell("ref-tiny", root=str(tiny_root),
                          bench_dir=str(tiny_root / "benchmark"))
    assert cell.traffic["depths"] == [4, 5]
    assert cell.config["constants"]["Servers"] == ["s1", "s2", "s3"]
    names = [m.name for m in cell.per_layer]
    assert "levels_in_window" in names
    assert "host_ms_per_level.deep" in names
    assert [m.name for m in cell.end_to_end] == ["states_per_s", "setup_s"]


def test_unknown_cell_is_an_error():
    with pytest.raises(KeyError):
        spec.load_cell("no-such-cell")


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 11, 2**33 + 5])
def test_quick_blocks_hold_every_depth_once(seed):
    gen = traffic.checks({"kind": "repeat_checks", "depths": [6, 7, 8, 9, 10],
                          "shuffle": True}, seed)
    for _ in range(4):
        block = [next(gen) for _ in range(5)]
        assert sorted(block) == [6, 7, 8, 9, 10]


def test_unshuffled_traffic_ignores_the_seed():
    t = {"kind": "repeat_checks", "depths": [14], "shuffle": False}
    a, b = traffic.checks(t, 1), traffic.checks(t, 99)
    assert [next(a) for _ in range(3)] == [next(b) for _ in range(3)] == [14] * 3


def test_unknown_traffic_kind_is_refused():
    with pytest.raises(ValueError):
        traffic.warmup({"kind": "open_loop", "depths": [3]})
