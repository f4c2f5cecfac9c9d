"""The s5-deep cell is found by name, with its config, traffic, answers
and readers; its lowering reader reads the recorded trace."""

import os
import types

import pytest

from harness import compare, spec, trace

S5_READERS = ("lower_ms.s5", "check_setup_ms.s5", "superstep_dispatch_ms.s5",
              "device_idle.s5", "level_prog_roofline.s5")


def test_s5_deep_finds_its_config_traffic_answers_and_readers():
    cell = spec.load_cell("s5-deep")
    assert cell.chips == 1
    assert cell.config["name"] == "raft-s5"
    assert cell.config["constants"]["Servers"] == [
        "s1", "s2", "s3", "s4", "s5"]
    assert cell.traffic["depths"] == [12]
    assert cell.answers["depths"]["12"]["distinct"] == 48595
    assert cell.answers["depths"]["12"]["generated"] == 162325
    names = [m.name for m in cell.per_layer]
    for name in S5_READERS:
        assert name in names
        assert callable(cell.reader(next(
            m for m in cell.per_layer if m.name == name)))
    assert {m.name for m in cell.end_to_end} == {
        "states_per_s", "peak_hbm_gb", "setup_s"}
    # no reader of another cell's twin runs here
    assert not [n for n in names if n.endswith((".deep", ".quick"))]


def test_lower_ms_reads_jax_lowering_on_the_recorded_trace():
    recorded = trace.load(os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "data",
        "trace_v5e.json"))
    lowered = [(s, e) for n, s, e in recorded.host
               if n == "lower_sharding_computation"]
    assert len(lowered) == 2
    cell = spec.load_cell("s5-deep")
    read = cell.reader(next(m for m in cell.per_layer
                            if m.name == "lower_ms.s5"))
    window = [compare.CheckResult(max_depth=8, ok=True, depth=8)] * 2
    ctx = types.SimpleNamespace(trace=recorded, window=window)
    want = sum(e - s for s, e in lowered) / 2 / 1e6
    assert read(ctx) == pytest.approx(want)
    assert 30.0 < 2 * read(ctx) < 35.0  # 18.2 ms + 15.2 ms
    assert read(types.SimpleNamespace(trace=None, window=window)) is None
