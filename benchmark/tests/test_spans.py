"""The readers of the program's spans, on small hand-built traces."""

import importlib.util
import os
import types

import pytest

from harness import compare, spans, trace

HERE = os.path.dirname(os.path.abspath(__file__))
METRICS = os.path.join(os.path.dirname(HERE), "metrics")
MS = 1_000_000


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        "reader_" + name.replace(".", "_"),
        os.path.join(METRICS, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _two_checks():
    """Two checks of depth 3 in a 200 ms window; the first starts 5 ms
    before the window does (so it is clipped)."""
    return trace.Trace(
        ops={0: [("fusion.1", 40 * MS, 60 * MS),
                 ("fusion.2", 140 * MS, 170 * MS)]},
        modules={0: []},
        host=[
            ("bench.window", 0, 200 * MS),
            ("raft.check", -5 * MS, 100 * MS),
            ("raft.check.construct", -5 * MS, 10 * MS),
            ("raft.check.init", 10 * MS, 25 * MS),
            ("raft.check.levels", 25 * MS, 90 * MS),
            ("raft.superstep.dispatch", 30 * MS, 38 * MS),
            ("raft.fetch", 38 * MS, 62 * MS),
            ("raft.level.single", 70 * MS, 85 * MS),
            ("raft.fetch", 75 * MS, 80 * MS),
            ("raft.check.finish", 90 * MS, 100 * MS),
            ("raft.check", 100 * MS, 200 * MS),
            ("raft.check.construct", 100 * MS, 110 * MS),
            ("raft.check.init", 110 * MS, 130 * MS),
            ("raft.check.levels", 130 * MS, 190 * MS),
            ("raft.superstep.dispatch", 132 * MS, 136 * MS),
            ("raft.fetch", 136 * MS, 172 * MS),
            ("raft.check.finish", 190 * MS, 200 * MS),
            ("PjitFunction(superstep_body)", 132 * MS, 135 * MS),
        ],
    )


def _ctx(tr, depths=(3, 3)):
    return types.SimpleNamespace(
        trace=tr,
        window=[compare.CheckResult(max_depth=d, ok=True, depth=d)
                for d in depths],
    )


def test_check_setup_is_check_start_to_level_loop_start():
    # (25 - 0) after clipping, and (130 - 100): mean 27.5 ms
    got = _reader("check_setup_ms.deep")(_ctx(_two_checks()))
    assert got == pytest.approx(27.5)


def test_superstep_dispatch_is_the_mean_span():
    got = _reader("superstep_dispatch_ms.deep")(_ctx(_two_checks()))
    assert got == pytest.approx(6.0)  # (8 + 4) / 2


def test_level_host_time_leaves_out_fetches_and_set_up():
    # loops 65 + 60 ms, fetches inside them 24 + 5 + 36 ms, 6 levels
    got = _reader("level_host_ms.deep")(_ctx(_two_checks()))
    assert got == pytest.approx((125 - 65) / 6)


def test_readers_report_nothing_without_the_spans():
    recorded = trace.load(os.path.join(HERE, "data", "trace_v5e.json"))
    for name in ("check_setup_ms.deep", "superstep_dispatch_ms.deep",
                 "level_host_ms.deep"):
        read = _reader(name)
        assert read(_ctx(None)) is None, name
        assert read(_ctx(recorded)) is None, name


def test_idle_by_span_names_the_innermost_raft_span():
    t = _two_checks()
    got = dict(spans.idle_by_span(t))
    ms = 1e-3
    # device busy [40, 60) and [140, 170) of the 200 ms window
    assert got["raft.check.construct"] == pytest.approx(20 * ms)
    assert got["raft.check.init"] == pytest.approx(35 * ms)
    assert got["raft.superstep.dispatch"] == pytest.approx(12 * ms)
    assert got["raft.fetch"] == pytest.approx((2 + 2 + 5 + 4 + 2) * ms)
    assert got["raft.level.single"] == pytest.approx(10 * ms)
    assert got["raft.check.levels"] == pytest.approx(
        (5 + 8 + 5 + 2 + 18) * ms)
    assert got["raft.check.finish"] == pytest.approx(20 * ms)
    # every idle instant is counted once
    assert sum(got.values()) == pytest.approx(
        trace.window_s(t) - trace.busy_s(t))


def test_idle_by_span_without_program_spans():
    t = _two_checks()
    t.host = [h for h in t.host if not h[0].startswith("raft.")]
    assert spans.idle_by_span(t) == [
        [spans.NO_SPAN, pytest.approx(0.150)]]
