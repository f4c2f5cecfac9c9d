"""The reduction from a trace to numbers, on small traces."""

import os

import pytest

from harness import trace

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "data", "trace_v5e.json")


def _synthetic():
    ms = 1_000_000
    return trace.Trace(
        ops={0: [("fusion.1", 10 * ms, 30 * ms),
                 ("fusion.2", 25 * ms, 40 * ms),   # overlaps fusion.1
                 ("copy.3", 60 * ms, 70 * ms),
                 ("fusion.1", 95 * ms, 130 * ms)]},  # runs past the end
        modules={0: [("jit_superstep_body(7)", 10 * ms, 40 * ms),
                     ("jit_inv_scan_impl(2)", 60 * ms, 70 * ms),
                     ("jit_level_body(3)", 95 * ms, 130 * ms)]},
        host=[("bench.window", 0, 100 * ms),
              ("bench.check depth=14", 0, 100 * ms),
              ("PjitFunction(_deflate)", 45 * ms, 55 * ms),
              ("np.asarray(jax.Array)", 71 * ms, 94 * ms)],
    )


def test_busy_is_the_union_inside_the_window():
    t = _synthetic()
    assert trace.window_s(t) == pytest.approx(0.100)
    # [10,40) + [60,70) + [95,100): 30 + 10 + 5 ms
    assert trace.busy_s(t) == pytest.approx(0.045)


def test_idle_gaps_are_named_by_the_innermost_host_span():
    gaps = trace.idle_gaps(_synthetic())
    assert gaps[0] == ["np.asarray(jax.Array)", pytest.approx(0.025)]
    assert gaps[1] == ["PjitFunction(_deflate)", pytest.approx(0.020)]
    assert gaps[2] == ["bench.check depth=14", pytest.approx(0.010)]
    assert len(gaps) == 3


def test_top_ops_sum_by_name_inside_the_window():
    ops = dict(trace.top_ops(_synthetic()))
    assert ops["fusion.1"] == pytest.approx(0.025)  # 20 + 5 ms clipped
    assert ops["fusion.2"] == pytest.approx(0.015)
    assert ops["copy.3"] == pytest.approx(0.010)


def test_program_time_matches_the_name_table():
    t = _synthetic()
    pats = ["^jit_level_body", "^jit_superstep_body"]
    assert trace.program_s(t, pats) == pytest.approx(0.035)  # 30 + 5 ms


def test_a_trace_without_the_window_span_is_refused():
    t = _synthetic()
    t.host = [h for h in t.host if h[0] != "bench.window"]
    with pytest.raises(ValueError):
        trace.busy_s(t)


def test_recorded_chip_trace():
    """A window recorded on a TPU v5e (one check of Raft.cfg), reduced."""
    t = trace.load(RECORDED)
    busy, win = trace.busy_s(t), trace.window_s(t)
    assert 0 < busy < win
    gaps = trace.idle_gaps(t)
    assert gaps and all(g[1] > 0 for g in gaps)
    assert sum(g[1] for g in trace.idle_gaps(t, top=10**9)) == pytest.approx(
        win - busy, rel=1e-6)
    assert trace.program_s(t, ["^jit_level_body", "^jit_superstep_body"]) > 0
