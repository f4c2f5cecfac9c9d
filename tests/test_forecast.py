"""Predictive capacity sizing (VERDICT r4 #7).

The forecast module extrapolates per-level new-state counts from the
measured frontier-ratio decay (BASELINE.md "golden counts"); the engines
use it to pre-size capacities once for a whole run so growth-triggered
full-program recompiles (the round-4 depth-14 mesh killer,
docs/MESH_DEEP.json) never fire.  Quick tier: the math checks against
the pinned golden levels; the mesh presize behavior test is in
test_sharded.py's virtual-mesh suite.
"""

import pytest

from tla_raft_tpu.engine.forecast import (
    forecast_final_distinct,
    forecast_new_states,
    pow2ceil,
)

from refenv import requires_reference

# the deepest verified per-level record (oracle/golden.py GOLDEN_LEVELS /
# BASELINE.md): levels 0..28 of the as-is reference config
GOLDEN = [
    1, 1, 3, 9, 22, 57, 136, 345, 931, 2468, 5881, 12505, 24705,
    47599, 91014, 169607, 301664, 511609, 839797, 1353766, 2150466,
    3350017, 5099018, 7596394, 11125029, 16077143, 22959572,
    32391457, 45102507,
]


def test_pow2ceil():
    assert pow2ceil(1) == 1
    assert pow2ceil(2) == 2
    assert pow2ceil(3) == 4
    assert pow2ceil(4096) == 4096
    assert pow2ceil(4097) == 8192


def test_forecast_matches_golden_deep():
    # from 21 observed levels, the level-28 forecast lands within 25%
    # of the measured record (actual accuracy ~5%; the decay model is
    # the whole point, so gate it with margin)
    fut = forecast_new_states(GOLDEN[:21], target_depth=28)
    assert len(fut) == 8
    assert abs(fut[-1] - GOLDEN[28]) / GOLDEN[28] < 0.25


def test_forecast_mid_depth_capacity_grade():
    # from 11 observed levels (the depth-14 parity script's resume
    # point), the level-14 forecast is capacity-grade: within a factor
    # of 2.5 of truth, and NOT a 10x overshoot that would OOM a presize
    fut = forecast_new_states(GOLDEN[:11], target_depth=14)
    assert len(fut) == 4
    assert GOLDEN[14] / 2.5 < fut[-1] < GOLDEN[14] * 2.5


def test_forecast_final_distinct_bounds():
    got = forecast_final_distinct(GOLDEN[:21], sum(GOLDEN[:21]),
                                  target_depth=28)
    true = sum(GOLDEN[:29])
    assert true / 1.5 < got < true * 1.5


def test_forecast_fixpoint_projection_terminates():
    # target_depth=None projects until the modeled frontier decays out;
    # must terminate and give a finite total
    fut = forecast_new_states(GOLDEN[:21], target_depth=None)
    assert 0 < len(fut) <= 128
    assert all(isinstance(x, int) and x > 0 for x in fut)


def test_forecast_no_signal():
    assert forecast_new_states([1], target_depth=10) == []
    assert forecast_new_states([1, 1, 3], target_depth=2) == []
    assert len(forecast_new_states([1, 1, 3], target_depth=3)) == 1


@pytest.mark.slow
@requires_reference
def test_jax_checker_presize_parity(monkeypatch):
    """Forced-on presize floors must not change any count: the floors
    only pad shapes (frontier capacity, visited trim, merge width)."""
    monkeypatch.setenv("TLA_RAFT_PRESIZE", "1")
    from tla_raft_tpu.cfgparse import load_raft_config
    from tla_raft_tpu.engine import JaxChecker

    cfg = load_raft_config("/root/reference/Raft.cfg")
    chk = JaxChecker(cfg, chunk=256)
    res = chk.run(max_depth=8)
    assert res.ok and list(res.level_sizes) == GOLDEN[:9]
    assert res.distinct == sum(GOLDEN[:9])
    assert chk._presize_fcap > 0, "presize floors never engaged"
