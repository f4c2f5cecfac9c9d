"""Multi-level resident supersteps (engine/superstep.py) vs per-level.

The resident N-level driver must be a pure execution-plan change:
distinct/generated/depth/level_sizes (and violation stop points) stay
BIT-IDENTICAL between ``--superstep N>1``, ``N=1`` (the per-level
megakernel) and the staged chain on every fixture; every overflow
class stops the superstep uncommitted and re-enters the existing
grow-and-redo machinery at the stopped level; ring high-water exits
early and restarts cleanly; a ``level.start`` SIGKILL mid-superstep
resumes through ``--recover``; the bucket path retires whole small
jobs in a couple of dispatches with sequential-identical summaries;
and the watchdog's armed deadline scales with the declared span.
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

import tla_raft_tpu.ops.hashstore as hashstore
from tla_raft_tpu.config import RaftConfig
from tla_raft_tpu.engine import JaxChecker
from tla_raft_tpu.engine import superstep as superstep_mod
from tla_raft_tpu.ops.hashstore import DeviceHashStore
from tla_raft_tpu.resilience import elastic

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

S2 = RaftConfig(n_servers=2, n_vals=1, max_election=1, max_restart=1)
S3V1 = RaftConfig(n_vals=1, max_election=1, max_restart=1)


def _quad(res):
    return (res.ok, res.distinct, res.generated, res.depth,
            tuple(res.level_sizes))


# -- superstep vs per-level vs staged: bit-identical parity ---------------

def test_superstep_vs_per_level_s2():
    # staged parity rides transitively: test_megakernel.py's
    # test_fused_vs_staged_s2_fixpoint gates staged == superstep=1 on
    # these exact constants (incl. action_counts), so the fast tier
    # skips re-computing the staged S2 fixpoint here
    per_level = JaxChecker(S2, chunk=64, superstep=1).run()
    chk = JaxChecker(S2, chunk=64, superstep=4)
    fused = chk.run()
    assert _quad(per_level) == _quad(fused)
    assert per_level.action_counts == fused.action_counts
    assert fused.distinct == 50 and fused.depth == 12
    # the whole run rode resident supersteps: 13 levels in 4 dispatches
    assert chk._ss_stats["supersteps"] == 4
    assert chk._ss_stats["levels"] == 13
    assert chk._ss_stats["stops"] == 0


def test_superstep_max_depth_clamps_span():
    """The resident loop must never expand past --max-depth: the span
    clamp covers prefixes whose depth is not a span multiple."""
    a = JaxChecker(S2, chunk=64, superstep=1).run(max_depth=6)
    chk = JaxChecker(S2, chunk=64, superstep=4)
    b = chk.run(max_depth=6)
    assert _quad(a) == _quad(b)
    assert b.depth == 6
    # 6 levels = one span-4 superstep + a span-2 remainder
    assert chk._ss_stats["levels"] == 6


# -- overflow classes stop the superstep and re-enter grow-and-redo -------

def test_superstep_cap_x_overflow_replays_per_level():
    chk = JaxChecker(S2, chunk=64, cap_x=16, superstep=4)
    res = chk.run()
    assert (res.distinct, res.depth) == (50, 12)
    # the stop routed the level through the per-level megakernel,
    # whose existing machinery grew cap_x and redid it
    assert chk._ss_stats["stops"] > 0
    assert chk._mega_stats["redo_x"] > 0
    assert chk.cap_x > 16


def test_superstep_slab_overflow_replays_per_level(monkeypatch):
    monkeypatch.setattr(hashstore, "MIN_CAP", 16)
    monkeypatch.setattr(
        DeviceHashStore, "need_grow", lambda self, extra=0: False
    )
    chk = JaxChecker(S2, chunk=64, superstep=4)
    res = chk.run()
    assert (res.distinct, res.depth) == (50, 12)
    assert chk._mega_stats["redo_slab"] > 0


def test_superstep_ring_high_water_early_exit(monkeypatch):
    """A deliberately tiny ring: the loop must exit at high-water with
    the committed prefix intact and restart there — counts pinned."""
    monkeypatch.setattr(
        superstep_mod, "ring_capacity",
        lambda fut, span, cap_f, pow2: 4,
    )
    chk = JaxChecker(S2, chunk=64, superstep=4)
    res = chk.run()
    assert (res.distinct, res.depth) == (50, 12)
    assert chk._ss_stats["ring_stops"] > 0


# -- accounting: the 1-dispatch-per-superstep ledger ----------------------

def test_dispatch_log_superstep_amortization():
    from tla_raft_tpu.analysis.sanitize import (
        DispatchLog,
        set_dispatch_sink,
    )

    log = DispatchLog()
    set_dispatch_sink(log)
    try:
        res = JaxChecker(S2, chunk=64, superstep=4).run()
    finally:
        set_dispatch_sink(None)
    log.close()
    assert res.distinct == 50
    # 13 levels retired by 4 programs: amortized 1/N of the per-level
    # megakernel's 13 (and far under the staged chain's 38)
    assert log.total == 4
    assert log.tags.get("superstep.levels") == 4
    assert len(log.per_superstep) == 4
    assert log.steady_max_superstep() == 1
    assert sum(log.superstep_levels) == 13


# -- shared program caches: one program per LevelSpec, no engine pinned --

# the two fused paths and their cache/stats: superstep=1 runs every
# level through the per-level megakernel program, superstep=4 through
# the resident driver; both share fused_level_core and the cache scheme
FUSED_PATHS = [
    pytest.param(1, "_mega_stats", id="megakernel"),
    pytest.param(4, "_ss_stats", id="superstep"),
]


def _path_cache(span):
    from tla_raft_tpu.engine import megakernel

    return megakernel._PROG_CACHE if span == 1 else superstep_mod._PROG_CACHE


@pytest.fixture
def empty_caches():
    from tla_raft_tpu.engine import megakernel

    megakernel._PROG_CACHE.clear()
    superstep_mod._PROG_CACHE.clear()
    yield
    megakernel._PROG_CACHE.clear()
    superstep_mod._PROG_CACHE.clear()


@pytest.fixture
def traced_cap_x(monkeypatch):
    """The ``cap_x`` of every trace of the fused level body, in order."""
    from tla_raft_tpu.engine import megakernel

    seen = []
    real = megakernel.fused_level_core

    def spy(spec, *args, **kw):
        seen.append(spec.cap_x)
        return real(spec, *args, **kw)

    monkeypatch.setattr(megakernel, "fused_level_core", spy)
    return seen


@pytest.mark.parametrize("span,stats", FUSED_PATHS)
def test_program_shared_across_checkers(span, stats, empty_caches,
                                        traced_cap_x):
    """Two checkers on one config, back to back: the second finds the
    first's program and re-traces none of its shape rungs, though the
    first grew cap_x during its check (the benchmark's back-to-back
    checks grow 4096 -> 6144)."""
    from tla_raft_tpu.oracle.golden import GOLDEN_LEVELS

    ref = RaftConfig(n_servers=3, n_vals=2, max_election=3, max_restart=3)
    golden = GOLDEN_LEVELS[(3, 2, 3, 3)][:7]
    a = JaxChecker(ref, chunk=64, cap_x=64, superstep=span)
    assert list(a.run(max_depth=6).level_sizes) == golden
    assert a.cap_x > 64
    assert getattr(a, stats)["program_builds"] >= 1
    cache = _path_cache(span)
    progs = dict(cache)
    rungs = {k: p.jitted._cache_size() for k, p in progs.items()}
    n_traces = len(traced_cap_x)
    assert n_traces > 0

    b = JaxChecker(ref, chunk=64, cap_x=64, superstep=span)
    assert list(b.run(max_depth=6).level_sizes) == golden
    assert getattr(b, stats)["program_builds"] == 0
    assert getattr(b, stats)["program_hits"] >= 1
    assert len(traced_cap_x) == n_traces  # no trace of the level body
    assert dict(cache) == progs
    assert {k: p.jitted._cache_size() for k, p in cache.items()} == rungs


@pytest.mark.parametrize("span,stats", FUSED_PATHS)
def test_grown_cap_x_gets_its_own_program(span, stats, empty_caches):
    chk = JaxChecker(S2, chunk=64, cap_x=16, superstep=span)
    res = chk.run()
    assert (res.distinct, res.depth) == (50, 12)
    assert chk.cap_x > 16
    caps = {key[0].cap_x for key in _path_cache(span)}
    # the overflowing budget's program and the grown one's, side by side
    assert {16, chk.cap_x} <= caps
    assert getattr(chk, stats)["program_builds"] >= 2


@pytest.mark.parametrize("span,stats", FUSED_PATHS)
def test_rung_traced_after_growth_keeps_its_budgets(span, stats,
                                                    empty_caches,
                                                    traced_cap_x):
    """A program's creator grows cap_x; a rung of the creator's old
    program traced afterwards still reads the old budgets, and a second
    checker at the old budgets reuses that program and lands the exact
    counts."""
    import jax
    import jax.numpy as jnp

    from tla_raft_tpu.models.raft import init_batch

    grower = JaxChecker(S2, chunk=64, cap_x=16, superstep=span)
    old_spec = grower.level_spec()
    grower.run()
    assert grower.cap_x > 16  # the creator moved on, and stays alive
    prog = next(p for k, p in _path_cache(span).items() if k[0] == old_spec)
    fr0, _ovf = jax.jit(grower._deflate)(init_batch(S2, 1))
    cap = 1 << 10  # a frontier rung the S2 run never reaches
    fr = grower._frontier_struct(fr0, cap)
    slab = jax.ShapeDtypeStruct((hashstore.MIN_CAP,), jnp.uint64)
    s_i64 = jax.ShapeDtypeStruct((), jnp.int64)
    sieve = jax.ShapeDtypeStruct((1,), jnp.uint64)
    del traced_cap_x[:]
    if span == 1:
        prog.lower(fr, slab, s_i64, sieve, cap_out=cap)
    else:
        prog.lower(fr, slab, s_i64, s_i64, sieve, cap_f=cap, ring=2 * cap)
    assert traced_cap_x and set(traced_cap_x) == {16}

    again = JaxChecker(S2, chunk=64, cap_x=16, superstep=span)
    res = again.run()
    assert (res.distinct, res.depth) == (50, 12)
    assert getattr(again, stats)["program_hits"] >= 1


@pytest.mark.parametrize("span,stats", FUSED_PATHS)
def test_program_cache_pins_no_engine(span, stats, empty_caches):
    import gc
    import weakref

    chk = JaxChecker(S2, chunk=64, superstep=span)
    assert chk.run().distinct == 50
    assert getattr(chk, stats)["program_builds"] >= 1
    assert _path_cache(span)  # the program outlives its check ...
    gc.collect()
    ref = weakref.ref(chk)
    gc.disable()
    try:
        # ... and the checker does not: no cache and no cycle holds it,
        # so its device hash slab goes with its last reference
        del chk
        assert ref() is None
    finally:
        gc.enable()


# -- watchdog: the N-level budget math ------------------------------------

def test_watchdog_superstep_budget_math():
    wd = elastic.Watchdog(10.0, mult=8.0, on_hard_timeout=lambda: None)
    try:
        # cold start, span 1: floor * mult headroom
        wd.arm("level 1")
        assert wd._armed["budget"] == pytest.approx(80.0)
        wd.disarm()
        # seed per-level history: pretend the last window covered 4
        # levels in 8s -> 2s/level recorded
        wd._hist[:] = []
        wd.arm("superstep", span=4)
        a = wd._armed
        # cold-start rule scales with the span too
        assert a["budget"] == pytest.approx(4 * 8.0 * 10.0)
        wd.disarm()
        wd._hist[:] = [2.0]
        wd.arm("superstep", span=4)
        # span * max(floor, mult * last-per-level)
        assert wd._armed["budget"] == pytest.approx(4 * 16.0)
        wd.disarm()
        wd._hist[:] = [2.0]
        wd.arm("level 9")  # span defaults to 1: per-level budget
        assert wd._armed["budget"] == pytest.approx(16.0)
        wd.disarm()
        # disarm normalizes a span-N window's wall time per level
        wd._hist[:] = []
        wd.arm("superstep", span=4)
        import time as _t

        _t.sleep(0.2)
        wd.disarm()
        assert wd._hist[-1] < 0.2  # elapsed / 4, not raw elapsed
        # a STOPPED window reports its committed level count: the
        # elapsed normalizes by min(declared, committed), not the full
        # declared span — otherwise a span-16 window stopping on its
        # first level would deflate the history and false-trip the
        # level's own per-level replay (span > mult)
        wd._hist[:] = []
        wd.arm("superstep", span=16)
        _t.sleep(0.2)
        wd.disarm(levels=1)
        assert wd._hist[-1] >= 0.2  # elapsed / 1, not elapsed / 16
    finally:
        wd.cancel()


# -- bucket path: whole jobs in a couple of dispatches --------------------

def test_bucket_superstep_parity_and_amortization():
    from tla_raft_tpu.service.bucket import BatchedChecker

    cfgs = [
        RaftConfig(n_servers=2, n_vals=1, max_election=1, max_restart=mr)
        for mr in (0, 1, 2)
    ]
    a = BatchedChecker(cfgs, superstep=1).run()
    chk = BatchedChecker(cfgs, superstep=4)
    b = chk.run()
    keys = ("ok", "distinct", "generated", "depth", "level_sizes",
            "violation")
    for ra, rb in zip(a, b):
        assert {k: ra[k] for k in keys} == {k: rb[k] for k in keys}
    assert chk.stats["supersteps"] >= 1
    # amortization: far fewer dispatches than committed levels
    assert chk.stats["dispatches"] < chk.stats["levels"]


def test_bucket_superstep_depth_caps():
    from tla_raft_tpu.service.bucket import BatchedChecker

    cfgs = [
        RaftConfig(n_servers=2, n_vals=1, max_election=1, max_restart=mr)
        for mr in (0, 1, 2)
    ]
    caps = [5, None, 9]
    a = BatchedChecker(cfgs, max_depths=caps, superstep=1).run()
    b = BatchedChecker(cfgs, max_depths=caps, superstep=4).run()
    keys = ("ok", "distinct", "generated", "depth", "level_sizes",
            "violation")
    for ra, rb in zip(a, b):
        assert {k: ra[k] for k in keys} == {k: rb[k] for k in keys}


# -- heavier rows: violations, cap_m, S3 parity, crash, smoke (@slow) -----

@pytest.mark.slow
def test_superstep_s3v1_fixpoint_parity():
    a = JaxChecker(S3V1, chunk=256, superstep=1).run()
    chk = JaxChecker(S3V1, chunk=256, superstep=4)
    b = chk.run()
    assert _quad(a) == _quad(b)
    assert b.distinct == 545  # the pinned S3V1 fixpoint
    assert chk._ss_stats["supersteps"] > 0


@pytest.mark.slow
def test_superstep_abort_stop_point_parity():
    """A split-brain abort mid-superstep: the loop stops uncommitted,
    the per-level replay reports the exact stop point."""
    cfg = RaftConfig(n_servers=3, n_vals=1, max_election=2,
                     max_restart=0, mutations=("double-vote",))
    a = JaxChecker(cfg, chunk=256, superstep=1).run()
    chk = JaxChecker(cfg, chunk=256, superstep=4)
    b = chk.run()
    assert _quad(a) == _quad(b)
    assert not b.ok
    assert a.violation[0] == b.violation[0] == (
        'Assert "split brain" (Raft.tla:185)'
    )
    assert len(a.violation[1]) == len(b.violation[1])
    assert chk._ss_stats["stops"] > 0


@pytest.mark.slow
def test_superstep_invariant_violation_stop_point_parity():
    cfg = RaftConfig(n_servers=3, n_vals=2, max_election=2,
                     max_restart=1, mutations=("median-bug",))
    a = JaxChecker(cfg, chunk=256, superstep=1).run()
    b = JaxChecker(cfg, chunk=256, superstep=4).run()
    assert _quad(a) == _quad(b)
    assert a.violation[0] == b.violation[0] == "Invariant Inv is violated"
    assert len(a.violation[1]) == len(b.violation[1])


@pytest.mark.slow
def test_superstep_cap_m_overflow_replays_per_level():
    chk = JaxChecker(S3V1, chunk=256, cap_m=4, superstep=4)
    res = chk.run()
    assert (res.distinct, res.depth) == (545, 19)
    assert chk._mega_stats["redo_m"] > 0
    assert chk.cap_m > 4


@pytest.mark.slow
def test_grouped_gfused_vs_staged_group_chain():
    """The grouped ultra-deep regime's fused per-group program (span
    expand + visited pre-filter + compact in ONE dispatch) must be
    bit-identical to the staged span -> _group_filter_hash chain."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tla_raft_tpu.engine import bfs as bfs_mod
    from tla_raft_tpu.models.raft import init_batch

    chk = JaxChecker(S3V1, chunk=8, superstep=1)
    chk.span_min_chunk = 8
    chk._jit_expand_programs()
    chk.run(max_depth=6)  # warms the visited slab
    fr, _ = jax.jit(chk._deflate)(init_batch(S3V1, 1))
    cap = chk.G * chk.chunk
    fr = jax.tree.map(lambda x: bfs_mod._pad_axis0(x, cap), fr)
    n_f = jnp.asarray(1, jnp.int64)
    b = jnp.asarray(0, jnp.int64)
    slab = chk.hstore.slab
    cvs, cfs, cps, mult_a, ab_a, ovf_a = chk._expand_span(fr, b, b, n_f)
    gv_a, gf_a, gp_a, og_a = bfs_mod._group_filter_hash(
        cvs.reshape(-1), cfs.reshape(-1), cps.reshape(-1), slab,
        chk.cap_g,
    )
    (gv_b, gf_b, gp_b, mult_b, ab_b, ovf_b,
     og_b) = chk._expand_group_gfused(
        fr, b, b, n_f, slab, cap_g=chk.cap_g
    )
    assert np.array_equal(np.asarray(gv_a), np.asarray(gv_b))
    assert np.array_equal(np.asarray(gf_a), np.asarray(gf_b))
    assert np.array_equal(np.asarray(gp_a), np.asarray(gp_b))
    assert np.array_equal(np.asarray(mult_a), np.asarray(mult_b))
    assert int(ab_a) == int(ab_b)
    assert bool(ovf_a) == bool(ovf_b) and bool(og_a) == bool(og_b)


CFG_2111 = textwrap.dedent(
    """
    CONSTANTS
        MaxTerm = 3
        MaxRestart = 1
        MaxElection = 1
        Servers = {s1, s2}
        Vals = {v1}
    SYMMETRY symmServers
    VIEW view
    INIT Init
    NEXT Next
    INVARIANT Inv
    """
)


def _run_cli(args, fault=None, timeout=300):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    if fault is not None:
        env["TLA_RAFT_FAULT"] = fault
    else:
        env.pop("TLA_RAFT_FAULT", None)
    return subprocess.run(
        [sys.executable, "-m", "tla_raft_tpu.check", *args],
        env=env, capture_output=True, text=True, timeout=timeout,
        cwd=REPO,
    )


def _json_line(proc):
    for line in reversed(proc.stdout.splitlines()):
        if line.startswith("{"):
            return json.loads(line)
    raise AssertionError(
        f"no JSON summary in output:\n{proc.stdout}\n{proc.stderr}"
    )


@pytest.mark.slow
def test_level_start_kill_mid_superstep_recover(tmp_path):
    """SIGKILL at a level boundary INSIDE a superstep's committed-
    prefix processing (the per-level ``level.start`` site keeps its
    once-per-level cadence there); --recover must replay the delta
    log and converge on the pinned fixpoint."""
    cfg = tmp_path / "tiny.cfg"
    cfg.write_text(CFG_2111)
    ck = str(tmp_path / "ck")
    common = [
        "--config", str(cfg), "--chunk", "64", "--superstep", "4",
        "--checkpoint-dir", ck, "--log", "-", "--json",
    ]
    # hit 6 lands mid-superstep (iteration tops fire once per
    # superstep; the committed-prefix levels fire the rest)
    killed = _run_cli(common, fault="level.start:kill@6")
    assert killed.returncode != 0, "the planted kill never fired"
    rec = _run_cli(common + ["--recover", ck])
    assert rec.returncode == 0, rec.stdout[-2000:] + rec.stderr[-2000:]
    got = _json_line(rec)
    assert (got["ok"], got["distinct"], got["depth"]) == (True, 50, 12)
    assert got["superstep"] == 4


@pytest.mark.slow
def test_sanitize_smoke_one_dispatch_one_fetch_per_superstep(tmp_path):
    """GRAFT_SANITIZE acceptance on the resident path: zero post-
    warmup recompiles, zero unledgered transfers, and the superstep
    ledger showing every window as exactly one engine program dispatch
    + one ledgered fetch."""
    cfg = tmp_path / "tiny.cfg"
    cfg.write_text(CFG_2111)
    env = dict(os.environ)
    env.update(
        GRAFT_SANITIZE="1", JAX_PLATFORMS="cpu",
        TLA_RAFT_SUPERSTEP="4",
        PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH", ""),
    )
    proc = subprocess.run(
        [sys.executable, "-m", "tla_raft_tpu.check",
         "--config", str(cfg), "--chunk", "64",
         "--log", str(tmp_path / "raft.log")],
        env=env, capture_output=True, text=True, timeout=600, cwd=REPO,
    )
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    assert "Sanitizer: OK" in proc.stdout
    assert "0 post-warmup unexpected recompiles" in proc.stdout
    assert "0 unledgered host transfers" in proc.stdout
    assert "supersteps covering 13 levels" in proc.stdout, proc.stdout
    assert (
        "steady-state max 1 dispatch(es) and 1 ledgered fetch(es) "
        "per superstep" in proc.stdout
    ), proc.stdout
