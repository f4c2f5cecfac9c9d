"""Whole-level megakernel (engine/megakernel.py) vs the staged chain.

The fused program must be a pure execution-plan change: per-config
distinct/generated/depth/level_sizes (and violation stop points) stay
BIT-IDENTICAL to the staged path on every fixture, every overflow
class re-enters the grow-and-redo machinery and still converges, a
``level.start`` SIGKILL resumes through ``--recover`` on the fused
path, and the sanitizer smoke pins the headline claim: one device
program + one ledgered fetch per steady-state level.
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
from tla_raft_tpu.ops.hashstore import DeviceHashStore
from tla_raft_tpu.resilience import faults

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

S2 = RaftConfig(n_servers=2, n_vals=1, max_election=1, max_restart=1)
S3V1 = RaftConfig(n_vals=1, max_election=1, max_restart=1)
S3121 = RaftConfig(n_vals=1, max_election=2, max_restart=1)


def _quad(res):
    return (res.ok, res.distinct, res.generated, res.depth,
            tuple(res.level_sizes))


# -- fused-vs-staged bit-identical parity ---------------------------------

def test_fused_vs_staged_s2_fixpoint():
    # superstep=1 pins the PER-LEVEL fused path (the multi-level
    # driver is default-on and has its own suite, tests/test_superstep
    # .py) — this row asserts every level ran through the per-level
    # megakernel
    a = JaxChecker(S2, chunk=64, megakernel=False).run()
    chk = JaxChecker(S2, chunk=64, megakernel=True, superstep=1)
    b = chk.run()
    assert _quad(a) == _quad(b)
    assert a.action_counts == b.action_counts
    assert b.distinct == 50 and b.depth == 12
    # every level (including the fixpoint-discovery one) ran fused
    assert chk._mega_stats["levels"] == b.depth + 1


def test_fused_vs_staged_s3v1_fixpoint():
    a = JaxChecker(S3V1, chunk=256, megakernel=False).run()
    b = JaxChecker(S3V1, chunk=256, megakernel=True).run()
    assert _quad(a) == _quad(b)
    assert b.distinct == 545  # the pinned S3V1 fixpoint


def test_fused_vs_staged_3121_prefix():
    a = JaxChecker(S3121, chunk=256, megakernel=False).run(max_depth=9)
    b = JaxChecker(S3121, chunk=256, megakernel=True).run(max_depth=9)
    assert _quad(a) == _quad(b)


@pytest.mark.slow
def test_fused_golden_full_3121():
    """GOLDEN_FULL acceptance: the fused path lands exactly on the
    dual-verified (3,1,2,1) fixpoint totals."""
    res = JaxChecker(S3121, chunk=1024, megakernel=True).run()
    assert (res.distinct, res.generated, res.depth) == (
        180_582, 747_500, 35,
    )


# -- overflow classes re-enter grow-and-redo ------------------------------

def test_slab_overflow_grows_and_redoes(monkeypatch):
    """A deliberately tiny slab with between-level growth disabled:
    probe windows MUST fill mid-level, and the fused path must discard
    the pending slab, grow the original and redo bit-identically."""
    monkeypatch.setattr(hashstore, "MIN_CAP", 16)
    monkeypatch.setattr(
        DeviceHashStore, "need_grow", lambda self, extra=0: False
    )
    chk = JaxChecker(S2, chunk=64, megakernel=True, superstep=1)
    res = chk.run()
    assert (res.distinct, res.depth) == (50, 12)
    assert chk._mega_stats["redo_slab"] > 0


def test_cap_out_overflow_exact_redo(monkeypatch):
    """An under-forecast output capacity redoes ONCE with the exact
    count from the control fetch (n_new is already known)."""
    orig = JaxChecker._mega_cap_out

    def tiny_guess(self, n_f, level_sizes, max_depth, n_lanes, floor):
        # first attempt always guesses the minimum rung; the redo's
        # exact floor must then land the level
        return orig(self, 1, [1], None, n_lanes, floor)

    monkeypatch.setattr(JaxChecker, "_mega_cap_out", tiny_guess)
    # chunk=2: the minimum rung (the 4*chunk one-shape floor) is 8,
    # under the S2 peak level of 9 — the forced guess must overflow
    chk = JaxChecker(S2, chunk=2, megakernel=True, superstep=1)
    res = chk.run()
    assert (res.distinct, res.depth) == (50, 12)
    assert chk._mega_stats["redo_out"] > 0


def test_cap_x_overflow_grows_and_redoes():
    chk = JaxChecker(S2, chunk=64, cap_x=16, megakernel=True,
                     superstep=1)
    res = chk.run()
    assert (res.distinct, res.depth) == (50, 12)
    assert chk._mega_stats["redo_x"] > 0
    assert chk.cap_x > 16


def test_cap_m_overflow_grows_and_redoes():
    # the staged reference is the pinned S3V1 fixpoint (545 distinct,
    # gated bit-identically by test_fused_vs_staged_s3v1_fixpoint) —
    # one fused run keeps this overflow row cheap in the fast tier
    chk = JaxChecker(S3V1, chunk=256, cap_m=4, megakernel=True,
                     superstep=1)
    res = chk.run()
    assert (res.distinct, res.depth) == (545, 19)
    assert chk._mega_stats["redo_m"] > 0
    assert chk.cap_m > 4


def test_grow_failure_degrades_to_staged():
    """An injected ``hashstore.grow`` fault mid-fused-level must
    degrade to the sort-based staged path and still converge with
    identical counts (never mid-run death)."""
    faults.install("hashstore.grow:fail@1")
    try:
        import unittest.mock as mock

        with mock.patch.object(hashstore, "MIN_CAP", 16), \
             mock.patch.object(
                 DeviceHashStore, "need_grow",
                 lambda self, extra=0: False,
             ):
            chk = JaxChecker(S2, chunk=64, megakernel=True,
                             superstep=1)
            res = chk.run()
    finally:
        faults.install("")
    assert (res.distinct, res.depth) == (50, 12)
    assert chk.megakernel is False and chk.use_hashstore is False


# -- violation / abort stop-point parity ----------------------------------

def test_split_brain_abort_stop_point_parity():
    cfg = RaftConfig(n_servers=3, n_vals=1, max_election=2,
                     max_restart=0, mutations=("double-vote",))
    a = JaxChecker(cfg, chunk=256, megakernel=False).run()
    b = JaxChecker(cfg, chunk=256, megakernel=True).run()
    assert _quad(a) == _quad(b)
    assert not b.ok
    assert a.violation[0] == b.violation[0] == (
        'Assert "split brain" (Raft.tla:185)'
    )
    assert len(a.violation[1]) == len(b.violation[1])


@pytest.mark.slow
def test_invariant_violation_stop_point_parity():
    """Slow tier: the fast tier keeps the split-brain abort stop-point
    gate above (same control-vector plumbing); the median-bug run
    expands to depth 11 twice and rides with the heavy rows."""
    cfg = RaftConfig(n_servers=3, n_vals=2, max_election=2,
                     max_restart=1, mutations=("median-bug",))
    a = JaxChecker(cfg, chunk=256, megakernel=False).run()
    b = JaxChecker(cfg, chunk=256, megakernel=True).run()
    assert _quad(a) == _quad(b)
    assert a.violation[0] == b.violation[0] == "Invariant Inv is violated"
    assert len(a.violation[1]) == len(b.violation[1])


# -- service bucket fusion ------------------------------------------------

@pytest.mark.slow  # tier-1 budget (PR 20): single-config fused-vs-
# staged parity stays fast above, and test_service's fast batched
# parity row runs the shipped fused bucket path; the staged-bucket
# cross rides with the heavy rows
def test_bucket_fused_vs_staged_parity():
    """The service slice of the fusion: a mixed-MaxRestart bucket's
    per-config summaries must be bit-identical between the fused
    (one program + one fetch per level) and staged (step + mat) paths,
    and the fused path must dispatch exactly one program per level."""
    from tla_raft_tpu.service.bucket import BatchedChecker

    cfgs = [
        RaftConfig(n_servers=2, n_vals=1, max_election=1, max_restart=mr)
        for mr in (0, 1, 2)
    ]
    a = BatchedChecker(cfgs, megakernel=False).run()
    chk = BatchedChecker(cfgs, megakernel=True, superstep=1)
    b = chk.run()
    keys = ("ok", "distinct", "generated", "depth", "level_sizes",
            "violation")
    for ra, rb in zip(a, b):
        assert {k: ra[k] for k in keys} == {k: rb[k] for k in keys}
    assert chk.stats["dispatches"] == (
        chk.stats["levels"] + chk.stats["redos"]
    )


# -- crash + recover on the fused path ------------------------------------

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


def _run_cli(args, fault=None, timeout=240):
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


def test_level_start_kill_recover_fused(tmp_path):
    """SIGKILL at the 4th level boundary on the fused path; --recover
    must replay the delta log and converge on the pinned fixpoint."""
    cfg = tmp_path / "tiny.cfg"
    cfg.write_text(CFG_2111)
    ck = str(tmp_path / "ck")
    common = [
        "--config", str(cfg), "--chunk", "64", "--megakernel", "1",
        "--superstep", "1",
        "--checkpoint-dir", ck, "--log", "-", "--json",
    ]
    killed = _run_cli(common, fault="level.start:kill@4")
    assert killed.returncode != 0, "the planted kill never fired"
    rec = _run_cli(common + ["--recover", ck])
    assert rec.returncode == 0, rec.stdout[-2000:] + rec.stderr[-2000:]
    got = _json_line(rec)
    assert (got["ok"], got["distinct"], got["depth"]) == (True, 50, 12)
    assert got["megakernel"] is True


# -- the headline claim: ONE program + ONE fetch per steady level ---------

def test_sanitize_smoke_one_dispatch_one_fetch(tmp_path):
    """GRAFT_SANITIZE acceptance on the fused path: zero post-warmup
    recompiles, zero unledgered transfers, and the per-level ledger
    showing every steady-state level as exactly one engine program
    dispatch + one ledgered fetch."""
    cfg = tmp_path / "tiny.cfg"
    cfg.write_text(CFG_2111)
    env = dict(os.environ)
    env.update(
        GRAFT_SANITIZE="1", JAX_PLATFORMS="cpu",
        TLA_RAFT_MEGAKERNEL="1",
        # pin the PER-LEVEL fused path: supersteps are default-on and
        # would otherwise run engine/superstep.py under this gate
        TLA_RAFT_SUPERSTEP="1",
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
    assert (
        "steady-state max 1 dispatch(es) and 1 ledgered fetch(es) "
        "per level" in proc.stdout
    ), proc.stdout


def test_summary_reports_program_cache_counters():
    """A check after a same-config check in one process builds no
    program: the summary's program-cache counters show only hits, for
    the superstep driver and the per-level program alike."""
    from tla_raft_tpu.check import run_check, summary_public

    run_check(S2, chunk=64, telemetry=False)
    s = summary_public(run_check(S2, chunk=64, telemetry=False))
    assert s["distinct"] == 50
    for block in ("superstep_stats", "megakernel_stats"):
        assert s[block]["program_builds"] == 0, s[block]
        assert s[block]["program_hits"] >= 1, s[block]


def test_dispatch_log_counts_fused_levels():
    """The choke-point dispatch ledger (GL011's measurement) sees the
    fused path as exactly one program per level."""
    from tla_raft_tpu.analysis.sanitize import (
        DispatchLog,
        set_dispatch_sink,
    )

    log = DispatchLog()
    set_dispatch_sink(log)
    try:
        res = JaxChecker(
            S2, chunk=64, megakernel=True, superstep=1
        ).run()
    finally:
        set_dispatch_sink(None)
    log.close()
    assert res.distinct == 50
    assert log.steady_max() == 1
    assert log.tags.get("megakernel.level") == res.depth + 1
