"""Spill sieve + its arm/stand-down governor + spilled frontiers + LSM
compaction (ops/sieve.py, store/tiered.py side-cars/compaction,
engine/bfs.py FrontierPager).

Fast rows share ONE (3,1,2,1) depth-14 forced-spill engine pair — the
hot budget ~5x under |visited| forces >= 2 whole-generation demotions,
the tiny warm budget drops every generation cold, fanout 2 forces LSM
compactions, and the frontier paging knobs stream the two widest levels
through host segments with disk spill — so one pair of runs feeds the
sieve-span, compaction-bound, side-car and spilled-frontier rows inside
the tier-1 wall budget.  The subprocess kill/flip and mesh rows are
@slow.
"""

import glob
import json
import os
import subprocess
import sys
import textwrap
import types

import numpy as np
import pytest

import jax.numpy as jnp

from tla_raft_tpu.config import RaftConfig
from tla_raft_tpu.engine import JaxChecker
from tla_raft_tpu.ops import hashstore  # noqa: F401  (x64 before u64 work)
from tla_raft_tpu.ops import sieve as sieve_mod
from tla_raft_tpu.store import tiered

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

S3121 = RaftConfig(n_servers=3, n_vals=1, max_election=2, max_restart=1)

# 16 KiB hot budget = a 2048-slot slab = 1023 resident entries: the
# depth-14 prefix's 10,752 distinct states overflow it ~10x even after
# the soft over-budget doublings, forcing demotions from level 10 on
BUDGET = 16 * 1024

# the shared pair's spill regime: 2 MiB frontier budget streams the two
# widest levels (13-14) as 256-row segments while levels 10-12 stay in
# superstep windows under spill (the sieve's span recovery); the 32 KiB
# host budget pushes streamed segments to disk (kind="fseg"); warm 64 B
# drops every generation cold and fanout 2 forces compactions
KNOBS = {
    "TLA_RAFT_DEV_BYTES": str(2 * 1024 * 1024),
    "TLA_RAFT_FSEG_ROWS": "256",
    "TLA_RAFT_FSEG_BYTES": str(32 * 1024),
    "TLA_RAFT_COMPACT_FANOUT": "2",
    "TLA_RAFT_WARM_BYTES": "64",
}

CFG_3121 = textwrap.dedent(
    """
    CONSTANTS
        MaxTerm = 3
        MaxRestart = 1
        MaxElection = 2
        Follower = Follower
        Candidate = Candidate
        Leader = Leader
        None = None
        VoteReq = VoteReq
        VoteResp = VoteResp
        AppendReq = AppendReq
        AppendResp = AppendResp
        s1 = s1
        s2 = s2
        s3 = s3
        Servers = {s1, s2, s3}
        v1 = v1
        Vals = {v1}

    SYMMETRY symmServers
    VIEW view

    INIT Init
    NEXT Next

    INVARIANT
    Inv
    """
)


def _run_cli(args, fault=None, devices=1, env_extra=None, timeout=300):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    if fault is not None:
        env["TLA_RAFT_FAULT"] = fault
    else:
        env.pop("TLA_RAFT_FAULT", None)
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "-m", "tla_raft_tpu.check", *args],
        env=env, capture_output=True, text=True, timeout=timeout,
    )


def _json_line(proc):
    for line in reversed(proc.stdout.splitlines()):
        if line.startswith("{"):
            return json.loads(line)
    raise AssertionError(
        f"no JSON summary in output:\n{proc.stdout}\n{proc.stderr}"
    )


# -- the ONE shared forced-spill engine pair ------------------------------


# the uncapped depth-14 reference, pinned once (deterministic: the same
# JaxChecker(S3121, chunk=256).run(max_depth=14) every run; re-measure
# with that one-liner if the engine's counts ever legitimately move) —
# pinning it saves the ~20 s hot arm from the module fixture, which is
# what keeps this module inside the tier-1 wall budget
HOT_3121_D14 = types.SimpleNamespace(
    distinct=10752,
    generated=27675,
    depth=14,
    level_sizes=(
        1, 1, 3, 6, 12, 22, 49, 112, 241, 443, 719, 1111, 1720, 2612,
        3700,
    ),
)


@pytest.fixture(scope="module")
def spill_pair(tmp_path_factory):
    hot = HOT_3121_D14
    old = {k: os.environ.get(k) for k in KNOBS}
    os.environ.update(KNOBS)
    try:
        ck = str(tmp_path_factory.mktemp("sieve_ck"))
        chk = JaxChecker(S3121, chunk=256, store_bytes=BUDGET)
        res = chk.run(max_depth=14, checkpoint_dir=ck, checkpoint_every=1)
    finally:
        for k, v in old.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    return hot, res, chk, ck


def test_spill_counts_bit_identical(spill_pair):
    hot, res, chk, _ck = spill_pair
    assert (res.distinct, res.generated, res.depth) == (
        hot.distinct, hot.generated, hot.depth,
    )
    assert res.level_sizes == hot.level_sizes
    # and it genuinely spilled, several times over
    st = chk.tiered.stats
    assert st["demotions"] >= 2, st
    assert st["spilled"] > res.distinct  # re-demotions re-spill reheats


def test_superstep_span_survives_spill(spill_pair):
    """The tentpole claim: with the sieve on, the resident superstep
    keeps running windows AFTER generations exist (PR 12 stood down to
    span 1 at the first demotion), and a window with in-kernel sieve
    hits stops for the exact per-level correction instead of committing
    a possibly-wrong level."""
    _hot, _res, chk, _ck = spill_pair
    assert chk.sieve_enabled
    ss = chk._ss_stats
    # windows kept launching after the level-10 first demotion: three
    # pre-spill windows cover levels 1-9 at span 4, so any count above
    # that is a window armed under spill
    assert ss["supersteps"] > 3, ss
    # dispatch amortization survived: more levels committed in-window
    # than windows dispatched (span > 1 on average)
    assert ss["levels"] > ss["supersteps"] // 2, ss
    # the exactness protocol fired: possible spilled revisits stopped
    # the window (host replay), never committed blind
    assert ss.get("sieve_stops", 0) >= 1, ss
    # the sieve image actually reached the device operand path
    assert chk._dev_sieve is not None
    assert chk.tiered.spill_sieve is not None
    assert chk.tiered.spill_sieve.n_added == chk.tiered.stats["spilled"]


def test_compaction_bounds_cold_runs(spill_pair):
    """LSM generation merge: with fanout 2 and every generation cold,
    the cold-run count is bounded by the fanout instead of growing one
    run per demotion — and each surviving run has a bloom side-car
    committed beside it."""
    _hot, _res, chk, ck = spill_pair
    st = chk.tiered.stats
    assert st["demotions"] >= 4, st
    assert st["compactions"] >= 1, st
    assert st["compact_runs"] > st["compactions"], st  # merged > 1 run
    cold = [g for g in chk.tiered.gens if g.cold]
    assert len(cold) <= chk.tiered.compact_fanout, (
        len(cold), chk.tiered.compact_fanout,
    )
    runs = [p for p in glob.glob(os.path.join(ck, "gen_*.npz"))
            if not p.endswith(tiered.SIDECAR_SUFFIX)]
    cars = [p for p in glob.glob(os.path.join(ck, "gen_*.npz"))
            if p.endswith(tiered.SIDECAR_SUFFIX)]
    assert len(runs) == len(chk.tiered.gens)
    assert len(cars) == len(runs)  # one side-car per surviving run


def test_spilled_frontier_streams_and_retires(spill_pair):
    """Spilled frontiers: the two widest levels ran segment-streamed
    through the fused program (multiple mega dispatches per level), the
    host segments paged through disk under the 32 KiB budget, and every
    transient fseg artifact was retired by the end of the run."""
    _hot, _res, chk, ck = spill_pair
    ms = chk._mega_stats
    assert ms.get("seg_levels", 0) >= 1, ms
    assert ms.get("seg_dispatches", 0) > ms.get("seg_levels", 0), ms
    ps = chk._fpager.stats
    assert ps["fseg_spills"] >= 1, ps
    assert ps["fseg_loads"] >= 1, ps
    assert ps["fseg_bytes"] > 0
    assert chk._fpager.live == 0
    assert not glob.glob(os.path.join(ck, tiered.FSEG_PREFIX + "*.npz"))


# -- sieve/store units (numpy, milliseconds) ------------------------------


def test_sieve_no_false_negatives_and_fp_rate():
    """The one thing a sieve must never do is report a false negative;
    and at the side-car design load the measured false-positive rate
    tracks the Poisson-mixture prediction (docs/PERF.md)."""
    rng = np.random.default_rng(7)
    keys = rng.integers(1, 2**63, 20_000, dtype=np.uint64)
    sv = sieve_mod.SpillSieve.build(keys)
    assert sv.contains(keys).all()  # no false negatives, ever
    fresh = rng.integers(1, 2**63, 50_000, dtype=np.uint64)
    fresh = fresh[~np.isin(fresh, keys)]
    rate = float(sv.contains(fresh).mean())
    predicted = sv.fp_rate()
    assert predicted < 0.02, predicted  # >= 12 bits/key sizing
    assert rate < max(2.5 * predicted, 0.005), (rate, predicted)


def test_sieve_device_probe_matches_numpy_mirror():
    """Host builder / numpy mirror / device probe share ONE hash
    pipeline — any drift would manufacture false negatives."""
    rng = np.random.default_rng(11)
    keys = rng.integers(1, 2**63, 4096, dtype=np.uint64)
    sv = sieve_mod.SpillSieve(1 << 10)
    sv.add(keys)
    qry = np.concatenate([
        keys[:500], rng.integers(1, 2**63, 2000, dtype=np.uint64),
    ])
    host = sv.contains(qry)
    dev = np.asarray(
        sieve_mod.probe_impl(jnp.asarray(sv.words), jnp.asarray(qry))
    )
    assert (host == dev).all()


def test_sidecar_skip_avoids_cold_load(tmp_path):
    """A cold probe consults the committed side-car BEFORE paging the
    run in: an IN-RANGE fingerprint (past the free [lo, hi] reject)
    whose side-car says definite-miss never touches disk
    (sidecar_skips); a side-car hit still gets the exact searchsorted
    verdict."""
    st = tiered.TieredVisitedStore(
        8 * 1024, warm_bytes=64, spill_dir=str(tmp_path),
    )
    # even fingerprints only: the odd in-range queries below are
    # definite misses the side-cars reject without a disk load
    st.demote(np.arange(100, 300, 2, dtype=np.uint64), depth=3)
    st.demote(np.arange(1000, 1200, 2, dtype=np.uint64), depth=5)
    assert all(g.cold for g in st.gens)
    before = st.stats["cold_loads"]
    miss = st.probe(np.asarray([101, 1001], np.uint64))
    assert not miss.any()
    assert st.stats["cold_loads"] == before
    assert st.stats["sidecar_skips"] >= 2
    # a real member still verifies exactly (side-car hit -> disk)
    hit = st.probe(np.asarray([150], np.uint64))
    assert hit.all()
    assert st.stats["cold_loads"] > before


def test_corrupt_sidecar_quarantined_and_rebuilt(tmp_path):
    """A torn/flipped side-car must never poison probes: the store
    quarantines it (manifest digest catches the corruption) and
    rebuilds from the membership-authoritative run."""
    st = tiered.TieredVisitedStore(
        8 * 1024, warm_bytes=64, spill_dir=str(tmp_path),
    )
    st.demote(np.arange(100, 300, 2, dtype=np.uint64), depth=3)
    car = glob.glob(
        os.path.join(str(tmp_path), "*" + tiered.SIDECAR_SUFFIX)
    )
    assert len(car) == 1
    with open(car[0], "r+b") as f:  # latent media corruption
        f.seek(60)
        b = f.read(1)
        f.seek(60)
        f.write(bytes([b[0] ^ 0xFF]))
    # drop the warm in-memory copy: a RESUMED incarnation only has the
    # committed file, which is exactly when corruption can bite
    st.gens[0].sidecar = None
    hit = st.probe(np.asarray([150, 101], np.uint64))
    assert hit.tolist() == [True, False]  # verdicts stay exact
    assert st.stats["sidecar_rebuilds"] >= 1
    # the rebuilt (in-memory) side-car skips in-range misses again
    st.probe(np.asarray([103], np.uint64))
    assert st.stats["sidecar_skips"] >= 1


def test_compaction_ledger_and_fault_sites_registered():
    from tla_raft_tpu.analysis import jaxpr_audit
    from tla_raft_tpu.resilience import faults

    assert "ops.sieve_probe" in jaxpr_audit.GL010_KERNELS
    gold = jaxpr_audit.load_golden()
    assert gold and "ops.sieve_probe" in gold
    for site in ("compact.tmp", "compact.commit", "sieve.tmp",
                 "sieve.commit", "fseg.tmp", "fseg.commit"):
        assert site in faults.FAULT_SITES, site


def test_sweep_clears_orphan_fsegs_and_sidecars(tmp_path):
    d = str(tmp_path)
    for name in ("fseg_00000.npz", "fseg_00007.npz"):
        np.savez(os.path.join(d, name), x=np.zeros(1))
    np.savez(os.path.join(d, "gen_0000.npz"), fps=np.zeros(1, np.uint64))
    np.savez(os.path.join(d, "gen_0000" + tiered.SIDECAR_SUFFIX),
             words=np.zeros(8, np.uint64))
    assert tiered.sweep_fsegs(d) == 2
    assert not glob.glob(os.path.join(d, "fseg_*"))
    # gen sweep takes run AND side-car (stale generations are noise;
    # the delta log is the source of truth on resume)
    assert tiered.sweep_gens(d) == 2
    assert not glob.glob(os.path.join(d, "gen_*"))


# -- adaptive sieve governor ----------------------------------------------

def test_governor_modes_from_env(monkeypatch):
    monkeypatch.delenv("TLA_RAFT_SIEVE", raising=False)
    assert sieve_mod.mode_from_env() == "auto"
    assert sieve_mod.mode_from_env(True) == "on"
    assert sieve_mod.mode_from_env(False) == "off"
    monkeypatch.setenv("TLA_RAFT_SIEVE", "0")
    assert sieve_mod.mode_from_env() == "off"
    monkeypatch.setenv("TLA_RAFT_SIEVE", "1")
    assert sieve_mod.mode_from_env() == "on"
    # explicit argument still forces over env
    assert sieve_mod.mode_from_env(False) == "off"


def test_governor_stand_down_and_rearm():
    gov = sieve_mod.SieveGovernor("auto")
    assert gov.armed
    # clean windows: stays armed forever
    for i in range(10):
        gov.note_window(sieve_stop=False, level=i)
    assert gov.armed and gov.stats["stand_downs"] == 0
    # dense sieve-dirty stops: stands down at the density threshold
    for i in range(sieve_mod.MIN_WINDOWS):
        gov.note_window(sieve_stop=True, level=20 + i)
    assert not gov.armed and gov.stats["stand_downs"] == 1
    # stood down: windows are not recorded, probation ticks are
    gov.note_window(sieve_stop=True, level=30)
    assert gov.stats["stand_downs"] == 1
    gov.note_level(30)
    assert not gov.armed
    gov.note_level(23 + sieve_mod.REARM_LEVELS)
    assert gov.armed and gov.stats["rearms"] == 1
    snap = gov.snapshot()
    assert snap["mode"] == "auto" and snap["armed"] is True


def test_governor_forced_modes_never_move():
    on = sieve_mod.SieveGovernor("on")
    for i in range(20):
        on.note_window(sieve_stop=True, level=i)
    assert on.armed and on.stats["stand_downs"] == 0
    off = sieve_mod.SieveGovernor("off")
    assert not off.armed
    off.note_level(100)
    assert not off.armed and off.stats["rearms"] == 0


def test_governor_emits_events(tmp_path):
    from tla_raft_tpu.obs import telemetry as tel

    d = str(tmp_path)
    with tel.TelemetryHub(run_dir=d) as hub:
        tel.install(hub)
        try:
            gov = sieve_mod.SieveGovernor("auto")
            for i in range(sieve_mod.MIN_WINDOWS):
                gov.note_window(sieve_stop=True, level=i)
            gov.note_level(sieve_mod.MIN_WINDOWS - 1
                           + sieve_mod.REARM_LEVELS)
        finally:
            tel.install(None)
    events, _ = tel.read_events(os.path.join(d, "events.jsonl"))
    kinds = [e["ev"] for e in events]
    assert "sieve_standdown" in kinds and "sieve_arm" in kinds
    sd = next(e for e in events if e["ev"] == "sieve_standdown")
    assert sd["density"] >= sieve_mod.STAND_DOWN_DENSITY
    assert sd["windows"] >= sieve_mod.MIN_WINDOWS


# -- subprocess rows (slow tier) ------------------------------------------


@pytest.mark.slow
def test_sigkill_mid_compaction_recovers_bit_identical(tmp_path):
    """SIGKILL inside the compaction commit window (compact.tmp — the
    merged run's tmp written, not renamed): the input runs are still
    live, so --recover rebuilds every tier from the delta log and
    completes bit-identical to the uncapped sweep."""
    cfgp = tmp_path / "Tiny.cfg"
    cfgp.write_text(CFG_3121)
    ck = str(tmp_path / "ck")
    env_extra = {
        "TLA_RAFT_COMPACT_FANOUT": "2",
        "TLA_RAFT_WARM_BYTES": "64",
    }
    base = [
        "--config", str(cfgp), "--max-depth", "10", "--chunk", "256",
        "--checkpoint-dir", ck, "--dev-bytes", "4096", "--log", "-",
        "--json",
    ]
    first = _run_cli(base, fault="compact.tmp:kill@1",
                     env_extra=env_extra)
    assert first.returncode not in (0, 1, 2, 3, 4), (
        f"compact.tmp kill did not fire:\n{first.stdout}\n{first.stderr}"
    )
    assert glob.glob(os.path.join(ck, "delta_*.npz"))
    rec = _run_cli(base + ["--recover", ck], env_extra=env_extra)
    assert rec.returncode == 0, rec.stdout + rec.stderr
    got = _json_line(rec)
    hot = JaxChecker(S3121, chunk=256).run(max_depth=10)
    assert got["distinct"] == hot.distinct
    assert got["generated"] == hot.generated
    assert got["level_sizes"] == list(hot.level_sizes)
    assert not glob.glob(os.path.join(ck, ".tmp_*"))


@pytest.mark.slow
def test_sidecar_flip_at_commit_is_harmless_and_detectable(tmp_path):
    """A side-car byte-flipped at its commit site (sieve.commit —
    latent media corruption of the just-renamed artifact): the sweep
    still converges bit-identical with rc 0 (side-cars are pure
    acceleration state — the run's warm in-memory filter serves the
    incarnation that built it, and a resume discards + rebuilds
    committed side-cars wholesale), and the manifest digest DETECTS the
    corrupted artifact — the detection that drives the store-level
    quarantine + rebuild-from-generation fallback
    (test_corrupt_sidecar_quarantined_and_rebuilt)."""
    from tla_raft_tpu.resilience import manifest as _manifest

    cfgp = tmp_path / "Tiny.cfg"
    cfgp.write_text(CFG_3121)
    ck = str(tmp_path / "ck")
    # default fanout (8): no compaction at this scale, so the flipped
    # first side-car survives to the end of the run for inspection
    env_extra = {"TLA_RAFT_WARM_BYTES": "64"}
    run = _run_cli(
        [
            "--config", str(cfgp), "--max-depth", "10", "--chunk",
            "256", "--checkpoint-dir", ck, "--dev-bytes", "4096",
            "--log", "-", "--json",
        ],
        fault="sieve.commit:flip@1", env_extra=env_extra,
    )
    assert run.returncode == 0, run.stdout + run.stderr
    got = _json_line(run)
    hot = JaxChecker(S3121, chunk=256).run(max_depth=10)
    assert got["distinct"] == hot.distinct
    assert got["generated"] == hot.generated
    assert got["level_sizes"] == list(hot.level_sizes)
    cars = sorted(
        os.path.basename(p) for p in
        glob.glob(os.path.join(ck, "*" + tiered.SIDECAR_SUFFIX))
    )
    assert cars, "no side-cars committed"
    states = {c: _manifest.Manifest.load(ck).verify(c) for c in cars}
    bad = [c for c, s in states.items() if s != "ok"]
    assert len(bad) == 1, states  # the flip fired, the digest sees it


@pytest.mark.slow
def test_mesh_deep_elastic_4_to_2_respills_with_blooms(tmp_path):
    """Mesh form of the tiered sweep under elastic resume: a 4-device
    deep sweep whose per-owner native stores spilled sorted runs (each
    run carries an in-memory bloom — native/fpstore.cpp — rebuilt at
    write_run on every incarnation) is SIGKILLed mid-run and resumes
    on 2 devices: the owner remap repartitions the replayed union and
    the rebuilt stores re-spill + re-filter under the new partition,
    bit-identically."""
    from tla_raft_tpu.oracle import OracleChecker

    cfg2 = CFG_3121.replace("MaxElection = 2", "MaxElection = 1").replace(
        "        s3 = s3\n", ""
    ).replace("Servers = {s1, s2, s3}", "Servers = {s1, s2}")
    cfgp = tmp_path / "Tiny.cfg"
    cfgp.write_text(cfg2)
    golden = OracleChecker(
        RaftConfig(n_servers=2, n_vals=1, max_election=1, max_restart=1)
    ).run()
    ck = str(tmp_path / "ck")
    base = [
        "--config", str(cfgp), "--chunk", "64", "--checkpoint-dir", ck,
        "--mesh-deep", "--seg-rows", "8", "--cap-x", "256",
        "--warm-bytes", "32", "--log", "-", "--json",
    ]
    first = _run_cli(
        base + ["--mesh", "4", "--fpstore-dir", str(tmp_path / "f1")],
        fault="mdelta.commit:kill@5", devices=4,
    )
    assert first.returncode not in (0, 1, 2, 3, 4), (
        f"kill fault did not kill the run:\n{first.stdout}"
    )
    assert glob.glob(os.path.join(str(tmp_path / "f1"), "shard_*",
                                  "run_*.fp"))
    rec = _run_cli(
        base + ["--mesh", "4", "--fpstore-dir", str(tmp_path / "f2"),
                "--recover", ck],
        devices=2,
    )
    assert rec.returncode == 0, rec.stdout + rec.stderr
    got = _json_line(rec)
    assert got["ok"]
    assert got["distinct"] == golden.distinct
    assert got["generated"] == golden.generated
    assert got["level_sizes"] == list(golden.level_sizes)
    assert got["telemetry"]["tiered"]["probes"] > 0
