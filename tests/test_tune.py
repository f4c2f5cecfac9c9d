"""Autotuner + plan cache (tla_raft_tpu/tune): the cost-model-driven
search, the versioned plan cache, and the adaptive sieve governor.

One module-scope search run (tiny space, depth-capped probes through
the real run_check path) feeds every fast row here — probes are the
expensive part, so they are paid once.  The S3V1 parity fixpoint and
the service-bucket plan path ride ``@slow``.

The plan-cache invariants pinned here are the load-bearing ones:

* quarantined-and-ignored — a corrupt/torn/stale cache is exactly an
  absent one; resolution never raises and a resume never crashes;
* counts are bit-identical under ANY plan — knobs change shapes and
  schedules only, and a knob that drifts ``distinct``/``generated``/
  ``depth`` fails the search loudly;
* a detuned plan cannot land silently — its dispatch-budget regression
  flips ``obs trend --check`` non-zero.
"""

from __future__ import annotations

import os

import pytest

from tla_raft_tpu.config import RaftConfig
from tla_raft_tpu.tune import active, adaptive, plans, search

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
S2 = RaftConfig(n_servers=2, n_vals=1, max_election=1, max_restart=1)
# S2's fixpoint identity (the golden-ledger reference config)
S2_COUNTS = (50, 97, 12)


# -- shared search run: pay the probes once -------------------------------

@pytest.fixture(scope="module")
def tuned(tmp_path_factory):
    """One real coordinate-descent search on S2 (baseline + one
    candidate), committed to a tmp plan cache, with the telemetry
    flight recorder capturing the probe trail."""
    from tla_raft_tpu.obs import telemetry as tel

    d = tmp_path_factory.mktemp("tune")
    run_dir = str(d / "events")
    os.makedirs(run_dir, exist_ok=True)
    path = str(d / "plans.json")
    hub = tel.TelemetryHub(run_dir=run_dir)
    tel.install(hub)
    try:
        res = search.tune(
            S2, backend="jax", path=path, commit=True,
            max_depth=6, top_k=1,
            space={"superstep_span": [2]},
        )
    finally:
        tel.install(None)
        hub.close()
    return dict(res=res, path=path, events=os.path.join(
        run_dir, "events.jsonl"))


def test_search_result_shape(tuned):
    res = tuned["res"]
    assert res["regime"] == "cpu|raft|S2V1|b2"
    assert res["committed"] == tuned["path"]
    assert res["probe"]["probes"] == len(res["ledger"]) >= 2
    assert set(plans.defaults()) == set(res["knobs"])
    # depth-capped probes: the prefix identity, not the fixpoint
    assert res["probe"]["depth"] == 6


def test_probe_parity_enforced(tuned):
    """Every probe in the ledger saw identical counts (the in-search
    parity gate), and a drifted probe raises."""
    res = tuned["res"]
    base = res["ledger"][0]
    for rec in res["ledger"]:
        assert (rec["distinct"], rec["generated"], rec["depth"]) == (
            base["distinct"], base["generated"], base["depth"])
    with pytest.raises(RuntimeError, match="changed semantics"):
        search._check_parity(base, dict(base, distinct=base["distinct"] + 1),
                             {"chunk": 512})


def test_probe_events_emitted(tuned):
    from tla_raft_tpu.obs import telemetry as tel

    events, dropped = tel.read_events(tuned["events"])
    assert dropped == 0
    probes = [e for e in events if e["ev"] == "tune_probe"]
    assert len(probes) == tuned["res"]["probe"]["probes"]
    for e in probes:
        assert e["regime"] == "cpu|raft|S2V1|b2"
        assert e["knobs"]["superstep_span"] in (2, 4)
        assert e["metric"] > 0 and e["ok"] is True


def test_plan_cache_roundtrip(tuned):
    doc = plans.load_cache(tuned["path"])
    assert doc["schema"] == plans.SCHEMA and doc["version"] == 1
    knobs = plans.resolve(S2, "jax", path=tuned["path"])
    assert knobs == tuned["res"]["knobs"]
    # re-commit folds (other regimes kept, version bumps)
    plans.commit(tuned["path"], "cpu|raft|S9V9|b0", {"chunk": 2048})
    doc = plans.load_cache(tuned["path"])
    assert doc["version"] == 2 and len(doc["plans"]) == 2
    assert plans.resolve(S2, "jax", path=tuned["path"]) == knobs


def test_run_check_under_plan_bit_identical(tuned):
    """The committed winner applied through run_check reproduces the
    fixpoint identity exactly (counts are the hard gate; the plan only
    reshapes schedules)."""
    from tla_raft_tpu.check import run_check

    summary = run_check(S2, plan=tuned["path"])
    assert (summary["distinct"], summary["generated"],
            summary["depth"]) == S2_COUNTS
    assert summary["ok"] is True
    assert summary["plan"] == tuned["res"]["knobs"]
    # plan off (the pre-tuner repo): same identity, no plan block
    off = run_check(S2, plan=False)
    assert (off["distinct"], off["generated"], off["depth"]) == S2_COUNTS
    assert "plan" not in off


def test_detuned_plan_flips_trend_gate(tuned, tmp_path, capsys):
    """A detuned plan (span 1 = no superstep amortization) regresses
    levels/dispatch, and the committed-history gate catches the record:
    a bad plan cannot land silently even though counts stay identical."""
    from tla_raft_tpu.check import run_check
    from tla_raft_tpu.obs.__main__ import main as obs_main

    good = run_check(S2, plan=tuned["path"], telemetry=True)
    bad = run_check(
        S2, plan={"superstep_span": 1, "pipeline_window": 1},
        telemetry=True,
    )
    # detuned counts are STILL identical — that is the wrong gate here
    assert (bad["distinct"], bad["depth"]) == (good["distinct"],
                                               good["depth"])
    d = str(tmp_path / "bench")

    def rec(round_no, s):
        t = s["telemetry"]
        return dict(
            schema="tla-raft-trend/1", round=round_no,
            metric="plan_s2", config=S2.describe(),
            distinct=s["distinct"], generated=s["generated"],
            depth=s["depth"], wall_s=1.0, rate=1.0,
            parity=True, ok=True,
            levels_per_dispatch=t["levels"] / max(1, t["dispatches"]),
        )

    from tla_raft_tpu.obs import trend
    trend.append_record(rec(1, good), d)
    assert obs_main(["trend", d, "--check"]) == 0
    trend.append_record(rec(2, bad), d)
    assert obs_main(["trend", d, "--check"]) == 1
    capsys.readouterr()


# -- pure cache/registry rows (no engine) ---------------------------------

def test_clamp_types_and_bounds():
    got = plans.clamp({
        "chunk": "4096", "cap_margin": 99, "probe_window": 1,
        "superstep_span": 7.9, "unknown_knob": 5, "min_bucket": None,
    })
    assert got == {
        "chunk": 4096, "cap_margin": 2.0, "probe_window": 2,
        "superstep_span": 7,
    }
    assert plans.clamp(None) == {}
    d = plans.defaults()
    assert plans.clamp(d) == d


def test_regime_key_and_fallback():
    assert plans.regime_key(S2, "jax") == "cpu|raft|S2V1|b2"
    big = RaftConfig(n_servers=3, n_vals=2, max_election=3,
                     max_restart=1)
    key = plans.regime_key(big, "cpu")
    assert key == "cpu|raft|S3V2|b3"
    # fallback walks SMALLER budget classes only, nearest first
    assert plans._fallback_keys(key) == [
        "cpu|raft|S3V2|b3", "cpu|raft|S3V2|b2",
        "cpu|raft|S3V2|b1", "cpu|raft|S3V2|b0",
    ]


def test_fallback_resolution_smaller_budget_only(tmp_path):
    path = str(tmp_path / "plans.json")
    plans.commit(path, "cpu|raft|S2V1|b1", {"chunk": 2048})
    plans.commit(path, "cpu|raft|S2V1|b4", {"chunk": 8192})
    # S2 is b2: the b1 plan transfers up, the b4 plan never flows down
    assert plans.resolve(S2, "jax", path=path)["chunk"] == 2048


def test_corrupt_and_stale_plans_quarantined(tmp_path):
    # missing
    missing = str(tmp_path / "nope" / "plans.json")
    assert plans.load_cache(missing) is None
    assert plans.resolve(S2, "jax", path=missing) == {}
    # torn/corrupt bytes (no manifest digest at all)
    corrupt = tmp_path / "plans.json"
    corrupt.write_text("{broken json", encoding="utf-8")
    assert plans.load_cache(str(corrupt)) is None
    assert plans.resolve(S2, "jax", path=str(corrupt)) == {}
    # digest-valid but schema-stale document
    from tla_raft_tpu import resilience
    d2 = tmp_path / "stale"
    d2.mkdir()
    resilience.commit_json(str(d2), "plans.json",
                           {"schema": "tla-raft-plan/0", "plans": {}},
                           kind=plans.PLAN_KIND)
    assert plans.load_cache(str(d2 / "plans.json")) is None
    # committed-then-mangled: digest mismatch == quarantined
    d3 = tmp_path / "mangled"
    d3.mkdir()
    plans.commit(str(d3 / "plans.json"), "cpu|raft|S2V1|b2",
                 {"chunk": 2048})
    p3 = d3 / "plans.json"
    p3.write_text(p3.read_text().replace("2048", "4096"),
                  encoding="utf-8")
    assert plans.resolve(S2, "jax", path=str(p3)) == {}


def test_out_of_range_plan_values_clamped(tmp_path):
    """A hand-mangled (or adversarially detuned) plan can make a run
    slow but never hand a kernel a nonsense shape."""
    path = str(tmp_path / "plans.json")
    plans.commit(path, "cpu|raft|S2V1|b2",
                 {"chunk": 10 ** 9, "probe_window": 0,
                  "cap_margin": 0.1})
    got = plans.resolve(S2, "jax", path=path)
    assert got["chunk"] == 1 << 16
    assert got["probe_window"] == 2
    assert got["cap_margin"] == 1.05


def test_active_registry_precedence(monkeypatch):
    from tla_raft_tpu.engine import pipeline, superstep
    from tla_raft_tpu.engine.forecast import cap_margin

    assert active.installed() is None
    assert active.get("chunk", 7) == 7  # no plan -> hand-set default
    active.install({"superstep_span": 8, "pipeline_window": 4,
                    "cap_margin": 1.5})
    try:
        assert superstep.span_from_env() == 8
        assert pipeline.window_from_env() == 4
        assert cap_margin() == 1.5
        # explicit env always beats the plan
        monkeypatch.setenv("TLA_RAFT_SUPERSTEP", "2")
        monkeypatch.setenv("TLA_RAFT_PIPELINE_WINDOW", "1")
        monkeypatch.setenv("TLA_RAFT_CAP_MARGIN", "1.1")
        assert superstep.span_from_env() == 2
        assert pipeline.window_from_env() == 1
        assert cap_margin() == 1.1
    finally:
        active.clear()
    assert active.installed() is None


def test_probe_window_setter_restores():
    from tla_raft_tpu.ops import hashstore

    assert hashstore.probe_window() == hashstore.DEFAULT_PROBE_WINDOW
    hashstore.set_probe_window(16)
    try:
        assert hashstore.probe_window() == 16
    finally:
        hashstore.set_probe_window(None)
    assert hashstore.probe_window() == hashstore.DEFAULT_PROBE_WINDOW


def test_prior_ranks_and_prunes():
    from tla_raft_tpu.tune import prior

    base = plans.defaults()
    cands = [dict(base, chunk=c) for c in (512, 1024, 4096)]
    ranked, pruned = prior.rank(cands, rows=512, distinct=10_000,
                                dev_bytes=None)
    assert not pruned and len(ranked) == 3
    # an absurd capacity knob trips the pre-OOM forecast prune
    huge = [dict(base, cap_margin=2.0, chunk=1 << 16)]
    _, pruned = prior.rank(huge, rows=1 << 22, distinct=1 << 24,
                           dev_bytes=1 << 20, budget=1 << 20)
    assert pruned


def test_committed_default_plan_cache_readable():
    """The cache shipped with the package must be digest-valid and
    carry the reference regime (a stale shipped cache would silently
    revert every default run to hand-set knobs)."""
    path = os.path.join(REPO, "tla_raft_tpu", "tune", plans.PLAN_NAME)
    assert os.path.exists(path), "committed default plan cache missing"
    doc = plans.load_cache(path)
    assert doc is not None, "shipped plan cache failed verification"
    assert "cpu|raft|S2V1|b2" in doc["plans"]
    for ent in doc["plans"].values():
        assert plans.clamp(ent["knobs"]) == ent["knobs"]


# -- adaptive sieve governor ----------------------------------------------

def test_governor_modes_from_env(monkeypatch):
    monkeypatch.delenv("TLA_RAFT_SIEVE", raising=False)
    assert adaptive.mode_from_env() == "auto"
    assert adaptive.mode_from_env(True) == "on"
    assert adaptive.mode_from_env(False) == "off"
    monkeypatch.setenv("TLA_RAFT_SIEVE", "0")
    assert adaptive.mode_from_env() == "off"
    monkeypatch.setenv("TLA_RAFT_SIEVE", "1")
    assert adaptive.mode_from_env() == "on"
    # explicit argument still forces over env
    assert adaptive.mode_from_env(False) == "off"


def test_governor_stand_down_and_rearm():
    gov = adaptive.SieveGovernor("auto")
    assert gov.armed
    # clean windows: stays armed forever
    for i in range(10):
        gov.note_window(sieve_stop=False, level=i)
    assert gov.armed and gov.stats["stand_downs"] == 0
    # dense sieve-dirty stops: stands down at the density threshold
    for i in range(adaptive.MIN_WINDOWS):
        gov.note_window(sieve_stop=True, level=20 + i)
    assert not gov.armed and gov.stats["stand_downs"] == 1
    # stood down: windows are not recorded, probation ticks are
    gov.note_window(sieve_stop=True, level=30)
    assert gov.stats["stand_downs"] == 1
    gov.note_level(30)
    assert not gov.armed
    gov.note_level(23 + adaptive.REARM_LEVELS)
    assert gov.armed and gov.stats["rearms"] == 1
    snap = gov.snapshot()
    assert snap["mode"] == "auto" and snap["armed"] is True


def test_governor_forced_modes_never_move():
    on = adaptive.SieveGovernor("on")
    for i in range(20):
        on.note_window(sieve_stop=True, level=i)
    assert on.armed and on.stats["stand_downs"] == 0
    off = adaptive.SieveGovernor("off")
    assert not off.armed
    off.note_level(100)
    assert not off.armed and off.stats["rearms"] == 0


def test_governor_emits_events(tmp_path):
    from tla_raft_tpu.obs import telemetry as tel

    d = str(tmp_path)
    with tel.TelemetryHub(run_dir=d) as hub:
        tel.install(hub)
        try:
            gov = adaptive.SieveGovernor("auto")
            for i in range(adaptive.MIN_WINDOWS):
                gov.note_window(sieve_stop=True, level=i)
            gov.note_level(adaptive.MIN_WINDOWS - 1
                           + adaptive.REARM_LEVELS)
        finally:
            tel.install(None)
    events, _ = tel.read_events(os.path.join(d, "events.jsonl"))
    kinds = [e["ev"] for e in events]
    assert "sieve_standdown" in kinds and "sieve_arm" in kinds
    sd = next(e for e in events if e["ev"] == "sieve_standdown")
    assert sd["density"] >= adaptive.STAND_DOWN_DENSITY
    assert sd["windows"] >= adaptive.MIN_WINDOWS


# -- slow tier ------------------------------------------------------------

@pytest.mark.slow
def test_s3v1_fixpoint_parity_under_plan(tmp_path):
    """Autotuned-vs-default bit-identical counts on the S3V1 fixpoint
    (the deeper sibling of the fast S2 row above)."""
    from tla_raft_tpu.check import run_check

    cfg = RaftConfig(n_servers=3, n_vals=1, max_election=2,
                     max_restart=1)
    path = str(tmp_path / "plans.json")
    plans.commit(path, plans.regime_key(cfg, "jax"),
                 {"chunk": 512, "superstep_span": 2,
                  "pipeline_window": 1, "probe_window": 4,
                  "cap_margin": 1.5})
    want = run_check(cfg, plan=False)
    got = run_check(cfg, plan=path)
    for k in ("ok", "distinct", "generated", "depth", "level_sizes"):
        assert got[k] == want[k], k
    assert got["plan"]["chunk"] == 512


@pytest.mark.slow
def test_cli_tune_then_run_under_plan(tmp_path):
    """The CLI closes the loop: ``python -m tla_raft_tpu.tune`` commits
    a plan, a later ``check --plan`` run resolves it by regime and
    reports it in the output."""
    import contextlib
    import io

    from tla_raft_tpu.check import main as check_main
    from tla_raft_tpu.tune.__main__ import main as tune_main

    if not os.path.exists("/root/reference/Raft.cfg"):
        pytest.skip("reference Raft.cfg unavailable")
    path = str(tmp_path / "plans.json")
    tiny = ["--servers", "2", "--vals", "1", "--max-election", "1",
            "--max-restart", "1"]
    rc = tune_main(["tune", *tiny, "--max-depth", "4", "--top-k", "1",
                    "--out", path, "--json"])
    assert rc == 0
    assert plans.load_cache(path) is not None
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc2 = check_main([*tiny, "--plan", path,
                          "--log", str(tmp_path / "raft.log")])
    out = buf.getvalue()
    assert rc2 == 0
    assert "Autotuned plan" in out
    assert "97 states generated, 50 distinct states found" in out
