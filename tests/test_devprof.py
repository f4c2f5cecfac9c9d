"""Device-cost observatory (analysis/devprof.py, analysis/cost_audit.py;
docs/OBSERVABILITY.md "Device-side profiling").

Lean fast tier (tier-1 sits near its 870 s gate on 1-core boxes): ONE
tiny S2 engine run with telemetry + ``--profile 1`` is shared by every
end-to-end row (program_profile emission, hbm block, profiler-merged
trace validity), the GL013 rule units run on dict fixtures (no
compile), the rotation rows are pure host units, and
the counts-parity row reuses the jit caches the shared run warmed.
The subprocess CLI profile smoke rides ``@slow`` (CI runs its twin).
"""

import glob
import json
import os
import subprocess
import sys
import textwrap

import pytest

from tla_raft_tpu.config import RaftConfig
from tla_raft_tpu.obs import telemetry as tel
from tla_raft_tpu.obs import tracefile
from tla_raft_tpu.obs.__main__ import summarize_events, _print_table

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
S2 = RaftConfig(n_servers=2, n_vals=1, max_election=1, max_restart=1)


# -- shared tiny run: pay the engine once, assert many things -------------

@pytest.fixture(scope="module")
def s2_prof_run(tmp_path_factory):
    """(summary, run_dir) of ONE S2 run with telemetry + --profile 1."""
    from tla_raft_tpu.check import run_check, summary_public

    d = str(tmp_path_factory.mktemp("devprof_run"))
    summary = summary_public(run_check(
        S2, chunk=64, checkpoint_dir=d, telemetry=True, profile=1,
    ))
    return summary, d


# -- program_profile emission (tentpole 1, runtime half) ------------------

def test_program_profile_events(s2_prof_run):
    summary, d = s2_prof_run
    events, dropped = tel.read_events(os.path.join(d, "events.jsonl"))
    assert dropped == 0
    pp = [e for e in events if e["ev"] == "program_profile"]
    assert pp, "no program_profile events from the dispatch sites"
    tags = {e["tag"] for e in pp}
    # the S2 default path runs supersteps; the driver's profile must be
    # there with real cost/memory numbers
    assert "superstep.levels" in tags
    for e in pp:
        assert e["flops"] > 0 and e["bytes"] > 0
        assert e["tmp_b"] >= 0 and e["arg_b"] > 0
        assert e["peak_b"] >= e["tmp_b"]
    # collection is compile-time only: dispatch amortization unchanged
    t = summary["telemetry"]
    assert t["dispatches"] < t["levels"]
    assert t["programs_profiled"] == len(pp)


def test_counts_parity_profile_on_off(s2_prof_run):
    from tla_raft_tpu.check import run_check, summary_public

    a, _d = s2_prof_run
    b = summary_public(run_check(S2, chunk=64, telemetry=False))
    for k in ("ok", "distinct", "generated", "depth", "level_sizes"):
        assert a[k] == b[k], k


# -- live HBM accounting (tentpole 2) -------------------------------------

def test_hbm_block(s2_prof_run):
    summary, _d = s2_prof_run
    hbm = summary["hbm"]
    bufs = hbm["buffers"]
    assert {"hslab", "frontier", "ring"} <= set(bufs)
    assert bufs["hslab"] >= 8 * 1024  # MIN_CAP slots * 8 B
    assert hbm["resident_bytes"] == sum(bufs.values())
    assert hbm["working_set_bytes"] == (
        hbm["resident_bytes"] + hbm["temp_peak_bytes"]
    )
    assert hbm["temp_peak_program"] in (
        "superstep.levels", "megakernel.level",
    )


def test_hbm_gauge_arithmetic():
    g = tel.hbm_gauge(
        {"slab": 1000, "frontier": 500}, {"a": 200, "b": 700},
        budget=10_000,
    )
    assert g["resident_bytes"] == 1500
    assert g["temp_peak_bytes"] == 700
    assert g["temp_peak_program"] == "b"
    assert g["working_set_bytes"] == 2200
    assert g["headroom_bytes"] == 10_000 - 2200
    assert g["used_frac"] == round(2200 / 10_000, 4)
    # no budget: no headroom keys, gauge still prices the working set
    g2 = tel.hbm_gauge({"slab": 8}, {})
    assert "headroom_bytes" not in g2
    assert g2["working_set_bytes"] == 8


def test_pre_oom_forecast_event(tmp_path):
    """A budget far below the S2 working set must raise the predictive
    pre_oom_forecast (the run itself stays correct: 50 states fit the
    hot tier, so no demotion and identical counts)."""
    from tla_raft_tpu.check import run_check, summary_public

    d = str(tmp_path / "oom")
    s = summary_public(run_check(
        S2, chunk=64, checkpoint_dir=d, telemetry=True,
        dev_bytes=8 * 1024,
    ))
    assert s["distinct"] == 50 and s["ok"]
    hbm = s["hbm"]
    assert hbm["budget_bytes"] == 8 * 1024
    assert hbm["pre_oom_forecasts"] >= 1
    last = hbm["last_pre_oom"]
    assert last["need"] > last["budget"]
    events, _ = tel.read_events(os.path.join(d, "events.jsonl"))
    pre = [e for e in events if e["ev"] == "pre_oom_forecast"]
    assert pre and pre[0]["need"] > pre[0]["budget"]
    assert any(e["ev"] == "hbm_budget" for e in events)


# -- cost ledger + GL013 (tentpole 1, committed half) ---------------------

def test_cost_ledger_schema():
    from tla_raft_tpu.analysis import cost_audit, devprof

    led = cost_audit.load_golden()
    assert led is not None, "analysis/cost_ledger.json not committed"
    meta = led["_meta"]
    assert meta["jax"] and meta["backend"]
    kernels = [k for k in led if k != "_meta"]
    assert {"engine.megakernel_level", "engine.superstep",
            "hashstore.probe", "hashstore.probe_and_insert",
            "successor.expand_guards", "successor.materialize",
            "dense.expand", "store.tiered_compact"} <= set(kernels)
    for k in kernels:
        for m in devprof.METRIC_KEYS:
            assert m in led[k], (k, m)
        assert led[k]["flops"] > 0, k
    # the registry and the ledger agree on the kernel set
    assert set(cost_audit.compiled_registry()) == set(kernels)


def test_gl013_seeded_regression():
    """The rule unit on dict fixtures: a seeded FLOPs/temp regression
    hard-fails, matching budgets pass, cross-env demotes to warnings.
    No compiles — `current` is injected."""
    import jax

    from tla_raft_tpu.analysis import cost_audit

    entry = dict(flops=1000.0, bytes=5000.0, arg_b=10, out_b=10,
                 alias_b=0, tmp_b=100, code_b=0)
    meta = {"jax": jax.__version__, "backend": jax.default_backend()}
    golden = {"_meta": meta, "k": dict(entry)}
    # clean
    f, w = cost_audit.audit(golden=golden,
                            current={"_meta": meta, "k": dict(entry)})
    assert not f and not w
    # seeded regression: flops x2, temp x4
    bad = dict(entry, flops=2000.0, tmp_b=400)
    f, w = cost_audit.audit(golden=golden,
                            current={"_meta": meta, "k": bad})
    assert len(f) == 2 and all("[GL013]" in x for x in f)
    assert any("flops" in x for x in f) and any("tmp_b" in x for x in f)
    # same regression on another backend's ledger: warnings only
    alien = {"_meta": {"jax": "0.0.0", "backend": "tpu"},
             "k": dict(entry)}
    f, w = cost_audit.audit(golden=alien,
                            current={"_meta": meta, "k": bad})
    assert not f and any("[GL013]" in x for x in w)
    # zero-budget class appearing is a regression
    z = {"_meta": meta, "k": dict(entry, tmp_b=0)}
    f, w = cost_audit.audit(
        golden=z, current={"_meta": meta, "k": dict(entry, tmp_b=64)}
    )
    assert any("grew a cost class" in x for x in f)
    # under budget: bank-the-win warning, not a failure
    f, w = cost_audit.audit(
        golden=golden,
        current={"_meta": meta, "k": dict(entry, flops=500.0)},
    )
    assert not f and any("bank the win" in x for x in w)


# -- events.jsonl rotation (satellite) ------------------------------------

def test_rotation_chain(tmp_path):
    d = str(tmp_path)
    hub = tel.TelemetryHub(run_dir=d, max_bytes=2048)
    with hub:
        for lvl in range(30):
            for i in range(20):
                tel.dispatch(f"t{i}")
            tel.level_commit(lvl + 1, 10, 10 * (lvl + 1), 0)
    assert hub.rotations >= 2
    chain = tel.rotated_paths(os.path.join(d, "events.jsonl"))
    assert chain and all(os.path.exists(p) for p in chain)
    events, dropped = tel.read_events(os.path.join(d, "events.jsonl"))
    assert dropped == 0
    lc = [e for e in events if e["ev"] == "level_commit"]
    assert [e["level"] for e in lc] == list(range(1, 31))
    ts = [e["t"] for e in events]
    assert ts == sorted(ts)
    # resume: heal + clock rebase keep the spliced chain monotonic
    hub2 = tel.TelemetryHub(run_dir=d, max_bytes=2048)
    with hub2:
        tel.level_commit(31, 1, 301, 0)
    events2, _ = tel.read_events(os.path.join(d, "events.jsonl"))
    ts2 = [e["t"] for e in events2]
    assert ts2 == sorted(ts2)
    assert events2[-1]["level"] == 31
    # no-rotation stream: chain helpers are no-ops
    assert tel.rotated_paths(os.path.join(d, "nope.jsonl")) == []


def test_rotation_env_default(monkeypatch):
    monkeypatch.delenv("TLA_RAFT_TELEMETRY_BYTES", raising=False)
    assert tel.max_bytes_from_env() == tel.DEFAULT_MAX_BYTES
    monkeypatch.setenv("TLA_RAFT_TELEMETRY_BYTES", "1e6")
    assert tel.max_bytes_from_env() == 1_000_000
    monkeypatch.setenv("TLA_RAFT_TELEMETRY_BYTES", "0")
    assert tel.max_bytes_from_env() == 0


# -- profiler-merged timelines (tentpole 3) -------------------------------

def test_profiler_capture_and_merge(s2_prof_run, tmp_path):
    _summary, d = s2_prof_run
    # the capture wrote a Perfetto device trace
    gz = glob.glob(os.path.join(
        d, "profile", "plugins", "profile", "*",
        "perfetto_trace.json.gz",
    ))
    assert gz, "no perfetto device trace from --profile 1"
    events, _ = tel.read_events(os.path.join(d, "events.jsonl"))
    begins = [e for e in events if e["ev"] == "profile_begin"]
    ends = [e for e in events if e["ev"] == "profile_end"]
    assert len(begins) == 1 and len(ends) == 1
    assert ends[0]["windows"] == 1
    out = str(tmp_path / "trace.json")
    stats = tracefile.export(
        os.path.join(d, "events.jsonl"), out, run_dir=d,
        max_device_events=5000,
    )
    assert stats["device_events"] > 0
    doc = json.load(open(out))
    evs = doc["traceEvents"]
    # device lanes present as separate processes
    dev = [e for e in evs
           if e.get("pid", 1) >= tracefile.DEVICE_PID_BASE]
    assert dev
    names = [e["args"]["name"] for e in evs
             if e.get("ph") == "M" and e.get("name") == "process_name"
             and e.get("pid", 1) >= tracefile.DEVICE_PID_BASE]
    assert names and all(n.startswith("device: ") for n in names)
    # matched B/E per (pid, tid) across BOTH host and device lanes
    depth = {}
    for e in evs:
        key = (e.get("pid"), e.get("tid"))
        if e.get("ph") == "B":
            depth[key] = depth.get(key, 0) + 1
        elif e.get("ph") == "E":
            depth[key] = depth.get(key, 0) - 1
            assert depth[key] >= 0, key
    assert all(v == 0 for v in depth.values())
    # device timestamps sit on the host clock: all >= the begin anchor
    anchor_us = begins[0]["t"] * 1e6
    assert all(
        float(e.get("ts", 0)) >= anchor_us - 1 for e in dev
        if e.get("ph") != "M"
    )
    # cap is honest: dropping shortest slices is reported
    assert stats["device_dropped"] >= 0


def test_trace_without_profile_still_valid(tmp_path):
    """No --profile capture: trace export degrades to host lanes only
    (the hardening satellite — absent subsystems never error)."""
    d = str(tmp_path)
    hub = tel.TelemetryHub(run_dir=d)
    with hub:
        tel.run_begin(config="t")
        tel.level_commit(1, 5, 5, 10)
        tel.run_end(ok=True, distinct=5, generated=10, depth=1)
    out = str(tmp_path / "t.json")
    stats = tracefile.export(os.path.join(d, "events.jsonl"), out,
                             run_dir=d)
    assert stats["device_events"] == 0
    assert json.load(open(out))["traceEvents"]


# -- report hardening (satellite) -----------------------------------------

def test_report_missing_optional_kinds():
    """Streams without superstep/tier/profile events summarize and
    render with blank/zero columns instead of erroring."""
    import io

    minimal = [
        dict(t=0.0, ev="run_begin"),
        dict(t=1.0, ev="dispatch", tag="x"),
        dict(t=2.0, ev="level_commit", level=1, n_new=3, distinct=3,
             generated=6),
        dict(t=3.0, ev="run_end", ok=True),
    ]
    rep = summarize_events(minimal)
    t = rep["totals"]
    assert t["supersteps"] == 0 and t["tier_probes"] == 0
    assert t["programs_profiled"] == 0
    buf = io.StringIO()
    _print_table("x", rep, buf)
    assert "tier_s" not in buf.getvalue()  # blank, not erroring
    # tiered stream grows the tier column
    tiered = minimal[:2] + [
        dict(t=1.5, ev="tier_probe", level=1, lanes=10, hits=2, s=0.01),
        dict(t=1.6, ev="tier_demote", level=1, n=5, gen=0, s=0.02),
    ] + minimal[2:]
    rep2 = summarize_events(tiered)
    assert rep2["totals"]["tier_probes"] == 1
    buf2 = io.StringIO()
    _print_table("x", rep2, buf2)
    assert "tier_s" in buf2.getvalue()
    # corrupt t field degrades instead of raising
    rep3 = summarize_events([dict(t="bogus", ev="run_begin")])
    assert rep3["totals"]["wall_s"] == 0.0


# -- heavy: CLI --profile smoke (the CI job's twin) -----------------------

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


@pytest.mark.slow
def test_cli_profile_smoke(tmp_path):
    cfg = tmp_path / "tiny.cfg"
    cfg.write_text(CFG_2111)
    d = str(tmp_path / "run")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run(
        [sys.executable, "-m", "tla_raft_tpu.check",
         "--config", str(cfg), "--chunk", "64",
         "--checkpoint-dir", d, "--profile", "1", "--json",
         "--log", "-"],
        capture_output=True, text=True, cwd=REPO, env=env,
        timeout=600,
    )
    assert r.returncode == 0, r.stdout + r.stderr
    line = [ln for ln in r.stdout.splitlines()
            if ln.startswith("{")][-1]
    summary = json.loads(line)
    assert summary["ok"] and "hbm" in summary
    r2 = subprocess.run(
        [sys.executable, "-m", "tla_raft_tpu.obs", "trace", d],
        capture_output=True, text=True, cwd=REPO, timeout=120,
    )
    assert r2.returncode == 0, r2.stdout + r2.stderr
    assert "device-lane events merged" in r2.stdout
    doc = json.load(open(os.path.join(d, "trace.json")))
    assert any(
        e.get("pid", 1) >= tracefile.DEVICE_PID_BASE
        for e in doc["traceEvents"]
    )
