"""Unified telemetry subsystem (tla_raft_tpu/obs/, docs/OBSERVABILITY.md).

Lean fast tier (this box's tier-1 budget is tight): the event-stream
schema + torn-tail tolerance, telemetry-on/off count parity on ONE
tiny engine run (shared module-level fixture — the run is paid once),
Chrome-trace export validity (monotonic ts, matched B/E pairs, every
committed level covered), metrics.json through the atomic writer, and
the progress/ETA math as pure units.  Heavier end-to-end rows
(SIGKILL + torn-tail resume, service metrics drain) ride ``@slow``.
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

from tla_raft_tpu.config import RaftConfig
from tla_raft_tpu.obs import metrics as obs_metrics
from tla_raft_tpu.obs import progress as obs_progress
from tla_raft_tpu.obs import telemetry as tel
from tla_raft_tpu.obs import tracefile
from tla_raft_tpu.obs.__main__ import summarize_events

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
S2 = RaftConfig(n_servers=2, n_vals=1, max_election=1, max_restart=1)


# -- shared tiny run: pay the engine once, assert many things -------------

@pytest.fixture(scope="module")
def s2_run(tmp_path_factory):
    """(summary_with_hub, summary_without_hub, run_dir)."""
    from tla_raft_tpu.check import run_check, summary_public

    d = str(tmp_path_factory.mktemp("obs_run"))
    with_tel = summary_public(
        run_check(S2, chunk=64, checkpoint_dir=d, telemetry=True)
    )
    without = summary_public(run_check(S2, chunk=64, telemetry=False))
    return with_tel, without, d


def test_on_off_count_parity(s2_run):
    a, b, _d = s2_run
    for k in ("ok", "distinct", "generated", "depth", "level_sizes"):
        assert a[k] == b[k], k
    assert "telemetry" in a and "telemetry" not in b
    t = a["telemetry"]
    assert t["levels"] == a["depth"]
    assert len(t["level_seconds"]) == t["levels"]
    assert len(t["dispatches_per_level"]) == t["levels"]
    assert len(t["compile_s_per_level"]) == t["levels"]
    assert sum(t["compile_s_per_level"]) <= t["compile_s"] + 0.01
    # superstep amortization is visible in the unified block: the S2
    # sweep retires 12 levels in ~4 dispatch windows (span 4)
    assert t["supersteps"] >= 1
    assert t["dispatches"] < t["levels"]
    assert t["checkpoints"] > 0


def test_event_stream_schema(s2_run):
    _a, _b, d = s2_run
    events, dropped = tel.read_events(os.path.join(d, "events.jsonl"))
    assert dropped == 0 and events
    kinds = {e["ev"] for e in events}
    assert {"run_begin", "run_end", "level_begin", "level_commit",
            "dispatch", "fetch", "checkpoint",
            "superstep_begin", "superstep_commit"} <= kinds
    # monotonic, digest-verified timestamps; typed required fields
    ts = [e["t"] for e in events]
    assert ts == sorted(ts) and ts[0] >= 0
    for e in events:
        if e["ev"] == "level_commit":
            assert {"level", "n_new", "distinct", "generated"} <= set(e)
    ends = [e for e in events if e["ev"] == "run_end"]
    assert ends and ends[-1]["distinct"] == _a["distinct"]
    # the post-hoc reader agrees with the in-process aggregates
    rep = summarize_events(events)
    assert rep["totals"]["levels"] == _a["telemetry"]["levels"]
    assert rep["totals"]["dispatches"] == _a["telemetry"]["dispatches"]


def test_torn_tail_tolerated_and_healed(tmp_path):
    path = str(tmp_path / "events.jsonl")
    with tel.TelemetryHub(path=path) as hub:
        for i in range(5):
            hub.emit("level_commit", level=i + 1, n_new=10 * i,
                     distinct=1, generated=1, slab_cap=0)
    # tear the tail mid-line (a SIGKILL mid-write)
    with open(path, "ab") as fh:
        fh.write(b'{"t":9.9,"ev":"level_commit","n_new":')
    events, dropped = tel.read_events(path)
    assert len(events) == 5 and dropped == 1
    # a corrupted INTERIOR byte also never raises
    data = open(path, "rb").read()
    bad = str(tmp_path / "bad.jsonl")
    with open(bad, "wb") as fh:
        fh.write(data[:20] + b"X" + data[21:])
    evs2, dropped2 = tel.read_events(bad)
    assert dropped2 >= 1 and isinstance(evs2, list)
    # a resumed hub heals (truncates) the torn tail, then appends
    with tel.TelemetryHub(path=path) as hub2:
        hub2.emit("run_begin")
    assert hub2.healed_lines == 1  # heal ran at first file touch
    events3, dropped3 = tel.read_events(path)
    assert dropped3 == 0 and len(events3) == 6


def test_chrome_trace_validity(s2_run, tmp_path):
    a, _b, d = s2_run
    out = str(tmp_path / "trace.json")
    stats = tracefile.export(os.path.join(d, "events.jsonl"), out)
    assert stats["dropped"] == 0
    doc = json.load(open(out))
    evs = doc["traceEvents"]
    assert evs and isinstance(evs, list)
    per_tid_open = {}
    level_slices = set()
    for e in evs:
        assert e["ph"] in ("M", "B", "E", "X", "i")
        if e["ph"] == "M":
            continue
        assert e["ts"] >= 0
        if e["ph"] == "X":
            assert e["dur"] >= 0
            if e["tid"] == 1 and e["name"].startswith("level "):
                level_slices.add(int(e["name"].split()[1]))
        elif e["ph"] == "B":
            per_tid_open[e["tid"]] = per_tid_open.get(e["tid"], 0) + 1
        elif e["ph"] == "E":
            per_tid_open[e["tid"]] = per_tid_open.get(e["tid"], 0) - 1
            assert per_tid_open[e["tid"]] >= 0, "E without B"
    assert all(v == 0 for v in per_tid_open.values()), "unmatched B"
    # every committed level appears on the level track
    assert level_slices == set(range(1, a["depth"] + 1))


def test_trace_closes_dangling_window():
    evs = [
        dict(t=0.0, ev="run_begin"),
        dict(t=1.0, ev="superstep_begin"),
        dict(t=2.0, ev="dispatch", tag="x"),
    ]
    doc = tracefile.to_chrome_trace(evs)
    bs = [e for e in doc["traceEvents"] if e["ph"] == "B"]
    es = [e for e in doc["traceEvents"] if e["ph"] == "E"]
    assert len(bs) == len(es) == 1


# -- metrics --------------------------------------------------------------

def test_metrics_atomic_commit(tmp_path):
    from tla_raft_tpu import resilience

    root = str(tmp_path)
    m = obs_metrics.Metrics()
    m.counter("jobs_done").inc(3)
    m.gauge("queue_depth").set(7)
    h = m.histogram("level_s")
    for v in (0.5, 1.5):
        h.observe(v)
    path = m.commit(root)
    assert os.path.basename(path) == "metrics.json"
    # committed through the atomic writer: digest-verified read works
    # and the manifest carries the entry
    doc = obs_metrics.load(root)
    assert doc["counters"]["jobs_done"] == 3
    assert doc["gauges"]["queue_depth"] == 7.0
    assert doc["histograms"]["level_s"]["count"] == 2
    assert doc["histograms"]["level_s"]["mean"] == 1.0
    assert resilience.Manifest.load(root).verify("metrics.json") == "ok"
    # a torn write is an absent read, not an exception
    with open(os.path.join(root, "metrics.json"), "w") as fh:
        fh.write('{"torn":')
    assert obs_metrics.load(root) is None


# -- progress / ETA math --------------------------------------------------

def test_eta_math_units():
    # decaying frontier: finite, positive forecast
    rem = obs_progress.forecast_remaining_states([100, 80, 40])
    assert rem is not None and 0 < rem < 200
    # growing with no decay signal: honest unknown
    assert obs_progress.forecast_remaining_states([10, 20, 40]) is None
    assert obs_progress.forecast_remaining_states([5]) is None
    # growth that is DECELERATING forecasts a finite remainder
    rem2 = obs_progress.forecast_remaining_states([100, 160, 200])
    assert rem2 is not None and rem2 > 0
    # eta = remaining / rate, in seconds
    eta = obs_progress.eta_seconds([100, 80, 40], rate=100.0)
    assert eta == pytest.approx(rem / 100.0)
    assert obs_progress.eta_seconds([10, 20, 40], 100.0) is None
    assert obs_progress.fmt_eta(None) == "—"
    assert obs_progress.fmt_eta(61) == "1:01"
    assert obs_progress.fmt_eta(3661) == "1:01:01"


def test_progress_line_renders(s2_run):
    a, _b, _d = s2_run
    pl = obs_progress.ProgressLine(stream=None)
    line = pl.update(
        dict(level=3, frontier=40, distinct=100, generated=200,
             elapsed=2.0),
        snap=a["telemetry"],
    )
    assert "level 3" in line and "st/s" in line and "ETA" in line
    assert "lvl/disp" in line


def test_gl012_host_purity_rule():
    """The lint gate backing the obs/ contract: jax imports and device
    syncs are flagged inside tla_raft_tpu/obs/, silent elsewhere."""
    from tla_raft_tpu.analysis.ast_lint import lint_source

    src = ("import jax\n"
           "def f(x):\n"
           "    return jax.device_get(x)\n")
    fs = lint_source(src, relpath="tla_raft_tpu/obs/fake.py")
    assert [f.rule for f in fs].count("GL012") == 2  # import + sync
    # lazy imports are banned too (host purity is not a warm-up
    # property)
    lazy = ("def g():\n"
            "    from jax import numpy as jnp\n"
            "    return jnp\n")
    fs2 = lint_source(lazy, relpath="tla_raft_tpu/obs/fake.py")
    assert any(f.rule == "GL012" for f in fs2)
    # outside obs/ the rule stays silent
    fs3 = lint_source(src, relpath="tla_raft_tpu/engine/fake.py")
    assert not [f for f in fs3 if f.rule == "GL012"]
    # the REAL obs/ package is clean under the rule
    from tla_raft_tpu.analysis.ast_lint import lint_paths

    obs_dir = os.path.join(REPO, "tla_raft_tpu", "obs")
    found = lint_paths([obs_dir], root=REPO, select={"GL012"})
    assert found == [], "\n".join(f.format() for f in found)


def test_hub_emit_is_noop_without_install():
    assert tel.current() is None
    tel.dispatch("x")  # must not raise, must not create state
    tel.level_commit(1, 1, 1, 1)
    assert tel.current() is None


# -- heavier end-to-end rows ----------------------------------------------

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


@pytest.mark.slow
def test_sigkill_torn_tail_then_recover(tmp_path):
    """SIGKILL mid-run, then --recover: a torn events.jsonl tail must
    never block the resume, and the healed stream keeps appending."""
    cfg = tmp_path / "tiny.cfg"
    cfg.write_text(CFG_2111)
    ck = str(tmp_path / "ck")
    common = [
        "--config", str(cfg), "--chunk", "64",
        "--checkpoint-dir", ck, "--log", "-",
    ]
    p = _run_cli(common, fault="level.start:kill@3")
    assert p.returncode not in (0, 1, 2), (p.returncode, p.stdout)
    ev_path = os.path.join(ck, "events.jsonl")
    assert os.path.exists(ev_path)
    # tear the tail the way a mid-write SIGKILL would
    with open(ev_path, "ab") as fh:
        fh.write(b'{"t":1.0,"ev":"level_com')
    p2 = _run_cli(common + ["--recover", ck])
    assert p2.returncode == 0, (p2.returncode, p2.stdout, p2.stderr)
    assert "50 distinct states" in p2.stdout
    events, dropped = tel.read_events(ev_path)
    assert dropped == 0  # the resumed hub healed the torn tail
    assert any(e["ev"] == "run_end" for e in events)
    # the resumed hub rebased its clock: the SPLICED stream is still
    # monotonic (two run_begin anchors, no timestamp overlay), so the
    # exported crash-postmortem trace shows the runs side by side
    assert sum(1 for e in events if e["ev"] == "run_begin") == 2
    ts = [e["t"] for e in events]
    assert ts == sorted(ts)


@pytest.mark.slow
def test_service_metrics_commit_each_pass(tmp_path):
    from tla_raft_tpu.service.daemon import Scheduler
    from tla_raft_tpu.service.queue import JobQueue

    root = str(tmp_path / "q")
    q = JobQueue(root)
    for mr in (1, 2):
        q.submit(RaftConfig(n_servers=2, n_vals=1, max_election=1,
                            max_restart=mr), max_depth=3,
                 options={"chunk": 64})
    sched = Scheduler(q, out=open(os.devnull, "w"))
    sched.run_once()
    doc = obs_metrics.load(root)
    assert doc is not None
    assert doc["counters"]["jobs_done"] == 2
    assert doc["gauges"]["queue_depth"] == 0
    assert doc["gauges"]["jobs_per_hour"] > 0
    # the CLI renders it
    from tla_raft_tpu.service.__main__ import main as svc_main

    assert svc_main(["status", "--root", root, "--metrics"]) == 0
