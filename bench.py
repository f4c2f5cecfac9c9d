"""Headline benchmark: distinct states/sec of the TPU checker.

Workload: the reference model (/root/reference/Raft.cfg) checked end to end
— BFS over the full bounded state space with symmetry + VIEW dedup and the
Inv invariant, exactly what `./myrun.sh` runs (BASELINE.md config 1/2).

Baseline: the reference publishes no numbers and its checker (TLC) is an
external Java tool that is not vendored (and cannot be fetched in this
zero-egress environment), so the recorded CPU baseline is this repo's
pure-Python oracle — the same semantics, measured once on a depth-capped
prefix of the same workload (BASELINE.md "first measurement task").

Self-verification (a correctness gate, not just a timer): the oracle
prefix run doubles as a golden answer — the engine's per-level state
counts must match it level for level, the engine must report a clean
sweep (the reference config is known violation-free), and when the run
reaches the full fixpoint the totals must equal the pinned golden
full-space counts (BASELINE.md).  A mismatch makes this benchmark FAIL
(exit 1) instead of reporting a number for a wrong computation.

Metrics: one full run on the attached chip (a TPU, unless
JAX_PLATFORMS=cpu asks for the CPU; no other fallback).  ``value`` is the
steady-state throughput — the best rate over a trailing window of BFS
levels once compilation has amortized (cold compiles are O(log) per
run; the persistent cache holds them for the next).  ``overall_rate``
includes everything (compiles, host driver, checkpointless run).

Output contract: NDJSON, LAST line wins; a failing stage prints one
ok:false line:
  {"metric": ..., "value": N, "unit": "distinct_states_per_sec",
   "vs_baseline": N, "parity": true, ...}

Env knobs: BENCH_MAX_DEPTH (0 = full sweep), BENCH_CHUNK, BENCH_SERVERS /
BENCH_VALS / BENCH_MAX_ELECTION (scale dials, BASELINE.md configs 3-5),
BENCH_GOLD_DEPTH (oracle prefix depth), RAFT_CFG, BENCH_HASHSTORE (0 =
sort-path A/B), BENCH_PIPELINE (0 = serial-chain A/B) /
BENCH_PIPELINE_WINDOW (in-flight fetch groups, default 2), BENCH_MXU
(0 = legacy per-lane expand A/B), BENCH_TIERED (1 = cap the hot visited
slab at BENCH_TIERED_BYTES, forcing generation demotions to host/disk —
the out-of-core tiered-store A/B), BENCH_SIEVE (0 = spill sieve off, so
a tiered run stands its superstep down to span 1 — the sieve A/B; only
meaningful with BENCH_TIERED=1), BENCH_MEGAKERNEL (0 = staged
program-chain A/B vs the fused whole-level program; dispatches/level
land in the record either way), BENCH_SUPERSTEP (0 = per-level fused
A/B vs the multi-level resident superstep driver; levels_per_dispatch
lands in the record either way), BENCH_AUDIT (1 = integrity audit at
BENCH_AUDIT_N rows/level, default 64 — overhead A/B, single-device
arm), BENCH_TELEMETRY (0 = flight recorder off — the telemetry
overhead A/B; on, the record's level accounting comes from the hub),
BENCH_SERVICE (1 = the sweep-service
jobs/hour A/B on the synthetic queue instead — see _bench_service).
"""

from __future__ import annotations

import dataclasses
import json
import os
import sys
import time

# Full-space golden totals for completed (empty-frontier) fixpoint runs,
# keyed (S, V, max_election, max_restart) -> (distinct, generated, depth).
# Pinned from the independent native C++ checker (native/cpubase.cpp); a
# BENCH_MAX_DEPTH=0 run of a dual-verified config FAILS unless it lands
# exactly here, while single-source rows (see GOLDEN_FULL_SINGLE_SOURCE
# below) only warn.  The as-is reference config's fixpoint (~10^9 states,
# BASELINE.md) has not been reached by any engine yet and stays unpinned.
GOLDEN_FULL = {
    (3, 1, 2, 1): (180_582, 747_500, 35),  # cpubase ≡ oracle (exact)
    (3, 1, 2, 2): (223_437, 936_729, 36),  # cpubase ≡ oracle (exact)
    # cpubase ≡ oracle (exact, round 5: 2.9-h oracle fixpoint run,
    # docs/ORACLE_FIX_V2ME2MR0.json — config [3,2,2,0], identical
    # distinct/generated/depth, so ADVICE r4 #1's "single-source"
    # premise no longer holds for this row and it GATES)
    (3, 2, 2, 0): (4_850_261, 26_087_894, 45),
}
# Rows confirmed by only ONE engine are ADVISORY (ADVICE r4 #1): a
# mismatch is warned and recorded with parity=null (indeterminate, exit
# 0) instead of hard-failing the run, so a bug in the single source
# cannot reject a correct chip run.  Empty today — every GOLDEN_FULL
# row above is dual-confirmed (cpubase.cpp + the python oracle); add a
# key here the moment a single-engine row lands, and remove it when a
# second independent engine confirms its totals.
GOLDEN_FULL_SINGLE_SOURCE: set = set()

# Per-level new-state counts of the deepest verified record (BASELINE.md
# "golden counts": levels 0-15 double-verified oracle+engine, 16+ device-
# produced with disjoint-new delta audits).  Any bench run deep enough to
# overlap this prefix is gated on it level for level — the numbers the
# project leans on hardest must be regression-checked, not prose-only.
GOLDEN_LEVELS = {
    (3, 2, 3, 3): [
        1, 1, 3, 9, 22, 57, 136, 345, 931, 2468, 5881, 12505, 24705,
        47599, 91014, 169607, 301664, 511609, 839797, 1353766, 2150466,
        3350017, 5099018, 7596394, 11125029, 16077143, 22959572,
        32391457, 45102507,
    ],
}


def _append_trend(record: dict, bench_out: str) -> None:
    """Fold this round's BENCH_OUT record into the docs/bench/ trend
    series (obs/trend.py) so the perf trajectory grows as a side
    effect of running the bench.  The round comes from the BENCH_OUT
    name (BENCH_rNN.json) or BENCH_ROUND; without either the record
    stays out of the series (a one-off probe run, not a round)."""
    try:
        from tla_raft_tpu.obs import trend as obs_trend

        rnd = obs_trend.round_from_name(bench_out)
        if rnd is None and os.environ.get("BENCH_ROUND"):
            rnd = int(os.environ["BENCH_ROUND"])
        if rnd is None:
            return
        bench_dir = os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "docs", "bench"
        )
        path = obs_trend.append_record(
            record, bench_dir, round_no=rnd,
            source=os.path.basename(bench_out),
        )
        if path:
            print(f"[bench] trend record -> {path}", file=sys.stderr)
    except Exception as e:  # graftlint: waive[GL003] — the trend
        # series is bookkeeping; it must never fail the bench run
        print(f"[bench] trend append failed: {e}", file=sys.stderr)


def _emit_failure(failure_class: str, exc: BaseException, **extra) -> None:
    import traceback

    traceback.print_exc(file=sys.stderr)
    print(json.dumps({
        "metric": "raft_cfg_check_failed",
        "value": 0.0,
        "unit": "distinct_states_per_sec",
        "vs_baseline": 0.0,
        "ok": False,
        "parity": False,
        "failure_class": failure_class,
        "error": f"{type(exc).__name__}: {exc}"[:500],
        **extra,
    }))
    sys.stdout.flush()


def _init_jax():
    """The backend, or one parseable ok:false line and exit 1: a run
    that finds no TPU (and was not pinned to the CPU) fails here."""
    try:
        from tla_raft_tpu.platform import setup_jax

        return setup_jax()
    except RuntimeError as e:
        _emit_failure("backend_init", e)
        sys.exit(1)


def _best_window_rate(levels, fallback, max_level=None):
    """Best trailing-window rate over >=25% of the states and >=2 levels.

    Excludes the cold-compile ramp.  ``max_level`` restricts the search to
    a depth prefix so the rate covers the same level mix as a depth-capped
    baseline run (ADVICE r3: steady-vs-overall across different depths is
    not comparable).  The window must also span >= 2% of the run's wall
    time: with multi-level supersteps every level of one dispatch window
    reports the SAME elapsed timestamp, so a window inside one burst
    divides a real state count by measurement noise (the first superstep
    A/B "measured" 10^8 states/s that way)."""
    lv = [x for x in levels if max_level is None or x[0] <= max_level]
    best = fallback
    if not lv:
        return best
    total = lv[-1][1]
    wall = lv[-1][2]
    for i in range(len(lv)):
        for j in range(i + 2, len(lv)):
            dn = lv[j][1] - lv[i][1]
            dtm = lv[j][2] - lv[i][2]
            if dn >= total // 4 and dtm > max(0.02 * wall, 1e-9):
                best = max(best, dn / dtm)
    return best


def _bench_service_arm() -> int:
    """One A/B arm, in its own process (BENCH_SERVICE_ARM=batched|
    sequential): builds its queue, drains it, prints one JSON line.

    Process isolation is the point: each arm runs with the persistent
    compile cache off (set by the parent) and a cold in-process
    kernel/jit cache, so neither arm rides programs the other (or an
    earlier bench run) already paid to compile."""
    jax = _init_jax()
    sys.path.insert(
        0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "scripts")
    )
    import queue_synth

    from tla_raft_tpu.service.daemon import Scheduler
    from tla_raft_tpu.service.queue import JobQueue

    arm = os.environ["BENCH_SERVICE_ARM"]
    n_jobs = int(os.environ.get("BENCH_SERVICE_JOBS", "40"))
    mr_width = int(os.environ.get("BENCH_SERVICE_MR_WIDTH", "16"))
    seed = int(os.environ.get("BENCH_SERVICE_SEED", "1"))
    chunk = int(os.environ.get("BENCH_SERVICE_CHUNK", "64"))
    jobs = queue_synth.synth_jobs(n_jobs, seed, mr_width, chunk)
    root = os.path.join(os.environ["BENCH_SERVICE_BASE"], arm)
    if int(os.environ.get("BENCH_SERVICE_WARM", "0")):
        # steady-state mode: drain one priming copy of the queue first
        # so the timed drain measures the long-lived daemon's warm
        # regime (program ladder paid in-process) —
        # the default cold mode keeps measuring the ladder cost itself
        qw = JobQueue(root + "_warmup")
        for cfg, cap, opt in jobs:
            qw.submit(cfg, max_depth=cap, options=opt)
        Scheduler(qw, batch=(arm == "batched")).run_once()
    q = JobQueue(root)
    jids = [
        q.submit(cfg, max_depth=cap, options=opt)
        for cfg, cap, opt in jobs
    ]
    sched = Scheduler(q, batch=(arm == "batched"))
    t0 = time.monotonic()
    stats = sched.run_once()
    wall = time.monotonic() - t0
    print(json.dumps(dict(
        service_arm=arm, wall_s=wall, stats=stats,
        results=[q.load_result(j) for j in jids],
        device=str(jax.devices()[0]),
    )))
    return 0


def _bench_service() -> int:
    """BENCH_SERVICE=1: the sweep-service jobs/hour A/B.

    Builds the synthetic sweep queue (scripts/queue_synth.py) twice and
    drains it through the scheduler both ways — config-batched and
    sequential, each arm a fresh subprocess with no persistent compile
    cache (see _bench_service_arm) — then gates on per-job summary parity
    between the arms (distinct/generated/depth/level_sizes must be
    bit-identical) before reporting jobs/hour and configs-per-dispatch.
    Knobs: BENCH_SERVICE_JOBS (default 40 — 10 MaxRestart values per
    base key, so every bucket demonstrates >= 10 configs on one
    compiled program ladder), BENCH_SERVICE_MR_WIDTH,
    BENCH_SERVICE_SEED, BENCH_SERVICE_CHUNK, BENCH_SERVICE_ROOT (keep
    the queue dirs), BENCH_SERVICE_WARM (1 = time a second drain after
    a priming pass — the long-lived daemon's steady state; default 0
    keeps measuring the cold compile-ladder cost)."""
    import shutil
    import subprocess
    import tempfile

    if os.environ.get("BENCH_SERVICE_ARM"):
        return _bench_service_arm()
    # this parent stays off JAX: each arm's child needs the chip

    try:
        n_jobs = int(os.environ.get("BENCH_SERVICE_JOBS", "40"))
        keep_root = os.environ.get("BENCH_SERVICE_ROOT")
        base = keep_root or tempfile.mkdtemp(prefix="bench_service_")
    except Exception as e:
        _emit_failure("bench_setup", e, unit="jobs_per_hour")
        return 1

    def run_arm(name: str):
        env = dict(
            os.environ,
            BENCH_SERVICE_ARM=name,
            BENCH_SERVICE_BASE=base,
            JAX_ENABLE_COMPILATION_CACHE="false",
        )
        p = subprocess.run(
            [sys.executable, os.path.abspath(__file__)],
            env=env, capture_output=True, text=True, timeout=7200,
        )
        sys.stderr.write(p.stderr[-4000:])
        if p.returncode != 0:
            raise RuntimeError(
                f"{name} arm exited {p.returncode}: {p.stdout[-500:]}"
            )
        doc = json.loads(
            [ln for ln in p.stdout.splitlines()
             if ln.startswith("{")][-1]
        )
        return doc["stats"], doc["wall_s"], doc["results"], doc

    try:
        b_stats, b_wall, b_res, b_doc = run_arm("batched")
        s_stats, s_wall, s_res, _s_doc = run_arm("sequential")
    except Exception as e:
        _emit_failure("service_run", e, unit="jobs_per_hour")
        return 1

    # parity gate: per-job summaries bit-identical between the arms
    keys = ("ok", "distinct", "generated", "depth", "level_sizes")
    parity = True
    mismatch = None
    for i, (a, b) in enumerate(zip(b_res, s_res)):
        if a is None or b is None or any(a[k] != b[k] for k in keys):
            parity = False
            mismatch = dict(
                job=i,
                batched=None if a is None else {k: a[k] for k in keys},
                sequential=None if b is None else {k: b[k] for k in keys},
            )
            break

    disp = max(b_stats["dispatches"], 1)
    out = {
        "metric": f"raft_sweep_service_{n_jobs}jobs",
        "value": round(n_jobs / b_wall * 3600.0, 1),
        "unit": "jobs_per_hour",
        "vs_baseline": round(s_wall / b_wall, 2),
        "parity": parity,
        "ok": parity and all(r is not None for r in b_res),
        "jobs": n_jobs,
        "wall_s": round(b_wall, 2),
        "sequential_jobs_per_hour": round(n_jobs / s_wall * 3600.0, 1),
        "sequential_wall_s": round(s_wall, 2),
        "buckets": b_stats["buckets"],
        "max_bucket_configs": b_stats["max_bucket"],
        "configs_per_dispatch": round(
            b_stats["config_dispatch_weight"] / disp, 2
        ),
        "batched_dispatches": b_stats["dispatches"],
        "programs_traced": b_stats["programs"],
        "device": b_doc["device"],
        "config": (
            "synthetic queue (seed "
            f"{os.environ.get('BENCH_SERVICE_SEED', '1')}, mr_width "
            f"{os.environ.get('BENCH_SERVICE_MR_WIDTH', '16')}, chunk "
            f"{os.environ.get('BENCH_SERVICE_CHUNK', '64')}, "
            + ("warm steady state: per-arm queue primed once)"
               if int(os.environ.get("BENCH_SERVICE_WARM", "0"))
               else "cold per-arm compiles, persistent cache off)")
        ),
    }
    if mismatch is not None:
        out["error"] = mismatch
    print(json.dumps(out))
    bench_out = os.environ.get("BENCH_OUT")
    if bench_out:
        record = {
            "schema": "tla-raft-bench/1",
            "metric": out["metric"],
            "config": out["config"],
            "jobs_per_hour": out["value"],
            "unit": out["unit"],
            "parity": out["parity"],
            "ok": out["ok"],
            "wall_s": out["wall_s"],
            "vs_baseline": out["vs_baseline"],
            "sequential_jobs_per_hour": out["sequential_jobs_per_hour"],
            "buckets": out["buckets"],
            "max_bucket_configs": out["max_bucket_configs"],
            "configs_per_dispatch": out["configs_per_dispatch"],
            "programs_traced": out["programs_traced"],
            "device": out["device"],
        }
        tmp = bench_out + ".tmp"
        with open(tmp, "w") as f:
            json.dump(record, f, indent=1)
        os.replace(tmp, bench_out)
        _append_trend(record, bench_out)
    if not keep_root:
        shutil.rmtree(base, ignore_errors=True)
    return 0 if parity else 1


def _bench_cfg():
    """The bench's config resolution, shared by every lever: RAFT_CFG
    (default the committed Raft.cfg) + the BENCH_SERVERS/VALS/MAX_ELECTION/
    MAX_RESTART scale-dial overrides."""
    from tla_raft_tpu.cfgparse import REPO_CFG, load_raft_config

    cfg = load_raft_config(os.environ.get("RAFT_CFG", REPO_CFG))
    overrides = {}
    if os.environ.get("BENCH_SERVERS"):
        overrides["n_servers"] = int(os.environ["BENCH_SERVERS"])
    if os.environ.get("BENCH_VALS"):
        overrides["n_vals"] = int(os.environ["BENCH_VALS"])
    if os.environ.get("BENCH_MAX_ELECTION"):
        overrides["max_election"] = int(os.environ["BENCH_MAX_ELECTION"])
    if os.environ.get("BENCH_MAX_RESTART"):
        overrides["max_restart"] = int(os.environ["BENCH_MAX_RESTART"])
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    return cfg


def _bench_tune(jax) -> int:
    """BENCH_TUNE=1: the autotuned-plan A/B (docs/PERF.md "Autotuned
    plans").

    Two in-process sweeps of the same config at BENCH_TUNE_DEPTH
    (default 12): the DEFAULTS arm (``plan=False`` — bit-for-bit the
    ``TLA_RAFT_PLAN=0`` path) vs the PLAN arm (the regime's knobs from
    a versioned plan cache).  The plan comes from BENCH_TUNE_PLAN (a
    plans.json path; default the committed cache,
    tla_raft_tpu/tune/plans.json); BENCH_TUNE_SEARCH=1 instead runs
    the coordinate-descent search right here and times it, so the
    record carries the honest search-cost-vs-steady-win ledger.  Each
    arm runs once untimed (compile prime) then BENCH_TUNE_REPS timed
    reps (default 2; best wall wins — single-core hosts time-slice the
    arms against the OS, so min is the honest point estimate).  Counts
    must be bit-identical across EVERY run of both arms: a plan may
    move shapes and schedules, never semantics.
    """
    import tempfile

    from tla_raft_tpu.check import run_check

    try:
        from tla_raft_tpu.tune import plans as tune_plans
        from tla_raft_tpu.tune import search as tune_search

        cfg = _bench_cfg()
        max_depth = int(os.environ.get("BENCH_TUNE_DEPTH", "12")) or None
        reps = max(1, int(os.environ.get("BENCH_TUNE_REPS", "2")))
        regime = tune_plans.regime_key(cfg, "jax")
        search_info = None
        if int(os.environ.get("BENCH_TUNE_SEARCH", "0")):
            pdir = tempfile.mkdtemp(prefix="bench_tune_")
            plan_path = os.path.join(pdir, "plans.json")
            t0 = time.monotonic()
            sres = tune_search.tune(
                cfg, backend="jax", path=plan_path, commit=True,
                max_depth=int(
                    os.environ.get("BENCH_TUNE_SEARCH_DEPTH", "6")
                ),
                top_k=int(os.environ.get("BENCH_TUNE_TOP_K", "2")),
                out=sys.stderr,
            )
            search_info = dict(
                sres["probe"],
                wall_s=round(time.monotonic() - t0, 2),
            )
        else:
            plan_path = (
                os.environ.get("BENCH_TUNE_PLAN")
                or tune_plans.plan_path()
            )
        knobs = tune_plans.resolve(cfg, "jax", path=plan_path)
        if not knobs:
            raise RuntimeError(
                f"no plan for regime {regime} in {plan_path!r} — run "
                "`python -m tla_raft_tpu.tune` first or set "
                "BENCH_TUNE_SEARCH=1"
            )
    except Exception as e:
        _emit_failure("bench_setup", e)
        return 1

    def run_arm(name: str, plan):
        best = None
        counts = None
        for rep in range(reps + 1):  # rep 0 = untimed compile prime
            t0 = time.monotonic()
            s = run_check(
                cfg, backend="jax", max_depth=max_depth, plan=plan,
                telemetry=True,
            )
            wall = time.monotonic() - t0
            got = (s["distinct"], s["generated"], s["depth"],
                   tuple(s["level_sizes"]), s["ok"])
            if counts is None:
                counts = got
            elif got != counts:
                raise RuntimeError(
                    f"tune arm {name} rep {rep}: counts drifted "
                    f"within the arm ({got[:3]} vs {counts[:3]})"
                )
            if rep == 0:
                continue
            tel = s.get("telemetry") or {}
            rec = {
                "wall_s": round(wall, 2),
                "dispatches": tel.get("dispatches"),
                "levels": tel.get("levels"),
                "levels_per_dispatch": round(
                    tel.get("levels", 0)
                    / max(tel.get("dispatches") or 1, 1), 3,
                ),
                "rate": round(s["distinct"] / wall, 1),
            }
            if plan and s.get("plan"):
                rec["knobs"] = s["plan"]
            if best is None or rec["wall_s"] < best["wall_s"]:
                best = rec
        best["counts"] = {
            "distinct": counts[0], "generated": counts[1],
            "depth": counts[2], "ok": counts[4],
        }
        print(
            f"[bench] tune arm {name}: best {best['wall_s']}s "
            f"({best['rate']}/s, {best['levels_per_dispatch']} "
            f"levels/dispatch)", file=sys.stderr,
        )
        return best, counts

    try:
        arm_p, c_p = run_arm("plan", plan_path)
        arm_d, c_d = run_arm("defaults", False)
    except Exception as e:
        _emit_failure("tune_run", e)
        return 1

    parity = c_p == c_d and bool(c_p[4])
    speedup = round(arm_d["wall_s"] / max(arm_p["wall_s"], 1e-9), 3)
    out = {
        "schema": "tla-raft-bench-ab/1",
        "metric": "tune",
        "arms": {"plan": arm_p, "defaults": arm_d},
        "unit": "seconds_wall",
        "speedup_vs_defaults": speedup,
        "regime": regime,
        "plan": knobs,
        "plan_source": plan_path,
        "reps": reps,
        "parity": parity,
        "ok": parity,
        "distinct": c_p[0],
        "generated": c_p[1],
        "depth": c_p[2],
        "device": str(jax.devices()[0]),
        "config": (
            f"{cfg.describe()}, depth<={max_depth}, "
            f"host_cpus={os.cpu_count()}"
        ),
    }
    if search_info is not None:
        out["search"] = search_info
    if not parity:
        out["error"] = {
            "plan_counts": list(c_p[:3]),
            "default_counts": list(c_d[:3]),
        }
    print(json.dumps(out))
    bench_out = os.environ.get("BENCH_OUT")
    if bench_out:
        tmp = bench_out + ".tmp"
        with open(tmp, "w") as f:
            json.dump(out, f, indent=1, sort_keys=True)
        os.replace(tmp, bench_out)
        _append_trend(out, bench_out)
    return 0 if parity else 1


def _bench_pool() -> int:
    """BENCH_POOL=N: worker-pool drain scaling — jobs/hour at 1..N
    workers over the same synthetic queue (ISSUE 19).

    Each arm submits the identical deterministic job set to a fresh
    queue root and drains it with n REAL worker processes
    (``python -m tla_raft_tpu.service run --once --worker workerK``),
    all sharing the persistent compile cache that an untimed priming
    drain fills first — the arms measure drain wall, not the one-time
    compile ladder.  Per-job results must be bit-identical across ALL
    arms (the pool must never buy throughput with correctness).
    Knobs: BENCH_POOL_JOBS (default 24), BENCH_POOL_MR_WIDTH (6),
    BENCH_POOL_SEED, BENCH_POOL_CHUNK, BENCH_POOL_ROOT (keep dirs).

    Scaling expectation is HOST-RELATIVE: on an N-core host the pool
    scales toward Nx; on a single-core host the workers time-slice one
    CPU and jobs/h stays ~flat (the record's config string names the
    cpu count so the trend gate compares like with like).
    """
    import shutil
    import subprocess
    import tempfile

    from tla_raft_tpu.platform import check_chip_workers
    from tla_raft_tpu.service.chaos import PARITY_KEYS, _job_set, _submit
    from tla_raft_tpu.service.queue import JobQueue

    # this parent stays off JAX; a chip-using pool runs one worker
    try:
        n_max = int(os.environ.get("BENCH_POOL", "0"))
        check_chip_workers(n_max)
        n_jobs = int(os.environ.get("BENCH_POOL_JOBS", "24"))
        seed = int(os.environ.get("BENCH_POOL_SEED", "1"))
        mr_width = int(os.environ.get("BENCH_POOL_MR_WIDTH", "6"))
        chunk = int(os.environ.get("BENCH_POOL_CHUNK", "64"))
        keep_root = os.environ.get("BENCH_POOL_ROOT")
        base = keep_root or tempfile.mkdtemp(prefix="bench_pool_")
        jobs = _job_set(n_jobs, seed, mr_width, chunk, 0)
    except Exception as e:
        _emit_failure("bench_setup", e, unit="jobs_per_hour")
        return 1

    def drain(n_workers: int, root: str) -> tuple[float, dict]:
        jids = _submit(root, jobs)
        env = dict(os.environ)
        env.pop("BENCH_POOL", None)
        t0 = time.monotonic()
        procs, logfs = [], []
        for i in range(n_workers):
            lf = open(os.path.join(root, f"worker{i + 1}.log"), "w")
            procs.append(subprocess.Popen(
                [sys.executable, "-m", "tla_raft_tpu.service", "run",
                 "--root", root, "--worker", f"worker{i + 1}",
                 "--once", "--min-bucket", "2", "--lease-ttl", "60"],
                env=env, stdout=lf, stderr=lf,
            ))
            logfs.append(lf)
        try:
            for p in procs:
                p.wait(timeout=3600)
        finally:
            for lf in logfs:
                lf.close()
        wall = time.monotonic() - t0
        bad = [p.returncode for p in procs if p.returncode != 0]
        if bad:
            raise RuntimeError(f"pool arm {n_workers}w: worker "
                               f"exit(s) {bad}")
        q = JobQueue(root)
        res = {j: q.load_result(j) for j in jids}
        missing = [j for j, r in res.items() if r is None]
        if missing:
            raise RuntimeError(
                f"pool arm {n_workers}w left {len(missing)} job(s) "
                f"undrained: {missing[:5]}"
            )
        return wall, res

    try:
        # untimed priming drain fills the shared compile cache
        drain(1, os.path.join(base, "prime"))
        arms: dict = {}
        golden = None
        parity = True
        mismatch = None
        for n in range(1, n_max + 1):
            wall, res = drain(n, os.path.join(base, f"pool{n}"))
            arms[f"workers{n}"] = dict(
                wall_s=round(wall, 2),
                jobs_per_hour=round(n_jobs / wall * 3600.0, 1),
            )
            if golden is None:
                golden = res
            else:
                for j, r in res.items():
                    g = golden[j]
                    if any(r.get(k) != g.get(k) for k in PARITY_KEYS):
                        parity = False
                        mismatch = dict(
                            arm=n, job=j,
                            got={k: r.get(k) for k in PARITY_KEYS},
                            want={k: g.get(k) for k in PARITY_KEYS},
                        )
            print(f"[bench] pool arm {n}w: {wall:.1f}s "
                  f"({arms[f'workers{n}']['jobs_per_hour']} jobs/h)",
                  file=sys.stderr)
    except Exception as e:
        _emit_failure("pool_run", e, unit="jobs_per_hour")
        return 1

    first = f"workers{n_max}"
    ncpu = os.cpu_count() or 1
    scaling = round(
        arms[first]["jobs_per_hour"] / arms["workers1"]["jobs_per_hour"],
        2,
    )
    # primary arm first: the pool at full width is the shipped config
    ordered = {first: arms[first]}
    ordered.update(
        (k, v) for k, v in arms.items() if k != first
    )
    out = {
        "schema": "tla-raft-bench-ab/1",
        "metric": "pool",
        "arms": ordered,
        "unit": "jobs_per_hour",
        "jobs": n_jobs,
        "scaling_vs_1worker": scaling,
        "host_cpus": ncpu,
        "parity": parity,
        "ok": parity,
        "device": next(
            (r["device"] for r in golden.values() if r.get("device")),
            None,
        ),
        "config": (
            f"synthetic queue (seed {seed}, mr_width {mr_width}, "
            f"chunk {chunk}, {n_jobs} jobs, warm shared compile "
            f"cache, host_cpus={ncpu})"
        ),
    }
    if mismatch is not None:
        out["error"] = mismatch
    print(json.dumps(out))
    bench_out = os.environ.get("BENCH_OUT")
    if bench_out:
        tmp = bench_out + ".tmp"
        with open(tmp, "w") as f:
            json.dump(out, f, indent=1, sort_keys=True)
        os.replace(tmp, bench_out)
        _append_trend(out, bench_out)
    if not keep_root:
        shutil.rmtree(base, ignore_errors=True)
    return 0 if parity else 1


def main():
    os.environ.setdefault("JAX_TRACEBACK_FILTERING", "off")
    # mesh benches on a virtual CPU mesh need the device-count XLA flag
    # pinned BEFORE the first jax import (tla_raft_tpu.xla_env does not
    # import jax); real multi-chip meshes need nothing here
    mesh_n = int(os.environ.get("BENCH_MESH", "0"))
    if mesh_n and os.environ.get("JAX_PLATFORMS") == "cpu":
        from tla_raft_tpu.xla_env import ensure_virtual_cpu_mesh

        ensure_virtual_cpu_mesh(mesh_n)
    # BENCH_SERVICE=1: the sweep-service jobs/hour A/B instead of the
    # single-sweep throughput bench (docs/SERVICE.md).  It and the pool
    # arm start chip-using children, so this process stays off JAX.
    if int(os.environ.get("BENCH_SERVICE", "0")):
        return _bench_service()

    # BENCH_POOL=N: worker-pool drain scaling (jobs/hour at 1..N real
    # worker processes over the same queue — docs/SERVICE.md)
    if int(os.environ.get("BENCH_POOL", "0")):
        return _bench_pool()

    jax = _init_jax()

    # BENCH_TUNE=1: the autotuned-plan A/B (committed plan cache vs
    # hand-set defaults — docs/PERF.md "Autotuned plans")
    if int(os.environ.get("BENCH_TUNE", "0")):
        return _bench_tune(jax)

    # every stage before the engine run is wrapped so an exception
    # anywhere still yields a parseable ok:false line (ADVICE r4 #2:
    # the round-3 unparseable-artifact failure mode lived exactly in
    # these unwrapped setup stages)
    try:
        from tla_raft_tpu.engine import JaxChecker
        from tla_raft_tpu.oracle import OracleChecker

        cfg = _bench_cfg()
    except Exception as e:
        _emit_failure("config_setup", e)
        return 1
    # Default: a depth-19 prefix (~3.4M distinct states — deep enough that
    # per-level fixed costs amortize into the steady-state rate).  The
    # full sweep of Raft.cfg runs for hours — the full-space golden
    # record lives in
    # BASELINE.md and gates any run that does reach the fixpoint
    # (BENCH_MAX_DEPTH=0 requests that).
    try:
        md_env = os.environ.get("BENCH_MAX_DEPTH", "19")
        max_depth = int(md_env) or None
    except Exception as e:
        _emit_failure("bench_setup", e)
        return 1
    # Build the kernel outside the timed region either way, so wall_s
    # measures the same thing whether or not BENCH_CHUNK is set (the
    # engine reuses this lru-cached instance).
    try:
        from tla_raft_tpu.ops.successor import get_kernel

        kern_K = get_kernel(cfg).K
    except Exception as e:
        _emit_failure("kernel_setup", e)
        return 1
    try:
        if os.environ.get("BENCH_CHUNK"):
            chunk = int(os.environ["BENCH_CHUNK"])
        else:
            # keep the expand program's chunk*K lane budget roughly
            # constant across the scale dial: 8192 is tuned for S=3
            # (K=696); S=7's K=3696 at the same chunk overflows HBM
            # (measured: 24.3G of 15.75G).  Largest pow2 <=
            # 8192 * 696 / K, clamped [1024, 8192].
            budget = max(1, 8192 * 696 // kern_K)
            chunk = max(1024, min(8192, 1 << (budget.bit_length() - 1)))
        # The oracle gold prefix is a secondary parity anchor (the
        # primary is cpubase's per-level counts to native_depth); its
        # default depth must scale down with S — the pure-Python S! fold
        # makes depth 12 at S=5 a ~45-min CPU stall before the chip does
        # any work (measured), while depth 9 keeps the same gate r3
        # shipped in ~1 min.
        default_gold = {3: 12, 5: 9}.get(cfg.S, 7)
        gold_depth = int(
            os.environ.get("BENCH_GOLD_DEPTH", str(default_gold))
        )
        if max_depth is not None:
            gold_depth = min(gold_depth, max_depth)
    except Exception as e:
        _emit_failure("bench_setup", e)
        return 1

    # one timed oracle run: golden prefix + the (weak) Python baseline rate
    try:
        t0 = time.monotonic()
        gold = OracleChecker(cfg).run(max_depth=gold_depth)
        o_dt = time.monotonic() - t0
        oracle_rate = gold.distinct / o_dt
        assert gold.ok, "oracle found a violation on a known-clean config"
    except Exception as e:
        _emit_failure("golden_oracle", e)
        return 1

    # the HONEST CPU baseline: the multithreaded native C++ checker of the
    # same semantics (native/cpubase.cpp — the `tlc -workers N` stand-in;
    # TLC itself is an external jar that cannot run here).  vs_baseline is
    # measured against THIS, on the deepest prefix it can do in reasonable
    # time; its per-level counts double as another parity anchor.
    import json as _json
    import subprocess as _sp

    # the native-baseline SETUP (import + depth parse) is part of the
    # parseable-failure contract like every other pre-engine stage; only
    # the baseline RUN below is allowed to fail soft (the bench is still
    # meaningful without a native rate)
    try:
        from tla_raft_tpu.native import build_cpubase

        native_depth = int(os.environ.get(
            "BENCH_NATIVE_DEPTH", str(min(max_depth or 19, 19))
        ))
    except Exception as e:
        _emit_failure("native_setup", e)
        return 1
    native = None
    try:
        nb = build_cpubase()
        # 4 threads = the reference's own parallelism (`-workers 4`,
        # /root/reference/myrun.sh:3), whatever this host's core count;
        # host_cores is recorded so the ratio can be read honestly
        nthreads = int(os.environ.get("BENCH_NATIVE_THREADS", "4"))
        out_n = _sp.run(
            [nb, str(cfg.S), str(cfg.V), str(cfg.max_election),
             str(cfg.max_restart), str(native_depth), str(nthreads)],
            capture_output=True, text=True, timeout=3600, check=True,
        )
        native = _json.loads(out_n.stdout)
    except Exception as e:  # keep benching even if the baseline breaks
        print(f"[bench] native baseline failed: {e}", file=sys.stderr)

    # one full engine run; per-level timing feeds the steady-state metric
    t0 = time.monotonic()
    levels = []  # (level, distinct, elapsed)

    def progress(s):
        levels.append((s["level"], s["distinct"], s["elapsed"]))
        print(
            f"[bench] level {s['level']}: frontier {s['frontier']}, "
            f"distinct {s['distinct']}, {s['distinct'] / max(s['elapsed'], 1e-9):,.0f}/s",
            file=sys.stderr,
        )
        sys.stderr.flush()

    try:
        # BENCH_HASHSTORE=0 pins the sort-based visited path — the A/B
        # lever for the hashstore-vs-lexsort dedup comparison
        # (BENCH_HASHSTORE vs BENCH_r06 at equal config); default
        # follows the engine default (on)
        use_hs = bool(int(os.environ.get("BENCH_HASHSTORE", "1")))
        # BENCH_PIPELINE=0 pins the serial fetch-after-dispatch chain —
        # the A/B lever for the async intra-level pipeline (docs/PERF.md
        # "Async level pipeline"); counts are bit-identical either way,
        # so the parity gates hold in both arms.  BENCH_PIPELINE_WINDOW
        # overrides the in-flight group window (default 2).
        use_pipe = bool(int(os.environ.get("BENCH_PIPELINE", "1")))
        pipe_window = (
            int(os.environ["BENCH_PIPELINE_WINDOW"])
            if os.environ.get("BENCH_PIPELINE_WINDOW") else None
        )
        # BENCH_MXU=0 pins the legacy per-lane guards/materialize — the
        # A/B lever for the MXU-native expand (docs/PERF.md "MXU-native
        # expand"); counts are bit-identical either way, so the parity
        # gates hold in both arms
        use_mxu = bool(int(os.environ.get("BENCH_MXU", "1")))
        # BENCH_MEGAKERNEL=0 pins the staged per-stage program chain —
        # the A/B lever for the whole-level megakernel (docs/PERF.md
        # "Whole-level megakernel"); counts are bit-identical either
        # way, so the parity gates hold in both arms
        use_mega = bool(int(os.environ.get("BENCH_MEGAKERNEL", "1")))
        # BENCH_SUPERSTEP=0 pins the per-level fused path (span 1) —
        # the A/B lever for the multi-level resident supersteps
        # (docs/PERF.md "Multi-level supersteps"); 1/unset keeps the
        # engine default span (TLA_RAFT_SUPERSTEP, 4).  Counts are
        # bit-identical either way, so the parity gates hold in both
        # arms.
        ss_env = os.environ.get("BENCH_SUPERSTEP")
        use_superstep = (
            None if ss_env is None or int(ss_env) else 1
        )
        # BENCH_TELEMETRY=0 disables the run flight recorder — the
        # overhead A/B lever for the telemetry hub (docs/
        # OBSERVABILITY.md; target <= 2% wall at depth 12).  With the
        # hub on, level_seconds/dispatches_per_level in the record are
        # sourced FROM the hub (one bookkeeping) instead of bench-local
        # timestamp math; counts are bit-identical either way.
        use_tel = bool(int(os.environ.get("BENCH_TELEMETRY", "1")))
        # BENCH_TIERED=1 caps the hot visited slab at
        # BENCH_TIERED_BYTES (default 128 KiB — the reference depth-12
        # sweep's 47k distinct states overflow its 8,191 resident
        # entries ~5.7x) so the run demotes whole generations to
        # host/disk (store/tiered.py) — the out-of-core A/B lever
        # (docs/PERF.md "Tiered visited store").  Counts are
        # bit-identical either way; the record carries the demotion +
        # probe-wait accounting so the spill-overlap acceptance
        # (probe-wait << level wall) is machine-checkable.
        tier_bytes = (
            int(float(os.environ.get("BENCH_TIERED_BYTES",
                                     str(1 << 17))))
            if int(os.environ.get("BENCH_TIERED", "0")) else 0
        )
        # BENCH_SIEVE=0 disables the device-resident spill sieve
        # (ops/sieve.py), reverting a tiered run to PR 12's span-1
        # stand-down — the A/B lever for the sieve's dispatch-
        # amortization recovery (docs/PERF.md "Spill sieve +
        # compaction").  Counts are bit-identical either way; the
        # interesting delta is levels_per_dispatch under spill.
        use_sieve = bool(int(os.environ.get("BENCH_SIEVE", "1")))
        # BENCH_AUDIT=1 arms the end-to-end integrity audit at
        # BENCH_AUDIT_N rows/level (default 64) — the A/B lever for the
        # audit-mode overhead record (docs/ROBUSTNESS.md; target < 5%
        # at --audit 64).  Counts are bit-identical either way (the
        # audit only READS; it rewinds solely on real corruption).
        # Single-device engine only; the mesh arms ignore it.
        audit_n = (
            int(os.environ.get("BENCH_AUDIT_N", "64"))
            if int(os.environ.get("BENCH_AUDIT", "0")) else 0
        )
    except Exception as e:
        _emit_failure("bench_setup", e)
        return 1
    exchange = None
    peak_dev_rows = None
    try:
        if mesh_n:
            # distributed bench: the sharded checker on an N-device mesh
            # (BENCH_MESH_DEEP=1 selects the 1/D-sharded deep-sweep path
            # with the sieve+compress exchange; its per-level exchange
            # bytes land in the canonical record below)
            from tla_raft_tpu.parallel import ShardedChecker, make_mesh

            deep = bool(int(os.environ.get("BENCH_MESH_DEEP", "0")))
            fpdir = os.environ.get("BENCH_FPSTORE", "") or None
            if deep and fpdir is None:
                fpdir = "/tmp/bench_mesh_fps"
            mchk = ShardedChecker(
                cfg, make_mesh(mesh_n),
                cap_x=(int(os.environ["BENCH_CAP_X"])
                       if os.environ.get("BENCH_CAP_X") else None),
                host_store_dir=fpdir, deep=deep,
                seg_rows=int(os.environ.get("BENCH_SEG_ROWS", str(1 << 15))),
                progress=progress, use_hashstore=use_hs,
                pipeline=use_pipe, pipeline_window=pipe_window,
                use_mxu=use_mxu,
            )
            res = mchk.run(max_depth=max_depth)
            if mchk.meter.levels:
                exchange = mchk.meter.summary()
            peak_dev_rows = getattr(mchk, "peak_dev_rows", None)
            pipe_on, pipe_win = mchk.pipeline, mchk.pipeline_window
        else:
            # per-level program-dispatch ledger (analysis.sanitize
            # choke-point accounting): the megakernel A/B record reports
            # dispatches/level in both arms
            from tla_raft_tpu.analysis import sanitize as _san
            from tla_raft_tpu.obs import telemetry as _tel

            dlog = _san.DispatchLog()
            _san.set_dispatch_sink(dlog)
            hub = None
            if use_tel:
                # in-memory flight recorder (no run dir): the hub's
                # aggregates are the record's level accounting source
                hub = _tel.TelemetryHub()
                _tel.install(hub)
                _san.obs_watch_compiles()
                _tel.run_begin(config=cfg.describe(), bench=True)
            try:
                chk1 = JaxChecker(
                    cfg, chunk=chunk, progress=progress,
                    use_hashstore=use_hs,
                    pipeline=use_pipe, pipeline_window=pipe_window,
                    use_mxu=use_mxu, megakernel=use_mega, audit=audit_n,
                    superstep=use_superstep,
                    store_bytes=tier_bytes or None,
                    sieve=use_sieve,
                )
                res = chk1.run(max_depth=max_depth)
            finally:
                _san.set_dispatch_sink(None)
                if hub is not None:
                    _tel.install(None)
            dlog.close()
            pipe_on, pipe_win = chk1.pipeline, chk1.pipeline_window
    except Exception as e:
        _emit_failure("engine_run", e)
        return 1
    dt = time.monotonic() - t0
    overall_rate = res.distinct / dt

    # steady-state rate: best window excluding the cold-compile ramp
    # (the frontier grows ~1.6x/level, so the last 2-3 levels hold most
    # of the distinct states and a qualifying window covers >60% of the
    # run).  vs_baseline uses the rate restricted to the SAME depth
    # prefix the native baseline ran (ADVICE r3 low #4).
    steady = _best_window_rate(levels, overall_rate)
    # fallback for the prefix rate stays prefix-restricted (cumulative
    # states/time at the prefix end), so vs_baseline never mixes depths
    pre = [x for x in levels if x[0] <= native_depth]
    prefix_fallback = (
        pre[-1][1] / pre[-1][2] if pre and pre[-1][2] > 0 else overall_rate
    )
    steady_prefix = _best_window_rate(
        levels, prefix_fallback, max_level=native_depth
    )

    # ---- parity gates ---------------------------------------------------
    prefix = gold.level_sizes
    parity = res.ok and res.level_sizes[: len(prefix)] == prefix
    if native is not None:
        nlv = native["level_sizes"]
        n = min(len(nlv), len(res.level_sizes))
        parity = parity and list(res.level_sizes[:n]) == nlv[:n]
    golden_key = (cfg.S, cfg.V, cfg.max_election, cfg.max_restart)
    full_golden = GOLDEN_FULL.get(golden_key) if max_depth is None else None
    golden_full_match = None
    advisory_mismatch = False
    if full_golden is not None:
        golden_full_match = (
            (res.distinct, res.generated, res.depth) == full_golden
        )
        if golden_key in GOLDEN_FULL_SINGLE_SOURCE:
            if not golden_full_match:
                advisory_mismatch = True
                print(
                    f"[bench] WARNING: fixpoint totals disagree with the "
                    f"single-source golden row {golden_key} "
                    f"(got {(res.distinct, res.generated, res.depth)}, "
                    f"pinned {full_golden}); advisory only — parity "
                    "reported as null (indeterminate), not failed",
                    file=sys.stderr,
                )
        else:
            parity = parity and golden_full_match
    pinned = GOLDEN_LEVELS.get(golden_key)
    if pinned is not None:
        n = min(len(pinned), len(res.level_sizes))
        parity = parity and list(res.level_sizes[:n]) == pinned[:n]
    if parity and advisory_mismatch:
        # every GATING anchor passed but the single-source advisory row
        # disagreed: the verdict is indeterminate, not a failure
        parity = None

    out = {
        "metric": "raft_cfg_full_check"
        if max_depth is None
        else f"raft_cfg_check_depth{max_depth}",
        "value": round(steady, 1),
        "unit": "distinct_states_per_sec",
        "vs_baseline": round(
            (steady_prefix / native["rate"]) if native
            else (steady / oracle_rate), 2
        ),
        "steady_rate_same_prefix": round(steady_prefix, 1),
        "parity": parity,
        "distinct": res.distinct,
        "generated": res.generated,
        "depth": res.depth,
        "ok": res.ok,
        "wall_s": round(dt, 2),
        "overall_rate": round(overall_rate, 1),
        "baseline": (
            {
                "impl": "cpubase_cpp",
                "rate": round(native["rate"], 1),
                "states": native["distinct"],
                "depth_cap": native_depth,
                "wall_s": native["seconds"],
                "threads": native["threads"],
                "host_cores": os.cpu_count(),
            }
            if native
            else {"impl": "python_oracle", "rate": round(oracle_rate, 1)}
        ),
        "baseline_python_oracle": {
            "rate": round(oracle_rate, 1),
            "states": gold.distinct,
            "depth_cap": gold_depth,
            "wall_s": round(o_dt, 2),
        },
        "device": str(jax.devices()[0]),
        "config": cfg.describe(),
        "hashstore": use_hs,
        "pipeline": pipe_on,
        "pipeline_window": pipe_win if pipe_on else 0,
        "mxu": use_mxu,
        # the EFFECTIVE state, not the lever: a sort-path arm
        # (BENCH_HASHSTORE=0) runs staged regardless of the env flag
        "megakernel": (
            bool(getattr(chk1, "megakernel", False)) if not mesh_n
            else False
        ),
        # the EFFECTIVE superstep span (1 = per-level; the lever is
        # BENCH_SUPERSTEP=0/1, the span itself TLA_RAFT_SUPERSTEP)
        "superstep": (
            int(getattr(chk1, "superstep_span", 1)) if not mesh_n else 1
        ),
        "audit": audit_n if not mesh_n else 0,
        # the tiered-store lever (0 = hot-only): budget + the demotion
        # and per-tier probe accounting when it actually spilled
        "tiered_bytes": tier_bytes if not mesh_n else 0,
        # the sieve lever's EFFECTIVE state (off on the mesh arms and
        # whenever the engine ran without tiering)
        "sieve": (
            bool(getattr(chk1, "sieve_enabled", False)) if not mesh_n
            else False
        ),
    }
    if not mesh_n and tier_bytes and getattr(chk1, "tiered", None):
        ts = chk1.tiered.stats
        out["tiered"] = dict(
            ts,
            generations=len(chk1.tiered.gens),
            probe_wait_s=round(ts["probe_wait_s"], 6),
            cold_load_s=round(ts["cold_load_s"], 6),
            compact_s=round(ts.get("compact_s", 0.0), 6),
        )
        # superstep sieve accounting: how often an in-kernel sieve hit
        # stopped a window early (each stop = one per-level replay)
        ss = getattr(chk1, "_ss_stats", None)
        if ss:
            out["superstep_stats"] = {
                k: int(v) for k, v in sorted(ss.items())
            }
    if not mesh_n and getattr(chk1, "_fpager", None) is not None:
        # spilled-frontier paging (engine/bfs.py FrontierPager): disk
        # traffic of levels whose working set outgrew TLA_RAFT_DEV_BYTES
        fp = chk1._fpager.stats
        out["fseg"] = dict(fp, fseg_load_s=round(fp["fseg_load_s"], 6))
    if not mesh_n:
        # per-level wall clock + program dispatches (the fused-vs-
        # staged A/B's secondary metric: launches/level is exactly
        # what the megakernel removes).  With the telemetry hub on
        # (BENCH_TELEMETRY=1, default) both come from the hub's
        # unified accounting; the bench-local fallback keeps the
        # BENCH_TELEMETRY=0 arm honest.
        snap = hub.snapshot() if hub is not None else None
        out["telemetry"] = bool(hub is not None)
        if snap is not None and snap["levels"]:
            out["level_seconds"] = snap["level_seconds"]
            out["dispatches_per_level"] = snap["dispatches_per_level"]
        else:
            out["level_seconds"] = [
                round(levels[i][2] - (levels[i - 1][2] if i else 0.0), 4)
                for i in range(len(levels))
            ]
            out["dispatches_per_level"] = list(dlog.per_level)
        out["steady_max_dispatches_per_level"] = dlog.steady_max()
        # dispatch amortization: BFS levels retired per engine program
        # dispatch (the superstep's headline metric — 1/span in steady
        # state, 1.0 on the per-level paths modulo redos)
        out["levels_per_dispatch"] = round(
            len(dlog.per_level) / max(dlog.total, 1), 3
        )
    if full_golden is not None:
        out["golden_full"] = {
            "distinct": full_golden[0],
            "generated": full_golden[1],
            "depth": full_golden[2],
            "match": golden_full_match,
            "advisory": golden_key in GOLDEN_FULL_SINGLE_SOURCE,
        }
    if mesh_n:
        out["mesh"] = mesh_n
        out["mesh_deep"] = bool(int(os.environ.get("BENCH_MESH_DEEP", "0")))
        if peak_dev_rows is not None:
            out["peak_dev_rows"] = peak_dev_rows
    if exchange is not None:
        out["exchange"] = exchange
    if parity is False:
        out["error"] = {
            "engine_levels": list(res.level_sizes[: len(prefix) + 2]),
            "golden_levels": list(prefix),
            "engine_ok": res.ok,
            "violation": str(res.violation[0]) if res.violation else None,
        }
    print(json.dumps(out))
    # canonical round record (BENCH_OUT=BENCH_rNN.json): one top-level
    # machine-readable artifact per campaign step so the perf trajectory
    # is greppable across rounds — config, steady rate, exchange
    # bytes/level (mesh-deep runs), parity flag, wall
    bench_out = os.environ.get("BENCH_OUT")
    if bench_out:
        record = {
            "schema": "tla-raft-bench/1",
            "metric": out["metric"],
            "config": out["config"],
            "steady_rate": out["value"],
            "unit": out["unit"],
            "parity": out["parity"],
            "ok": out["ok"],
            "wall_s": out["wall_s"],
            "distinct": out["distinct"],
            "generated": out["generated"],
            "depth": out["depth"],
            "vs_baseline": out["vs_baseline"],
            "device": out["device"],
            "hashstore": out["hashstore"],
            "pipeline": out["pipeline"],
            "pipeline_window": out["pipeline_window"],
            "mxu": out["mxu"],
            "megakernel": out["megakernel"],
            "superstep": out["superstep"],
            "audit": out["audit"],
        }
        for k in ("mesh", "mesh_deep", "peak_dev_rows", "exchange",
                  "telemetry", "level_seconds", "dispatches_per_level",
                  "steady_max_dispatches_per_level",
                  "levels_per_dispatch", "tiered_bytes", "tiered",
                  "sieve", "superstep_stats", "fseg"):
            if k in out:
                record[k] = out[k]
        tmp = bench_out + ".tmp"
        with open(tmp, "w") as f:
            json.dump(record, f, indent=1)
        os.replace(tmp, bench_out)
        _append_trend(record, bench_out)
    # parity None = advisory-only disagreement (indeterminate): exit 0
    # so a single-source row can never fail a correct chip run
    return 1 if parity is False else 0


if __name__ == "__main__":
    sys.exit(main())
