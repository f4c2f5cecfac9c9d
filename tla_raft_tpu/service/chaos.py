"""Deterministic multi-worker chaos campaigns for the sweep pool.

``python -m tla_raft_tpu.service chaos`` launches N supervised workers
against a synthetic queue (scripts/queue_synth.py job mix, plus
optional deliberately-violating configs), applies a per-worker fault
schedule, and gates drain-to-convergence against a clean sequential
arm:

    python -m tla_raft_tpu.service chaos --base /tmp/fleet \\
        --jobs 60 --workers 3 --lease-ttl 2 \\
        --schedule "worker2:kill@bucket.level#2;worker3:pause@lease.renew#4"

**Schedule grammar** — ``worker:action@site[#n]`` items separated by
``,`` or ``;``: the named worker is launched with the corresponding
``TLA_RAFT_FAULT`` trigger (``site:action@n``), so the fault fires at
the site's Nth hit *inside that worker*, deterministically
(resilience/faults.py counts per-process).  Sites and actions are
validated by :class:`~tla_raft_tpu.resilience.faults.FaultPlan` at
parse time; the pool-relevant ones are ``bucket.level`` (top of each
batched-bucket level), ``lease.renew`` (top of each lease heartbeat)
and the writer sites (``lease.tmp``, ``result.commit``, ...), with
actions ``kill`` (SIGKILL — worker dies, peers recover its jobs),
``pause`` (SIGSTOP — the zombie case: the supervisor SIGCONTs the
worker after its leases aged out, and fencing must make it abandon),
``torn``/``flip``/``fail`` as in the single-worker campaigns.

**The campaign**:

1. submit the same deterministic job set (ids ``synth0000``...) to two
   queue roots: ``<base>/golden`` and ``<base>/fleet``;
2. drain golden with ONE clean sequential worker (``--no-batch``, no
   faults) — this arm *is* the "sequential check.py" reference, traces
   included;
3. drain fleet with N pool workers under the schedule, supervising:
   a SIGSTOPped worker is SIGCONTed after ``2 * lease_ttl`` (past the
   TTL, so its claims were requeued — the zombie wake-up), and every
   job's ``result.json`` (mtime, size) is watched from the moment it
   first appears — any later change is a duplicated terminal commit;
4. gate: queue drained, zero poisoned (``failed/`` quarantine empty),
   zero result rewrites, per-job counts bit-identical to golden,
   violating jobs carry a reconstructed trace equal to golden's, and
   the pool's fencing counter covers the scheduled pauses.

The report prints as one JSON line; exit 0 iff every gate held.
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import time

from ..resilience.faults import FaultPlan

PARITY_KEYS = ("ok", "distinct", "generated", "depth", "level_sizes")


# ---------------------------------------------------------------------------
# schedule grammar
# ---------------------------------------------------------------------------


def parse_schedule(spec: str) -> dict[str, str]:
    """``worker:action@site[#n]`` items -> {worker: TLA_RAFT_FAULT spec}.

    Multiple items for one worker join into one comma-separated plan.
    Site/action names are validated by building the per-worker
    FaultPlan here, so a typo'd schedule fails the campaign at parse
    time instead of silently testing nothing.
    """
    out: dict[str, list[str]] = {}
    for item in (spec or "").replace(";", ",").split(","):
        item = item.strip()
        if not item:
            continue
        try:
            worker, rest = item.split(":", 1)
            action, sitespec = rest.split("@", 1)
        except ValueError:
            raise ValueError(
                f"chaos schedule {item!r}: expected "
                "worker:action@site[#n]"
            ) from None
        n = 1
        if "#" in sitespec:
            sitespec, ns = sitespec.split("#", 1)
            n = int(ns)
        trigger = f"{sitespec.strip()}:{action.strip()}@{n}"
        out.setdefault(worker.strip(), []).append(trigger)
    plans = {w: ",".join(ts) for w, ts in out.items()}
    for w, p in plans.items():
        FaultPlan(p)  # validate; raises ValueError on unknown names
    return plans


# ---------------------------------------------------------------------------
# queue construction
# ---------------------------------------------------------------------------


def _job_set(n_jobs: int, seed: int, mr_width: int, chunk: int,
             violations: int):
    """[(job_id, cfg, max_depth, options)] — deterministic ids so the
    golden and fleet roots carry the SAME jobs and compare 1:1."""
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__)))),
        "scripts",
    ))
    import queue_synth

    from ..config import RaftConfig

    jobs = [
        (f"synth{i:04d}", cfg, cap, opt)
        for i, (cfg, cap, opt) in enumerate(
            queue_synth.synth_jobs(n_jobs, seed, mr_width, chunk)
        )
    ]
    for k in range(violations):
        # deliberately-violating members (negated-probe invariant):
        # their own shape bucket, so the batched path must reconstruct
        # their counterexample traces service-side
        cfg = RaftConfig(
            n_servers=2, n_vals=1, max_election=1, max_restart=k,
            invariants=("~RaftCanCommt",),
        )
        jobs.append((f"viol{k:03d}", cfg, None, dict(chunk=chunk)))
    return jobs


def _submit(root: str, jobs) -> list[str]:
    from .queue import JobQueue

    q = JobQueue(root)
    for jid, cfg, cap, opt in jobs:
        q.submit(cfg, max_depth=cap, options=opt, job_id=jid)
    return [j[0] for j in jobs]


# ---------------------------------------------------------------------------
# worker processes
# ---------------------------------------------------------------------------


def _spawn(root: str, name: str, args, fault: str = "",
           batch: bool = True):
    # the workers share the persistent compile cache (platform.py): later
    # workers, and the fleet arm after golden, ride programs already
    # compiled
    env = dict(os.environ)
    env["TLA_RAFT_FAULT"] = fault
    cmd = [
        sys.executable, "-m", "tla_raft_tpu.service", "run",
        "--root", root, "--worker", name,
        "--poll", str(args.poll), "--max-idle", str(args.max_idle),
        "--lease-ttl", str(args.lease_ttl),
        "--min-bucket", str(args.min_bucket),
    ]
    if not batch:
        cmd.append("--no-batch")
    logdir = os.path.join(root, "logs")
    os.makedirs(logdir, exist_ok=True)
    logf = open(os.path.join(logdir, f"{name}.log"), "w")
    return subprocess.Popen(cmd, env=env, stdout=logf, stderr=logf), logf


def _proc_state(pid: int) -> str:
    """One-char process state from /proc (T = stopped); '?' off-Linux
    or when the process is gone."""
    try:
        with open(f"/proc/{pid}/stat") as fh:
            # field 3, after the parenthesised comm (which may itself
            # contain spaces — split from the right of the last ')')
            return fh.read().rsplit(")", 1)[1].split()[0]
    except (OSError, IndexError):
        return "?"


def _result_stamp(path: str):
    try:
        st = os.stat(path)
        return (st.st_mtime_ns, st.st_size)
    except OSError:
        return None


# ---------------------------------------------------------------------------
# the campaign
# ---------------------------------------------------------------------------


def run_campaign(args, out=sys.stderr) -> dict:
    from ..platform import check_chip_workers

    t0 = time.monotonic()
    # the fleet runs N workers at once (golden runs alone, before it);
    # this process never touches JAX; a chip worker runs alone
    check_chip_workers(args.workers)
    plans = parse_schedule(args.schedule)
    names = [f"worker{i + 1}" for i in range(args.workers)]
    unknown = sorted(set(plans) - set(names))
    if unknown:
        raise ValueError(
            f"chaos schedule names unknown worker(s) {unknown} "
            f"(launching {names})"
        )
    jobs = _job_set(args.jobs, args.seed, args.mr_width, args.chunk,
                    args.violations)
    base = args.base
    golden_root = os.path.join(base, "golden")
    fleet_root = os.path.join(base, "fleet")
    jids = _submit(golden_root, jobs)
    _submit(fleet_root, jobs)

    def say(msg):
        print(f"[chaos] {msg}", file=out)
        out.flush()

    from .queue import JobQueue

    # -- golden arm: one clean sequential worker -----------------------
    say(f"golden arm: draining {len(jids)} jobs sequentially")
    p, logf = _spawn(golden_root, "golden", args, fault="",
                     batch=False)
    try:
        p.wait(timeout=args.timeout)
    except subprocess.TimeoutExpired:
        p.kill()
        raise RuntimeError(
            f"golden arm did not drain within {args.timeout}s"
        )
    finally:
        logf.close()
    gq = JobQueue(golden_root)
    golden = {j: gq.load_result(j) for j in jids}
    missing = [j for j, r in golden.items() if r is None]
    if missing:
        raise RuntimeError(
            f"golden arm left {len(missing)} job(s) without results: "
            f"{missing[:5]}"
        )

    # -- fleet arm: N workers under the schedule -----------------------
    say(
        f"fleet arm: {args.workers} worker(s), schedule "
        f"{args.schedule!r}"
    )
    procs: dict[str, subprocess.Popen] = {}
    logs = []
    for name in names:
        procs[name], lf = _spawn(
            fleet_root, name, args, fault=plans.get(name, ""),
            batch=True,
        )
        logs.append(lf)
    fq = JobQueue(fleet_root, worker="chaos-supervisor",
                  lease_ttl=args.lease_ttl)
    resume_after = 2.0 * args.lease_ttl
    stopped_at: dict[str, float] = {}
    resumed: list[str] = []
    sup_requeued = 0
    stamps: dict[str, tuple] = {}
    rewrites: list[str] = []
    deadline = time.monotonic() + args.timeout
    while any(p.poll() is None for p in procs.values()):
        if time.monotonic() > deadline:
            for name, p in procs.items():
                if p.poll() is None:
                    try:
                        os.kill(p.pid, signal.SIGCONT)
                    except OSError:
                        pass
                    p.kill()
            raise RuntimeError(
                f"fleet arm did not drain within {args.timeout}s"
            )
        for name, p in procs.items():
            if p.poll() is not None:
                continue
            if _proc_state(p.pid) == "T":
                now = time.monotonic()
                if name not in stopped_at:
                    stopped_at[name] = now
                    say(f"{name} stopped (SIGSTOP observed); "
                        f"resuming in {resume_after:.1f}s")
                elif now - stopped_at[name] >= resume_after:
                    # before waking the zombie, run the same stale-
                    # lease sweep any pool peer runs each pass: the
                    # peers may be deep inside a bucket compile/compute
                    # and not pass the sweep during the stop window, in
                    # which case the zombie would wake to find its
                    # leases untouched and the campaign would test
                    # nothing — the supervisor's sweep guarantees the
                    # leases actually changed hands past the TTL
                    requeued = fq.requeue_stale()
                    sup_requeued += len(requeued)
                    os.kill(p.pid, signal.SIGCONT)
                    resumed.append(name)
                    stopped_at.pop(name)
                    say(f"{name} resumed (zombie wake-up: "
                        f"{len(requeued)} of its lease(s) were "
                        "requeued past the TTL while stopped)")
        # duplicated-terminal-commit watch: a result.json that changes
        # AFTER it first appeared was committed twice (done jobs are
        # never requeued, so there is no legitimate second commit)
        for jid in jids:
            path = os.path.join(fq.job_dir(jid), "result.json")
            st = _result_stamp(path)
            if st is None:
                continue
            if jid in stamps and stamps[jid] != st:
                rewrites.append(jid)
                stamps[jid] = st
            elif jid not in stamps:
                stamps[jid] = st
        time.sleep(0.3)
    for lf in logs:
        lf.close()
    exits = {n: p.returncode for n, p in procs.items()}

    # every scheduled trigger must actually have fired (the fault
    # plan prints "[fault] site:action@n" when it does) — a campaign
    # whose fault never hit its site tested nothing and must say so
    unfired: list[str] = []
    for name, plan in plans.items():
        try:
            with open(os.path.join(fleet_root, "logs",
                                   f"{name}.log")) as fh:
                text = fh.read()
        except OSError:
            text = ""
        for trig in plan.split(","):
            if f"[fault] {trig}" not in text:
                unfired.append(f"{name}:{trig}")

    # -- gates ---------------------------------------------------------
    fleet = {j: fq.load_result(j) for j in jids}
    undrained = [j for j, r in fleet.items() if r is None]
    mismatches = []
    trace_bad = []
    n_viol = 0
    for j in jids:
        g, f = golden[j], fleet.get(j)
        if f is None:
            continue
        if any(g.get(k) != f.get(k) for k in PARITY_KEYS) or (
            g.get("violation") != f.get("violation")
        ):
            mismatches.append(dict(
                job=j,
                golden={k: g.get(k) for k in PARITY_KEYS},
                fleet={k: f.get(k) for k in PARITY_KEYS},
            ))
        if g.get("violation"):
            n_viol += 1
            # the service-side reconstructed trace must equal the
            # sequential arm's (both render through check.trace_doc)
            if f.get("trace") != g.get("trace") or not g.get("trace"):
                trace_bad.append(j)
    poisoned = []
    failed_dir = os.path.join(fleet_root, "failed")
    if os.path.isdir(failed_dir):
        poisoned = sorted(os.listdir(failed_dir))
    # pool bookkeeping: fenced/recovered from the worker records
    # (killed workers never deregister; their record just reads dead)
    from .pool import WorkerRegistry

    reg = WorkerRegistry(fleet_root, "chaos-supervisor",
                         ttl=args.lease_ttl)
    fenced_total = 0
    recovered_total = sup_requeued  # the supervisor's sweep is a pool
    # peer's sweep: stale leases it requeued (a killed worker's claims,
    # typically, while the survivors were mid-compute) are recoveries
    for name, doc in reg.list_workers().items():
        st = doc.get("stats") or {}
        fenced_total += int(st.get("fenced", 0))
        recovered_total += int(st.get("recovered", 0))
    want_pause = sum(":pause@" in p for p in plans.values())
    want_kill = sum(":kill@" in p for p in plans.values())
    ok = (
        not undrained
        and not mismatches
        and not trace_bad
        and not rewrites
        and not poisoned
        and not unfired
        and (fenced_total >= 1 if resumed else True)
        and (recovered_total >= 1 if want_kill else True)
    )
    report = dict(
        ok=ok,
        jobs=len(jids),
        workers=args.workers,
        schedule=args.schedule,
        violations=n_viol,
        drained=not undrained,
        undrained=len(undrained),
        parity=not mismatches,
        traces_ok=not trace_bad,
        duplicate_commits=len(rewrites),
        poisoned=len(poisoned),
        fenced_total=fenced_total,
        recovered_total=recovered_total,
        supervisor_requeued=sup_requeued,
        paused_resumed=resumed,
        scheduled_pauses=want_pause,
        scheduled_kills=want_kill,
        unfired=unfired,
        worker_exits=exits,
        wall_s=round(time.monotonic() - t0, 2),
    )
    if mismatches:
        report["mismatch"] = mismatches[:3]
    if trace_bad:
        report["trace_bad"] = trace_bad[:5]
    if rewrites:
        report["rewritten"] = sorted(set(rewrites))[:5]
    return report


def main(args) -> int:
    try:
        report = run_campaign(args)
    except (RuntimeError, ValueError) as e:
        print(json.dumps(dict(ok=False, error=str(e))))
        return 1
    print(json.dumps(report))
    return 0 if report["ok"] else 1
