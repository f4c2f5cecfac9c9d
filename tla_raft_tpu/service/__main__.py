"""Sweep-service CLI: submit / status / results / run / chaos.

    python -m tla_raft_tpu.service submit  --root Q --config Raft.cfg \
        [--servers N] [--vals N] [--max-election N] [--max-restart N] \
        [--max-depth N] [--invariant I]... [--mutate M]... [--chunk N] \
        [--count N] [--max-queue N] [--json]
    python -m tla_raft_tpu.service status  --root Q [--job ID] [--json]
    python -m tla_raft_tpu.service results --root Q JOB [--json]
    python -m tla_raft_tpu.service run     --root Q [--once] [--poll S] \
        [--max-idle S] [--no-batch] [--min-bucket N] [--lease-ttl S] \
        [--supervise N] [--worker NAME] [--admit-configs N] \
        [--admit-bytes B]
    python -m tla_raft_tpu.service chaos   --base DIR --workers N \
        --schedule "worker2:kill@bucket.level;worker3:pause@lease.renew"

``results`` emits the same ``--json`` summary schema ``check.py``
produces (one JSON object per line), so sweep tooling parses one
format whether a config ran through the service or standalone.
``run --supervise N`` wraps the scheduler in the same relaunch loop
``check.py --supervise`` uses: crashes and preemptions (exit 75)
relaunch the daemon, whose first pass requeues the dead worker's
stale-leased jobs and resumes them from their checkpoint dirs.

``run --worker NAME`` joins the worker pool: the daemon registers a
health-checked membership record (service/pool.py), heartbeats it every
pass, and on exit — graceful idle drain or preemption — flips it to
``draining`` and deregisters with its final scheduler counters, so the
fleet's fencing/recovery arithmetic survives the worker's death.
``submit --max-queue N`` is admission control at the front door: when
the pending backlog is already >= N, the submission is rejected with
exit 75 (EX_TEMPFAIL — retry later), mirroring the preemption code so
sweep drivers reuse one backoff path.  ``chaos`` runs a deterministic
multi-worker fault campaign (service/chaos.py).
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys


def _build_cfg(args):
    from ..config import RaftConfig

    if args.config and os.path.exists(args.config):
        from ..cfgparse import load_raft_config

        cfg = load_raft_config(args.config)
    else:
        cfg = RaftConfig()
    over = {}
    if args.servers is not None:
        over["n_servers"] = args.servers
    if args.vals is not None:
        over["n_vals"] = args.vals
    if args.max_election is not None:
        over["max_election"] = args.max_election
    if args.max_restart is not None:
        over["max_restart"] = args.max_restart
    if args.invariant:
        over["invariants"] = tuple(args.invariant)
    if args.mutate:
        over["mutations"] = tuple(args.mutate)
    if args.no_symmetry:
        over["symmetry"] = False
    if args.no_view:
        over["use_view"] = False
    return dataclasses.replace(cfg, **over) if over else cfg


def _cmd_submit(args) -> int:
    from .queue import JobQueue

    q = JobQueue(args.root)
    if args.max_queue:
        pending = len(q.pending())
        if pending >= args.max_queue:
            print(
                f"submit rejected: {pending} pending >= --max-queue "
                f"{args.max_queue} (backpressure; retry later)",
                file=sys.stderr,
            )
            return 75
    cfg = _build_cfg(args)
    options = {}
    if args.chunk is not None:
        options["chunk"] = args.chunk
    if args.backend != "jax":
        options["backend"] = args.backend
    if args.dev_bytes:
        # tiered job: the worker runs it with a hot-slab device budget
        # (store/tiered.py) — the scheduler can pack configs whose
        # visited sets exceed HBM; they route sequential (the batched
        # bucket core shares ONE slab across tenants)
        options["dev_bytes"] = int(args.dev_bytes)
    if args.warm_bytes:
        options["warm_bytes"] = int(args.warm_bytes)
    jids = []
    for _ in range(args.count):
        jids.append(
            q.submit(cfg, max_depth=args.max_depth, options=options)
        )
    if args.json:
        print(json.dumps(dict(submitted=jids, config=cfg.describe())))
    else:
        for j in jids:
            print(j)
    return 0


def _cmd_status(args) -> int:
    from .queue import JobQueue

    q = JobQueue(args.root)
    if args.metrics:
        # the daemon's per-pass atomic snapshot (obs/metrics.py):
        # queue depth, lease ages, jobs/hour, poisoned count
        from ..obs import metrics as obs_metrics

        doc = obs_metrics.load(args.root)
        if doc is None:
            print(f"{args.root}: no readable metrics.json "
                  "(daemon not run yet?)", file=sys.stderr)
            return 2
        if args.json:
            print(json.dumps(doc))
        else:
            obs_metrics.render(doc)
        return 0
    if args.job:
        try:
            st = q.load_state(args.job)
        except FileNotFoundError as e:
            print(e, file=sys.stderr)
            return 2
        doc = dict(job_id=args.job, **st)
        if args.json:
            print(json.dumps(doc))
        else:
            print(f"{args.job}: {st['status']} "
                  f"(attempt {st.get('attempt')}, "
                  f"worker {st.get('worker')})")
        return 0
    c = q.counts()
    if args.json:
        print(json.dumps(c))
    else:
        for k, v in c.items():
            print(f"{k:>10}: {v}")
    return 0


def _cmd_results(args) -> int:
    from .queue import JobQueue

    q = JobQueue(args.root)
    res = q.load_result(args.job)
    if res is None:
        try:
            st = q.load_state(args.job)
        except FileNotFoundError as e:
            print(e, file=sys.stderr)
            return 2
        print(
            f"job {args.job}: no result yet (status {st['status']})",
            file=sys.stderr,
        )
        return 4
    if args.json:
        print(json.dumps(res))
    else:
        verdict = "OK" if res.get("ok") else (
            res.get("violation") or "FAILED"
        )
        print(
            f"{args.job}: {verdict} — {res.get('distinct')} distinct, "
            f"{res.get('generated')} generated, depth {res.get('depth')}"
        )
    return 0 if res.get("ok") else 1


def _supervise_run(args, raw_argv) -> int:
    """Relaunch loop for the daemon (check.py --supervise shape)."""
    import subprocess

    child_args = []
    skip = False
    for a in raw_argv:
        if skip:
            skip = False
            continue
        if a == "--supervise":
            skip = True
            continue
        if a.startswith("--supervise="):
            continue
        child_args.append(a)
    attempts = 0
    while True:
        rc = subprocess.call(
            [sys.executable, "-m", "tla_raft_tpu.service", *child_args]
        )
        if rc in (0, 1, 2, 3):
            return rc
        attempts += 1
        if attempts > args.supervise:
            print(
                f"supervise: giving up after {attempts - 1} "
                f"relaunch(es) (last exit {rc})",
                file=sys.stderr,
            )
            return rc
        print(
            f"supervise: scheduler exited {rc}; relaunch "
            f"{attempts}/{args.supervise}",
            file=sys.stderr,
        )


def _cmd_run(args, raw_argv) -> int:
    if args.supervise:
        return _supervise_run(args, raw_argv)
    from .. import resilience
    from ..platform import setup_jax
    from .daemon import Scheduler
    from .queue import JobQueue

    # the batched bucket path uses jax directly (no check.py in the
    # loop), so the daemon must configure the platform override and the
    # persistent compile cache itself — a supervised relaunch otherwise
    # re-pays the whole bucket compile ladder every restart
    setup_jax()
    resilience.install_signal_handlers()
    q = JobQueue(
        args.root, lease_ttl=args.lease_ttl,
        max_attempts=args.retry_budget,
    )
    registry = None
    if args.worker:
        from .pool import WorkerRegistry

        # membership TTL == lease TTL: a worker whose record goes
        # stale is presumed dead on the same clock as its job leases
        registry = WorkerRegistry(
            args.root, args.worker, ttl=args.lease_ttl,
        )
        registry.register()
    sched = Scheduler(
        q, batch=not args.no_batch, min_bucket=args.min_bucket,
        registry=registry, admit_configs=args.admit_configs,
        admit_bytes=args.admit_bytes,
    )
    if args.progress:
        # live per-level line for whatever bucket/job is on the device
        from ..obs.progress import ProgressLine

        pl = ProgressLine(stream=sys.stderr)
        sched.progress = pl.write

    def _leave():
        # graceful drain: announce, then leave the pool with the final
        # scheduler counters attached — the chaos/fleet gates audit
        # fencing and recovery arithmetic from these records after the
        # worker process is gone
        if registry is not None:
            registry.drain()
            registry.deregister(
                stats=dict(sched.stats, fenced=q.fenced),
            )

    try:
        if args.once:
            stats = sched.run_once()
        else:
            stats = sched.serve(poll=args.poll, max_idle=args.max_idle)
    except resilience.Preempted as e:
        print(f"[service] preempted: {e}", file=sys.stderr)
        _leave()
        return 75
    _leave()
    print(json.dumps(dict(stats, counts=q.counts())))
    return 0


def _cmd_chaos(args) -> int:
    from .chaos import main as chaos_main

    return chaos_main(args)


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    p = argparse.ArgumentParser(prog="tla_raft_tpu.service")
    sub = p.add_subparsers(dest="cmd", required=True)

    ps = sub.add_parser("submit", help="enqueue a checking job")
    ps.add_argument("--root", required=True)
    ps.add_argument("--config", default=None,
                    help="TLC .cfg file (default: built-in reference "
                         "constants)")
    ps.add_argument("--backend", choices=("jax", "oracle"), default="jax")
    ps.add_argument("--servers", type=int, default=None)
    ps.add_argument("--vals", type=int, default=None)
    ps.add_argument("--max-election", type=int, default=None)
    ps.add_argument("--max-restart", type=int, default=None)
    ps.add_argument("--max-depth", type=int, default=None)
    ps.add_argument("--invariant", action="append", default=None)
    ps.add_argument("--mutate", action="append", default=None,
                    choices=("median-bug", "double-vote",
                             "legacy-append", "become-follower"))
    ps.add_argument("--no-symmetry", action="store_true")
    ps.add_argument("--no-view", action="store_true")
    ps.add_argument("--dev-bytes", type=float, default=None,
                    help="device-memory budget for the job's hot "
                         "visited tier: lets the scheduler pack "
                         "configs whose visited sets exceed HBM "
                         "(tiered store — the job runs sequentially)")
    ps.add_argument("--warm-bytes", type=float, default=None,
                    help="host-RAM budget for the job's warm tier")
    ps.add_argument("--chunk", type=int, default=None,
                    help="sequential-path chunk override")
    ps.add_argument("--count", type=int, default=1,
                    help="submit N identical jobs")
    ps.add_argument("--max-queue", type=int, default=0, metavar="N",
                    help="admission control: reject the submission "
                         "with exit 75 (EX_TEMPFAIL, retry later) when "
                         "the pending backlog is already >= N")
    ps.add_argument("--json", action="store_true")

    pt = sub.add_parser("status", help="queue or per-job status")
    pt.add_argument("--root", required=True)
    pt.add_argument("--job", default=None)
    pt.add_argument("--metrics", action="store_true",
                    help="render the daemon's metrics.json snapshot "
                         "(queue depth, lease ages, jobs/h, poisoned)")
    pt.add_argument("--json", action="store_true")

    pr = sub.add_parser("results", help="print a job's summary")
    pr.add_argument("--root", required=True)
    pr.add_argument("job")
    pr.add_argument("--json", action="store_true")

    pd = sub.add_parser("run", help="run the scheduler daemon")
    pd.add_argument("--root", required=True)
    pd.add_argument("--once", action="store_true",
                    help="one pass over the pending queue, then exit")
    pd.add_argument("--poll", type=float, default=2.0)
    pd.add_argument("--max-idle", type=float, default=None,
                    help="exit after this many idle seconds")
    pd.add_argument("--no-batch", action="store_true",
                    help="disable config-batched execution (A/B lever; "
                         "every job runs sequentially)")
    pd.add_argument("--min-bucket", type=int, default=2,
                    help="smallest bucket worth batching (default 2)")
    pd.add_argument("--lease-ttl", type=float, default=30.0,
                    help="seconds without a heartbeat before a "
                         "worker's claim is presumed dead")
    pd.add_argument("--retry-budget", type=int, default=3, metavar="N",
                    help="poison-job quarantine: a job whose worker "
                         "dies N times is failed with its accumulated "
                         "failure log and moved to failed/ instead of "
                         "being requeued forever (default 3)")
    pd.add_argument("--supervise", type=int, default=0, metavar="N",
                    help="relaunch a crashed/preempted scheduler up "
                         "to N times")
    pd.add_argument("--progress", action="store_true",
                    help="live one-line progress for the in-flight "
                         "bucket/job (states/s, configs alive, ETA)")
    pd.add_argument("--worker", default=None, metavar="NAME",
                    help="join the worker pool under NAME: register a "
                         "health-checked membership record, heartbeat "
                         "it every pass, deregister (with final "
                         "counters) on drain or preemption")
    pd.add_argument("--admit-configs", type=int, default=None,
                    metavar="N",
                    help="admission control: claim at most N configs "
                         "per batched bucket; the tail stays pending "
                         "for peers (default: env "
                         "TLA_RAFT_ADMIT_CONFIGS, 0 = unlimited)")
    pd.add_argument("--admit-bytes", type=float, default=None,
                    metavar="B",
                    help="admission control: defer tiered jobs whose "
                         "declared dev_bytes exceed this worker's "
                         "device budget (default: env "
                         "TLA_RAFT_ADMIT_BYTES, 0 = unlimited)")

    pc = sub.add_parser(
        "chaos",
        help="deterministic multi-worker fault campaign (kill/pause/"
             "torn schedules against a synthetic queue, drained to "
             "convergence and gated bit-identical vs a clean "
             "sequential arm)",
    )
    pc.add_argument("--base", required=True,
                    help="campaign directory (golden/ and fleet/ queue "
                         "roots plus the shared compile cache live "
                         "under it)")
    pc.add_argument("--workers", type=int, default=3)
    pc.add_argument("--jobs", type=int, default=60,
                    help="synthetic queue depth (scripts/queue_synth "
                         "mix)")
    pc.add_argument("--violations", type=int, default=2,
                    help="extra deliberately-violating configs whose "
                         "counterexample traces must match the "
                         "sequential arm's")
    pc.add_argument("--schedule", default="",
                    help="worker:action@site[#n] items separated by "
                         "',' or ';' — e.g. 'worker2:kill@bucket."
                         "level#2;worker3:pause@lease.renew#4'")
    pc.add_argument("--seed", type=int, default=1)
    pc.add_argument("--mr-width", type=int, default=5)
    pc.add_argument("--chunk", type=int, default=64)
    pc.add_argument("--lease-ttl", type=float, default=2.0)
    pc.add_argument("--poll", type=float, default=0.3)
    pc.add_argument("--min-bucket", type=int, default=2)
    pc.add_argument("--max-idle", type=float, default=None,
                    help="worker idle-exit window (default: "
                         "4*lease_ttl + 5, so paused-worker requeues "
                         "land before peers give up)")
    pc.add_argument("--timeout", type=float, default=900.0,
                    help="per-arm drain deadline in seconds")

    args = p.parse_args(argv)
    if args.cmd == "submit":
        return _cmd_submit(args)
    if args.cmd == "status":
        return _cmd_status(args)
    if args.cmd == "results":
        return _cmd_results(args)
    if args.cmd == "chaos":
        if args.max_idle is None:
            args.max_idle = 4.0 * args.lease_ttl + 5.0
        return _cmd_chaos(args)
    return _cmd_run(args, argv)


if __name__ == "__main__":
    sys.exit(main())
