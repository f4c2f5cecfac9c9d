"""CLI entry point: the L4 launcher layer, TPU-native.

The reference's launcher is ``myrun.sh`` (/root/reference/myrun.sh:3):
``java ... tla2tools.jar -deadlock -workers 4 -config Raft.cfg Raft.tla $@
2>&1 | tee raft.log``.  This module is the checker it launches when
``-backend=jax`` is selected: it parses the same ``.cfg`` file
(cfgparse.py), runs the TPU BFS engine (engine/bfs.py) or the pure-Python
oracle, prints TLC-shaped progress/result lines, and tees everything to
``raft.log`` — keeping the reference's observability contract (grep-able
state counts + verdict, SURVEY.md §5 "metrics/logging").

Usage:
  python -m tla_raft_tpu.check [--config Raft.cfg] \
      [--backend jax|oracle] [--max-depth N] [--chunk N] \
      [--invariant NAME]... [--no-symmetry] [--no-view] \
      [--checkpoint-dir states] [--recover states] \
      [--log raft.log] [--servers N] [--vals N] [--max-election N] \
      [--max-restart N]

Flags mirror TLC where an analog exists: ``--workers`` is accepted and
ignored (parallelism is the device mesh, not a thread count);
``--recover`` matches TLC's ``-recover``; deadlock checking is disabled
with no off switch, matching the pinned ``-deadlock`` flag.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import sys
import time

from .config import MSG_TYPE_NAMES, ROLE_NAMES, RaftConfig
from .cfgparse import REPO_CFG, load_raft_config

# expand rows per device dispatch when the caller names none
DEFAULT_CHUNK = 1024


class Tee:
    def __init__(self, *streams):
        self.streams = streams

    def write(self, s):
        for st in self.streams:
            st.write(s)

    def flush(self):
        for st in self.streams:
            st.flush()


def format_state(cfg: RaftConfig, st) -> str:
    """Pretty-print one oracle OState, TLA-style."""
    S = cfg.S

    def server_fn(vals, fmt=str):
        return "[" + ", ".join(f"s{i + 1} |-> {fmt(v)}" for i, v in enumerate(vals)) + "]"

    def fmt_vote(v):
        return f"s{v}" if v else "None"

    def fmt_log(log):
        return "<<" + ", ".join(f"[term |-> {t}, val |-> {'v%d' % v if v else 'None'}]" for t, v in log) + ">>"

    def fmt_msg(m):
        t = MSG_TYPE_NAMES[m[0]]
        if m[0] == 0:  # VoteReq
            return (f"[type |-> {t}, src |-> s{m[1]}, dst |-> s{m[2]}, term |-> {m[3]}, "
                    f"lastLogIndex |-> {m[4]}, lastLogTerm |-> {m[5]}]")
        if m[0] == 1:  # VoteResp
            return f"[type |-> {t}, src |-> s{m[1]}, dst |-> s{m[2]}, term |-> {m[3]}]"
        if m[0] == 2:  # AppendReq
            ent = ", ".join(f"[term |-> {et}, val |-> v{ev}]" for et, ev in m[6])
            return (f"[type |-> {t}, src |-> s{m[1]}, dst |-> s{m[2]}, term |-> {m[3]}, "
                    f"prevLogIndex |-> {m[4]}, prevLogTerm |-> {m[5]}, "
                    f"entries |-> <<{ent}>>, leaderCommit |-> {m[7]}]")
        return (f"[type |-> {t}, src |-> s{m[1]}, dst |-> s{m[2]}, term |-> {m[3]}, "
                f"prevLogIndex |-> {m[4]}, succ |-> {'TRUE' if m[5] else 'FALSE'}]")

    lines = [
        f"/\\ votedFor = {server_fn(st.voted_for, fmt_vote)}",
        f"/\\ currentTerm = {server_fn(st.current_term)}",
        f"/\\ role = {server_fn(st.role, lambda r: ROLE_NAMES[r])}",
        f"/\\ logs = {server_fn(st.logs, fmt_log)}",
        f"/\\ matchIndex = {server_fn(st.match_index, lambda r: '[' + ', '.join(f's{j + 1} |-> {x}' for j, x in enumerate(r)) + ']')}",
        f"/\\ nextIndex = {server_fn(st.next_index, lambda r: '[' + ', '.join(f's{j + 1} |-> {x}' for j, x in enumerate(r)) + ']')}",
        f"/\\ commitIndex = {server_fn(st.commit_index)}",
        "/\\ msgs = {" + ",\n            ".join(fmt_msg(m) for m in sorted(st.msgs)) + "}",
        f"/\\ electionCount = {st.election_count}",
        f"/\\ restartCount = {st.restart_count}",
        f"/\\ valSent = [" + ", ".join(
            f"v{i + 1} |-> {'None' if v == 0 else 'FALSE'}" for i, v in enumerate(st.val_sent)
        ) + "]",
    ]
    return "\n".join(lines)


def trace_doc(cfg: RaftConfig, trace) -> list[dict]:
    """Serializable counterexample trace: one ``{action, state}`` dict
    per step, states rendered exactly as :func:`print_trace` shows
    them.  The one place trace rendering is defined — the CLI's
    pretty-printer and the sweep service's ``result.json`` trace block
    both come from here, so a service-reconstructed trace is
    byte-equal to what ``check.py`` would print for the same run."""
    return [
        dict(
            action="Initial predicate" if action == "Init" else action,
            state=format_state(cfg, st),
        )
        for action, st in trace
    ]


def print_trace(cfg: RaftConfig, trace, out):
    print("The behavior up to this point is:", file=out)
    for i, step in enumerate(trace_doc(cfg, trace)):
        print(f"\nSTATE {i + 1}: <{step['action']}>", file=out)
        print(step["state"], file=out)


def _report_preempted(e, out, logf) -> int:
    """Preemption is a RESUMABLE outcome, not an error: say where the
    durable state sits and exit 75 (EX_TEMPFAIL — the supervisor and
    any fleet scheduler relaunch on it)."""
    print(f"Preempted: {e}.", file=out)
    if e.checkpoint_dir:
        print(f"Resume with --recover {e.checkpoint_dir}", file=out)
    if logf:
        logf.close()
    return 75


def _has_checkpoints(ckdir: str) -> bool:
    import glob

    return bool(
        glob.glob(os.path.join(ckdir, "delta_*.npz"))
        or glob.glob(os.path.join(ckdir, "mdelta_*.npz"))
        or os.path.exists(os.path.join(ckdir, "base.npz"))
    )


def _mxu_arg(args):
    """--mxu-expand / --no-mxu-expand -> the checkers' use_mxu argument
    (None = the TLA_RAFT_MXU env default, which is ON)."""
    if args.no_mxu_expand:
        return False
    if args.mxu_expand is None:
        return None
    return bool(args.mxu_expand)


def _supervise(args, raw_argv) -> int:
    """Supervisor mode: run the check as a child process, relaunching a
    crashed/preempted child from its own checkpoint directory up to N
    times (TLC deployments wrap the jar in exactly this kind of babysit
    loop; ``--supervise`` builds it in).  Terminal exits — clean sweep
    (0), model violation (1), usage error (2), sanitizer findings (3)
    — are returned as-is; anything else (SIGKILL, OOM, preemption exit
    75) relaunches with ``--recover`` pointing at the checkpoint dir,
    where the self-healing resume quarantines whatever the crash tore."""
    import subprocess

    if not args.checkpoint_dir:
        print(
            "--supervise requires --checkpoint-dir (the relaunch "
            "resumes from it)",
            file=sys.stderr,
        )
        return 2
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
        cmd = [sys.executable, "-m", "tla_raft_tpu.check", *child_args]
        if (
            "--recover" not in child_args
            and _has_checkpoints(args.checkpoint_dir)
        ):
            cmd += ["--recover", args.checkpoint_dir]
        rc = subprocess.call(cmd)
        # terminal: clean sweep (0), model violation (1), usage error
        # (2), sanitizer findings (3), integrity fail-stop (4 —
        # deterministic corruption; relaunching cannot outrun it)
        if rc in (0, 1, 2, 3, 4):
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
            f"supervise: child exited {rc}; relaunch "
            f"{attempts}/{args.supervise} from {args.checkpoint_dir}",
            file=sys.stderr,
        )


class _Silent:
    def write(self, s):
        pass

    def flush(self):
        pass


def summarize(res, chk=None, seconds: float | None = None,
              hub=None) -> dict:
    """CheckResult -> the canonical ``--json`` summary schema.

    The one place the schema is defined: the CLI's ``--json`` line, the
    sweep service's ``result.json`` records and the programmatic
    :func:`run_check` return value all come from here, so they can
    never drift apart.  Keys beginning with ``_`` carry non-JSON
    payloads (the raw result/checker objects) and are stripped by
    :func:`summary_public` before anything is serialized.  ``hub`` (a
    telemetry hub, when the run carried one) contributes the unified
    ``telemetry`` block — level wall times, dispatches, fetch waits,
    grow/redo counts, checkpoint I/O, straggler skew — in ONE place
    instead of per-subsystem ad-hoc keys.
    """
    out = dict(
        ok=res.ok,
        distinct=res.distinct,
        generated=res.generated,
        depth=res.depth,
        # the crash-matrix tests diff these against an
        # uninterrupted run's, level by level
        level_sizes=list(res.level_sizes),
        mxu=getattr(chk, "use_mxu", None),
        megakernel=getattr(chk, "megakernel", None),
        superstep=getattr(chk, "superstep_span", None),
        seconds=round(seconds, 3) if seconds is not None else None,
        violation=res.violation[0] if res.violation else None,
    )
    if chk is not None:
        import jax

        devs = jax.devices()
        out["device"] = dict(
            platform=devs[0].platform, kind=devs[0].device_kind,
            count=len(devs),
        )
        # the hash store's grow-failure fallback (device OOM, injected
        # fault): None, or why the run left the fast path
        out["hashstore_degraded"] = getattr(chk, "hashstore_degraded", None)
    # integrity-audit counters (single-device --audit runs only)
    aud = getattr(chk, "audit_stats", None)
    if aud and aud.get("levels"):
        out["audit"] = dict(aud)
    # tiered visited store (store/tiered.py): demotion + per-tier probe
    # accounting — present once a device budget actually spilled
    tiered = getattr(chk, "tiered", None)
    if tiered is not None and (
        tiered.stats["demotions"] or tiered.stats["probes"]
    ):
        out["tiered"] = dict(
            tiered.stats,
            dev_bytes=tiered.dev_bytes,
            generations=len(tiered.gens),
            probe_wait_s=round(tiered.stats["probe_wait_s"], 6),
            cold_load_s=round(tiered.stats["cold_load_s"], 6),
        )
        out["sieve"] = bool(getattr(chk, "sieve_enabled", False))
        # spilled-frontier paging (store/tiered.py FrontierPager)
        fpager = getattr(chk, "_fpager", None)
        if fpager is not None and fpager.stats["fseg_spills"]:
            out["fseg"] = dict(
                fpager.stats,
                fseg_load_s=round(fpager.stats["fseg_load_s"], 6),
            )
    # superstep window accounting (supersteps/levels/stop causes): under
    # spill this is the span-survival evidence — sieve-clean windows keep
    # committing levels, sieve_stops count the exact per-level
    # corrections (ops/sieve.py).  program_hits/program_builds count this
    # check's lookups in the shared program caches (a check after a
    # same-config one in the process builds nothing), for the superstep
    # driver here and the per-level megakernel in megakernel_stats
    ss = getattr(chk, "_ss_stats", None)
    if ss and ss.get("supersteps"):
        out["superstep_stats"] = {
            k: int(v) for k, v in sorted(ss.items())
        }
    mega = getattr(chk, "_mega_stats", None)
    if mega and getattr(chk, "megakernel", False):
        out["megakernel_stats"] = {
            k: int(v) for k, v in sorted(mega.items())
        }
    # adaptive sieve governor (ops/sieve.py): present whenever the
    # measured arm/stand-down policy saw a window or flipped state
    gov = getattr(chk, "sieve_governor", None)
    if gov is not None and (
        gov.stats["windows"] or gov.stats["stand_downs"]
        or gov.stats["rearms"]
    ):
        out["sieve_governor"] = gov.snapshot()
    # sharded deep sweep: the capacities it ended at, its reactive
    # grows (each recompiles the deep programs) and what the sieve did
    if getattr(chk, "deep", False):
        ex = chk.meter.summary()
        out["mesh_deep"] = dict(
            cap_x=chk.cap_x, scap=chk.scap if chk.sieve else 0,
            reactive_grows=chk.reactive_grows,
            candidates=ex["candidates"], sieved=ex["sieved"],
        )
    # per-owner straggler/skew metrics (mesh runs); kept at top level
    # for compatibility AND folded into the telemetry block below
    skew = getattr(chk, "skew", None)
    if skew is not None and getattr(skew, "levels", 0):
        out["straggler"] = skew.summary()
    if hub is not None:
        tel = hub.snapshot()
        if "straggler" in out:
            tel["straggler"] = out["straggler"]
        out["telemetry"] = tel
        if "hbm" in tel:
            # the live device-memory gauge (registered buffers + worst
            # program temp, vs the --dev-bytes budget) surfaces at the
            # top level beside the counts it prices
            out["hbm"] = tel["hbm"]
    return out


def summary_public(summary: dict) -> dict:
    """The JSON-serializable view of a :func:`run_check` summary."""
    return {k: v for k, v in summary.items() if not k.startswith("_")}


def run_check(
    cfg: RaftConfig,
    *,
    backend: str = "jax",
    max_depth: int | None = None,
    chunk: int = DEFAULT_CHUNK,
    checkpoint_dir: str | None = None,
    checkpoint_every: int = 1,
    recover: str | None = None,
    fpstore_dir: str | None = None,
    mesh: int = 0,
    exchange: str = "all_to_all",
    mesh_deep: bool = False,
    seg_rows: int = 1 << 15,
    sieve: bool = True,
    compress: bool = True,
    cap_x: int | None = None,
    canon: str = "late",
    use_hashstore: bool | None = None,
    pipeline: bool | None = None,
    pipeline_window: int | None = None,
    prewarm: bool | None = None,
    use_mxu: bool | None = None,
    megakernel: bool | None = None,
    superstep: int | None = None,
    audit: int = 0,
    audit_retries: int = 3,
    watchdog: float = 0.0,
    telemetry: bool | None = None,
    profile: int = 0,
    dev_bytes: int | None = None,
    warm_bytes: int | None = None,
    progress=None,
    out=None,
    install_signals: bool = False,
) -> dict:
    """One in-process model-checking run -> the ``--json`` summary dict.

    The programmatic core ``main`` used to inline: the sweep service,
    the bench harness and the tests all invoke the checker through
    here instead of shelling out through argv.  ``out`` (a writable
    stream, or None for silence) receives the same informational lines
    the CLI prints; ``progress`` is the per-level stats callback.
    Raises ``resilience.Preempted`` on cooperative preemption (the CLI
    maps it to exit 75) and propagates engine errors as exceptions —
    policy (exit codes, tee logs, trace pretty-printing) stays with the
    caller.  Extra ``_res`` / ``_chk`` / ``_sanitizer`` / ``_hub`` keys
    carry the raw objects for callers that need the violation trace,
    the exchange meter or the telemetry hub; ``summary_public`` strips
    them.

    ``telemetry`` (default: ``TLA_RAFT_TELEMETRY``, on) installs the
    process-wide flight recorder (obs/telemetry.py) for the run: every
    level, dispatch, ledgered fetch, compile, checkpoint commit,
    grow/redo and watchdog event lands in ``<checkpoint_dir>/
    events.jsonl`` (in-memory aggregation only when the run has no
    checkpoint dir and ``TLA_RAFT_TELEMETRY_DIR`` is unset), and the
    returned summary carries the unified ``telemetry`` block.  A hub
    already installed by an outer caller (bench, the service bucket
    loop) is reused, never re-anchored or closed.
    """
    from .obs import telemetry as obs_telemetry

    if backend != "oracle":
        # before anything touches a device: the platform check, and the
        # compile cache must be configured before the first compile
        from .platform import setup_jax

        setup_jax()
    tel_on = (
        obs_telemetry.enabled_by_env() if telemetry is None
        else bool(telemetry)
    ) and backend != "oracle"
    hub = None
    own_hub = False
    if tel_on:
        hub = obs_telemetry.current()
        if hub is None:
            run_dir = (
                checkpoint_dir
                or os.environ.get("TLA_RAFT_TELEMETRY_DIR")
                or None
            )
            hub = obs_telemetry.TelemetryHub(run_dir=run_dir)
            obs_telemetry.install(hub)
            own_hub = True
            obs_telemetry.run_begin(
                config=cfg.describe(), backend=backend, mesh=mesh,
                mesh_deep=mesh_deep, recover=bool(recover),
            )
    if hub is not None:
        # publish XLA backend compiles into the flight recorder and
        # record its spans on the profiler's clock (idempotent,
        # armed only after setup_jax picked the platform)
        from .analysis.sanitize import obs_watch_compiles

        obs_watch_compiles()
    with obs_telemetry.span("raft.check"):
        try:
            return _run_check_impl(
                cfg, backend=backend, max_depth=max_depth, chunk=chunk,
                checkpoint_dir=checkpoint_dir,
                checkpoint_every=checkpoint_every, recover=recover,
                fpstore_dir=fpstore_dir, mesh=mesh, exchange=exchange,
                mesh_deep=mesh_deep, seg_rows=seg_rows, sieve=sieve,
                compress=compress, cap_x=cap_x, canon=canon,
                use_hashstore=use_hashstore, pipeline=pipeline,
                pipeline_window=pipeline_window, prewarm=prewarm,
                use_mxu=use_mxu, megakernel=megakernel,
                superstep=superstep, audit=audit,
                audit_retries=audit_retries, watchdog=watchdog,
                profile=profile,
                dev_bytes=dev_bytes, warm_bytes=warm_bytes,
                hub=hub, progress=progress, out=out,
                install_signals=install_signals,
            )
        finally:
            if own_hub:
                obs_telemetry.install(None)
                hub.close()


def _run_check_impl(
    cfg: RaftConfig,
    *,
    backend,
    max_depth,
    chunk,
    checkpoint_dir,
    checkpoint_every,
    recover,
    fpstore_dir,
    mesh,
    exchange,
    mesh_deep,
    seg_rows,
    sieve,
    compress,
    cap_x,
    canon,
    use_hashstore,
    pipeline,
    pipeline_window,
    prewarm,
    use_mxu,
    megakernel,
    superstep,
    audit,
    audit_retries,
    watchdog,
    profile,
    dev_bytes,
    warm_bytes,
    hub,
    progress,
    out,
    install_signals,
) -> dict:
    if mesh_deep and not mesh:
        raise ValueError("mesh_deep requires mesh >= 1")
    if mesh_deep and not fpstore_dir:
        raise ValueError("mesh_deep requires fpstore_dir")
    if warm_bytes is None and os.environ.get("TLA_RAFT_WARM_BYTES"):
        # honor the env on the external-store paths too (the tiered
        # slab path reads it internally); when NEITHER is set those
        # stores keep their native 64 MiB buffer default — the 1 GiB
        # tiered-generation default must not silently re-budget them
        warm_bytes = int(float(os.environ["TLA_RAFT_WARM_BYTES"]))
    from .obs import telemetry as obs_telemetry

    out = out if out is not None else _Silent()
    t0 = time.monotonic()
    sanitizer = None
    tsan = None
    chk = None  # the engine instance (None on the oracle backend)
    if backend == "oracle":
        from .oracle import OracleChecker

        res = OracleChecker(cfg).run(max_depth=max_depth)
    else:
        from . import resilience
        from .platform import setup_jax

        jax = setup_jax()
        if install_signals:
            # SIGTERM/SIGINT request a cooperative preemption: the
            # engine finishes the in-flight level, flushes its
            # checkpoints, and raises Preempted -> exit 75 (resumable);
            # a second signal kills immediately.  CLI-only — library
            # callers (the service daemon owns its own handlers) poll
            # the flag.
            resilience.install_signal_handlers()

        from .engine import JaxChecker

        if os.environ.get("GRAFT_SANITIZE") == "1":
            # graftlint layer 3 (docs/ANALYSIS.md): host-transfer ledger
            # + per-level compile-count ledger + dispatch-thread guard
            from .analysis.sanitize import Sanitizer

            sanitizer = Sanitizer()
            print(
                f"Sanitizer: armed (warmup {sanitizer.warmup_levels} "
                f"levels, {'strict' if sanitizer.strict else 'counting'} "
                "transfer guard)",
                file=out,
            )

        if os.environ.get("GRAFT_TSAN") == "1":
            # graftsync layer 2 (docs/ANALYSIS.md): happens-before
            # sanitizer + lock contention profiler over the known
            # thread boundaries; composes with GRAFT_SANITIZE.
            # GRAFT_TSAN_STRICT=1 raises at the racing access instead
            # of reporting at exit (exit code 3 either way).
            from .analysis.tsan import TSan

            tsan = TSan(
                strict=os.environ.get("GRAFT_TSAN_STRICT") == "1"
            )
            print(
                "TSan: armed (happens-before sanitizer, "
                f"{'strict' if tsan.strict else 'report-at-exit'}; "
                "lock profiler on)",
                file=out,
            )

        print(f"Devices: {jax.devices()}", file=out)

        # per-level hang watchdog (resilience/elastic.py): --watchdog S
        # arms every level with max(S, 8 * last level seconds); a hung
        # dispatch becomes a clean resumable exit 75 instead of an
        # infinite stall
        wd = None
        if watchdog and watchdog > 0 and backend != "oracle":
            wd = resilience.elastic.Watchdog(float(watchdog))
            resilience.elastic.install_watchdog(wd)
            print(f"Watchdog: armed (floor {float(watchdog)}s/level)",
                  file=out)

        # opt-in jax-profiler capture (--profile N, default off): the
        # device-side twin of the flight recorder — N dispatch windows
        # (supersteps on the fused path) traced into
        # <run_dir>/profile/, merged beside the host lanes by
        # `obs trace` (analysis/devprof.py)
        prof = None
        if profile and int(profile) > 0:
            from .analysis import devprof as graft_devprof

            prof_dir = (
                checkpoint_dir
                or os.environ.get("TLA_RAFT_TELEMETRY_DIR")
            )
            if hub is None:
                # without the flight recorder there is no
                # profile_begin merge anchor and no events.jsonl for
                # `obs trace` to hang the device lanes off — a capture
                # would be unreachable through the documented flow
                print(
                    "--profile needs telemetry on (the profile_begin "
                    "event anchors the device-lane merge; flag "
                    "ignored)", file=out,
                )
            elif not prof_dir:
                print(
                    "--profile needs --checkpoint-dir (or "
                    "TLA_RAFT_TELEMETRY_DIR): the device trace lands "
                    "beside events.jsonl (flag ignored)", file=out,
                )
            else:
                prof = graft_devprof.ProfilerCapture(
                    prof_dir, int(profile)
                )
                if prof.start():
                    graft_devprof.install_profiler(prof)
                    print(
                        f"Profiler: capturing {prof.windows} dispatch "
                        f"window(s) -> {prof.trace_dir}", file=out,
                    )
                else:
                    prof = None

        def wd_teardown():
            # on EVERY exit (Preempted, device loss, IntegrityError
            # propagate to the caller by contract): a leaked watchdog
            # thread would park forever and a stale global would
            # swallow the next run's heartbeats
            if prof is not None:
                from .analysis import devprof as graft_devprof

                prof.stop()
                graft_devprof.install_profiler(None)
            if wd is not None:
                wd.cancel()
                resilience.elastic.install_watchdog(None)

        # elastic relaunch: a recovery run on a shrunken fleet (device
        # loss) re-shards onto the surviving devices instead of
        # refusing to start; fresh runs keep the strict mesh check
        if mesh and recover:
            mesh = resilience.elastic.effective_mesh(mesh, out)

        host_store = None  # single-device external store (mesh has its own)
        if fpstore_dir and not mesh:
            from .native import HostFPStore

            # --warm-bytes bounds the store's in-RAM buffer; past it
            # the native tier spills sorted runs to disk (the warm/cold
            # boundary of the external-store loop)
            host_store = HostFPStore(
                fpstore_dir,
                mem_budget_entries=(
                    max(warm_bytes // 8, 1) if warm_bytes else 0
                ),
            )
            if not recover:
                # sweep run files orphaned by a crashed earlier process
                # (never loaded, but they waste disk and shadow names)
                host_store.clear()
            print(f"Native FP store: {fpstore_dir}", file=out)

        sanctx = sanitizer if sanitizer is not None else (
            contextlib.nullcontext()
        )
        tsanctx = tsan if tsan is not None else contextlib.nullcontext()
        if mesh:
            if dev_bytes:
                print(
                    "--dev-bytes applies to the single-device engine's "
                    "hot slab; mesh out-of-core runs tier through the "
                    "owner-sharded external stores (--fpstore-dir) "
                    "with --warm-bytes bounding their RAM (flag "
                    "ignored)", file=out,
                )
            if fpstore_dir:
                # mesh x external store: one HostFPStore per owner shard
                # (fp % D), host-filtered after the all_to_all routing
                print(f"Native FP store (owner-sharded x{mesh}): "
                      f"{fpstore_dir}", file=out)
            from .parallel import ShardedChecker, make_mesh

            chk = ShardedChecker(
                cfg, make_mesh(mesh), cap_x=cap_x,
                exchange=exchange, progress=progress, canon=canon,
                host_store_dir=fpstore_dir or None,
                deep=mesh_deep, seg_rows=seg_rows,
                sieve=sieve, compress=compress,
                use_hashstore=(
                    True if use_hashstore is None else use_hashstore
                ),
                pipeline=pipeline,
                pipeline_window=pipeline_window,
                use_mxu=use_mxu,
                watchdog=wd,
                warm_bytes=warm_bytes,
            )
            if audit:
                print(
                    "--audit applies to the single-device engine; mesh "
                    "runs keep the always-on conservation checks "
                    "(count reconciliation, store occupancy)",
                    file=out,
                )
            try:
                with sanctx, tsanctx:
                    res = chk.run(
                        max_depth=max_depth,
                        checkpoint_dir=checkpoint_dir,
                        checkpoint_every=checkpoint_every,
                        resume_from=recover,
                    )
            finally:
                wd_teardown()
            if mesh_deep and chk.meter.levels:
                # run-summary exchange ledger: the sieve+compress bytes
                # vs what the uncompressed exchange would have moved
                s = chk.meter.summary()
                print(
                    f"Exchange: {s['exchanged_bytes']:,} fp bytes over "
                    f"{s['levels']} levels (uncompressed equivalent "
                    f"{s['raw_bytes']:,}; reduction {s['reduction']}x; "
                    f"sieved {s['sieved']:,} of {s['candidates']:,} "
                    "candidates)",
                    file=out,
                )
                for lv in s["per_level"]:
                    print(
                        f"  level {lv['level']}: {lv['exchanged_bytes']:,}"
                        f" B (raw {lv['raw_bytes']:,} B, "
                        f"x{lv['reduction']}), sieved {lv['n_sieved']:,}"
                        f"/{lv['n_candidates']:,}",
                        file=out,
                    )
        else:
            with sanctx, tsanctx:
                with obs_telemetry.span("raft.check.construct"):
                    chk = JaxChecker(
                        cfg, chunk=chunk, progress=progress,
                        host_store=host_store, canon=canon,
                        use_hashstore=(
                            True if use_hashstore is None
                            else use_hashstore
                        ),
                        pipeline=pipeline,
                        pipeline_window=pipeline_window,
                        use_mxu=use_mxu,
                        megakernel=megakernel,
                        superstep=superstep,
                        prewarm=prewarm,
                        audit=audit,
                        audit_retries=audit_retries,
                        watchdog=wd,
                        store_bytes=dev_bytes,
                        warm_bytes=warm_bytes,
                    )
                if dev_bytes:
                    print(
                        f"Tiered visited store: hot slab budget "
                        f"{dev_bytes:,} B (demotions spill to "
                        "host/disk generations)", file=out,
                    )
                if audit:
                    print(
                        f"Integrity audit: {audit} sampled rows/level "
                        "re-expanded through the legacy kernels "
                        f"(fail-stop after {audit_retries} strikes)",
                        file=out,
                    )
                try:
                    res = chk.run(
                        max_depth=max_depth,
                        checkpoint_dir=checkpoint_dir,
                        checkpoint_every=checkpoint_every,
                        resume_from=recover,
                    )
                finally:
                    wd_teardown()

    with obs_telemetry.span("raft.check.finish"):
        if hub is not None:
            obs_telemetry.run_end(
                ok=res.ok, distinct=res.distinct,
                generated=res.generated, depth=res.depth,
            )
        summary = summarize(res, chk, time.monotonic() - t0, hub=hub)
    summary["_res"] = res
    summary["_chk"] = chk
    summary["_sanitizer"] = sanitizer
    summary["_tsan"] = tsan
    summary["_hub"] = hub
    return summary


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="tla_raft_tpu.check")
    p.add_argument("--config", default=REPO_CFG,
                   help="TLC .cfg file (single source of truth for constants)")
    p.add_argument("--spec", default=None,
                   help="TLA+ spec file to validate against the compiled "
                        "semantics (default: Raft.tla next to the cfg)")
    p.add_argument("--backend", choices=("jax", "oracle"), default="jax")
    p.add_argument("--workers", type=int, default=None,
                   help="accepted for myrun.sh compatibility; ignored")
    p.add_argument("--max-depth", type=int, default=None)
    p.add_argument("--chunk", type=int, default=DEFAULT_CHUNK,
                   help="expand rows per device dispatch (default "
                        f"{DEFAULT_CHUNK})")
    p.add_argument("--invariant", action="append", default=None,
                   help="override INVARIANT (repeatable; ~Name negates)")
    p.add_argument("--no-symmetry", action="store_true")
    p.add_argument("--no-view", action="store_true")
    p.add_argument("--mutate", action="append", default=None,
                   choices=("median-bug", "double-vote", "legacy-append",
                            "become-follower"),
                   help="compile in a planted spec bug or a dead legacy "
                        "action variant (SURVEY §4.4; the checker must "
                        "then find a violation or a state-count "
                        "divergence from the live spec)")
    p.add_argument("--servers", type=int, default=None, help="override |Servers|")
    p.add_argument("--vals", type=int, default=None, help="override |Vals|")
    p.add_argument("--max-election", type=int, default=None)
    p.add_argument("--max-restart", type=int, default=None)
    p.add_argument("--fpstore-dir", default=None,
                   help="use the native external-memory fingerprint store "
                        "(TLC's states/ spill analog) rooted at this dir")
    p.add_argument("--checkpoint-dir", default=None,
                   help="write per-level delta-log checkpoints here "
                        "(both backends; the single-device external-store "
                        "path also spills per-group partial records "
                        "inside a level)")
    p.add_argument("--checkpoint-every", type=int, default=1,
                   help="0 disables checkpointing; any other value "
                        "records EVERY level (the delta-log replay chain "
                        "cannot skip levels)")
    p.add_argument("--recover", default=None,
                   help="resume from a checkpoint: the --checkpoint-dir "
                        "directory (delta log) or a monolith .npz; "
                        "corrupt/torn/unmanifested records are "
                        "quarantined and the run resumes from the last "
                        "good contiguous prefix (docs/ROBUSTNESS.md)")
    p.add_argument("--fault", action="append", default=None,
                   metavar="SITE:ACTION[@N]",
                   help="deterministic fault injection (repeatable): "
                        "kill/torn/flip/fail at a named site's Nth hit, "
                        "e.g. delta.commit:kill@3 (docs/ROBUSTNESS.md; "
                        "env TLA_RAFT_FAULT takes the same grammar)")
    p.add_argument("--supervise", type=int, default=0, metavar="N",
                   help="supervisor mode: run the check as a child "
                        "process and relaunch it from its own "
                        "--checkpoint-dir up to N times after a crash "
                        "or preemption (model verdicts, usage errors "
                        "and integrity fail-stops are terminal, never "
                        "relaunched)")
    p.add_argument("--audit", type=int, default=0, metavar="N",
                   help="end-to-end integrity audit: every level, "
                        "re-expand N deterministic frontier rows "
                        "through the retained legacy kernels and "
                        "cross-check children/guards/fingerprints "
                        "against the hot path; on mismatch the level "
                        "is quarantined and the run rewinds to the "
                        "last committed checkpoint (single-device "
                        "engine; docs/ROBUSTNESS.md)")
    p.add_argument("--audit-retries", type=int, default=3, metavar="R",
                   help="fail-stop (exit 4) after R reproducible audit "
                        "mismatches (default 3)")
    p.add_argument("--watchdog", type=float, default=0.0, metavar="SECS",
                   help="per-level hang watchdog: arm every level with "
                        "a deadline of max(SECS, 8x the previous "
                        "level's wall time); a hung device dispatch "
                        "becomes a clean resumable exit 75 instead of "
                        "an infinite stall (0 = off)")
    p.add_argument("--mesh", type=int, default=0,
                   help="run distributed over an N-device mesh (0 = single device)")
    p.add_argument("--exchange", choices=("all_to_all", "all_gather"),
                   default="all_to_all", help="distributed fingerprint exchange")
    p.add_argument("--mesh-deep", action="store_true",
                   help="sharded deep sweep: frontier 1/D across devices "
                        "as uniform segment lists, sieve-and-compress "
                        "fingerprint exchange, double-buffered level tail "
                        "(requires --mesh and --fpstore-dir)")
    p.add_argument("--seg-rows", type=int, default=1 << 15,
                   help="rows per frontier segment per device (deep mode)")
    p.add_argument("--no-sieve", action="store_true",
                   help="deep mode: disable the pre-routing visited sieve")
    p.add_argument("--no-compress", action="store_true",
                   help="deep mode: fetch raw u64 fingerprints instead of "
                        "the delta-packed stream")
    p.add_argument("--no-pipeline", action="store_true",
                   help="disable the async intra-level pipeline "
                        "(overlapped expand/fetch/insert windows) — the "
                        "serial fetch-after-dispatch chain; counts are "
                        "bit-identical either way (env: "
                        "TLA_RAFT_PIPELINE=0)")
    p.add_argument("--pipeline-window", type=int, default=None,
                   metavar="N",
                   help="bounded in-flight fetch groups of the async "
                        "pipeline (default 2; 0 = serial; env: "
                        "TLA_RAFT_PIPELINE_WINDOW)")
    p.add_argument("--prewarm", type=int, choices=(0, 1), default=None,
                   help="forecast-driven AOT program prewarm: compile "
                        "the deep-level shape ladder in a background "
                        "thread while shallow levels run (default: "
                        "off; env: TLA_RAFT_PREWARM; "
                        "single-device engine only — ignored with "
                        "--mesh)")
    p.add_argument("--mxu-expand", type=int, choices=(0, 1), default=None,
                   help="MXU-native expand: guard coefficient matmul + "
                        "gather-free materialize (ops/mxu_expand.py). "
                        "Default on; 0 reverts to the legacy per-lane "
                        "kernels (A/B — counts are bit-identical). "
                        "env: TLA_RAFT_MXU")
    p.add_argument("--no-mxu-expand", action="store_true",
                   help="shorthand for --mxu-expand 0")
    p.add_argument("--megakernel", type=int, choices=(0, 1), default=None,
                   help="whole-level megakernel: fuse expand -> "
                        "probe-and-insert -> materialize -> invariant "
                        "scan into ONE jitted program per level with one "
                        "ledgered control fetch (engine/megakernel.py). "
                        "Default on; 0 reverts to the staged program "
                        "chain (A/B — counts are bit-identical). "
                        "Single-device engine; the external-store path "
                        "fuses expand+dedup per group. env: "
                        "TLA_RAFT_MEGAKERNEL")
    p.add_argument("--superstep", type=int, default=None, metavar="N",
                   help="multi-level resident supersteps: run up to N "
                        "consecutive fused levels inside ONE device "
                        "program with ONE ledgered ring fetch "
                        "(engine/superstep.py) — the dispatch floor "
                        "amortizes to 1/N.  Default 4; "
                        "1 reverts to the per-level megakernel "
                        "(A/B — counts are bit-identical).  Requires "
                        "the fused path (--megakernel 1); --audit "
                        "forces per-level.  env: TLA_RAFT_SUPERSTEP")
    p.add_argument("--dev-bytes", type=float, default=None,
                   metavar="BYTES",
                   help="device-memory budget for the HOT visited tier "
                        "(the on-device hash slab): growth past it "
                        "demotes whole generations to host RAM / disk "
                        "(store/tiered.py) instead of growing — "
                        "|visited| becomes storage-bounded like TLC's "
                        "disk FPSet.  0/unset = unbounded (hot-only; "
                        "counts are bit-identical either way).  env: "
                        "TLA_RAFT_STORE_BYTES")
    p.add_argument("--warm-bytes", type=float, default=None,
                   metavar="BYTES",
                   help="host-RAM budget for the WARM tier: demoted "
                        "generations past it drop to cold (disk-only, "
                        "re-read through an LRU page cache; default "
                        "1 GiB); on the external-store paths this "
                        "bounds the native store's in-RAM buffer "
                        "before it spills sorted runs (unset = the "
                        "native 64 MiB default).  env: "
                        "TLA_RAFT_WARM_BYTES")
    p.add_argument("--no-hashstore", action="store_true",
                   help="revert to the sort-based visited path (lexsort "
                        "+ searchsorted + sorted merge) instead of the "
                        "on-device open-addressing fingerprint store")
    p.add_argument("--cap-x", type=int, default=None,
                   help="per-device candidate capacity (distributed mode; "
                        "default 4096, with --mesh-deep 8 x --seg-rows)")
    p.add_argument("--canon", choices=("late", "expand"), default="late",
                   help="candidate canonicalization: 'late' fingerprints "
                        "only compacted candidates (default; required for "
                        "big symmetry groups), 'expand' folds the hash "
                        "into every fan-out lane")
    p.add_argument("--log", default="raft.log")
    p.add_argument("--coverage", action="store_true",
                   help="print per-action fired-transition counts (TLC -coverage)")
    p.add_argument("--json", action="store_true", help="emit a final JSON summary line")
    p.add_argument("--telemetry", type=int, choices=(0, 1), default=None,
                   help="run flight recorder (obs/telemetry.py): typed "
                        "run events appended crash-tolerantly to "
                        "events.jsonl in the checkpoint dir, plus the "
                        "unified telemetry block in --json.  Default "
                        "on; 0 disables.  Host-side only — counts and "
                        "dispatch/fetch budgets are identical either "
                        "way.  env: TLA_RAFT_TELEMETRY")
    p.add_argument("--profile", type=int, default=0, metavar="N",
                   help="opt-in device profiler: capture jax.profiler "
                        "traces for the first N dispatch windows "
                        "(supersteps on the fused path) into "
                        "<checkpoint-dir>/profile/, then `python -m "
                        "tla_raft_tpu.obs trace` merges the device "
                        "lanes into trace.json beside the host lanes. "
                        "Default off; counts are bit-identical either "
                        "way")
    p.add_argument("--progress", action="store_true",
                   help="live one-line progress display (states/s, "
                        "frontier, slab load, levels/dispatch, "
                        "forecast ETA to fixpoint) instead of one "
                        "Progress line per level")
    args = p.parse_args(argv)

    if args.supervise:
        return _supervise(args, argv if argv is not None else sys.argv[1:])
    if args.fault:
        from .resilience import faults as _faults

        _faults.install(";".join(args.fault))

    cfg = load_raft_config(args.config)
    overrides = {}
    if args.invariant:
        overrides["invariants"] = tuple(args.invariant)
    if args.no_symmetry:
        overrides["symmetry"] = False
    if args.no_view:
        overrides["use_view"] = False
    if args.mutate:
        overrides["mutations"] = tuple(args.mutate)
    if args.servers is not None:
        overrides["n_servers"] = args.servers
    if args.vals is not None:
        overrides["n_vals"] = args.vals
    if args.max_election is not None:
        overrides["max_election"] = args.max_election
    if args.max_restart is not None:
        overrides["max_restart"] = args.max_restart
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)

    logf = open(args.log, "w") if args.log and args.log != "-" else None
    out = Tee(sys.stdout, logf) if logf else sys.stdout
    if args.mesh_deep and not args.mesh:
        # without this guard the run would silently fall through to the
        # single-device engine and be mistaken for a deep-sweep result
        print("--mesh-deep requires --mesh N (the sharded deep sweep "
              "runs on a device mesh)", file=out)
        return 2
    t0 = time.monotonic()
    print(f"tla-raft-tpu checker: backend={args.backend}", file=out)
    print(f"Config {args.config}: {cfg.describe()}", file=out)

    # structural spec validation (SURVEY.md §7.2 step 1): the compiled
    # semantics must match the spec file actually sitting next to the cfg
    spec_path = args.spec
    if spec_path is None:
        cand = os.path.join(os.path.dirname(os.path.abspath(args.config)), "Raft.tla")
        spec_path = cand if os.path.exists(cand) else None
    if spec_path:
        from .tla_frontend import validate_spec

        problems = validate_spec(spec_path)
        if problems:
            for pr in problems:
                print(f"SPEC MISMATCH: {pr}", file=out)
            print("Refusing to check a spec that diverges from the compiled "
                  "semantics (pass --spec '' to skip).", file=out)
            return 2
        print(f"Spec {spec_path}: structure matches compiled semantics.", file=out)

    pline = None
    if args.progress:
        from .obs.progress import ProgressLine

        pline = ProgressLine(stream=sys.stderr)

    def progress(s):
        rate = s["distinct"] / max(s["elapsed"], 1e-9)
        if pline is not None:
            # live CR-updated line on stderr; the grep-able per-level
            # Progress lines keep landing in the log file (or on
            # stdout under --log - , which is a different stream from
            # the live line — the grep contract survives either way)
            pline.write(s)
            print(
                f"Progress: level {s['level']}, frontier "
                f"{s['frontier']}, distinct {s['distinct']}, "
                f"generated {s['generated']}, {rate:,.0f} states/s",
                file=logf if logf else out,
            )
            return
        print(
            f"Progress: level {s['level']}, frontier {s['frontier']}, "
            f"distinct {s['distinct']}, generated {s['generated']}, "
            f"{rate:,.0f} states/s",
            file=out,
        )
        out.flush()

    if args.mesh and args.prewarm:
        print("--prewarm applies to the single-device engine "
              "only; the mesh level loops compile their program "
              "set in line (flag ignored)", file=out)
    if args.mesh and args.mesh_deep and not args.fpstore_dir:
        print("--mesh-deep requires --fpstore-dir (the sharded "
              "deep sweep filters through per-owner external "
              "stores)", file=out)
        return 2
    from . import resilience

    try:
        summary = run_check(
            cfg,
            backend=args.backend,
            max_depth=args.max_depth,
            chunk=args.chunk,
            checkpoint_dir=args.checkpoint_dir,
            checkpoint_every=args.checkpoint_every,
            recover=args.recover,
            fpstore_dir=args.fpstore_dir,
            mesh=args.mesh,
            exchange=args.exchange,
            mesh_deep=args.mesh_deep,
            seg_rows=args.seg_rows,
            sieve=not args.no_sieve,
            compress=not args.no_compress,
            cap_x=args.cap_x,
            canon=args.canon,
            use_hashstore=not args.no_hashstore,
            pipeline=False if args.no_pipeline else None,
            pipeline_window=args.pipeline_window,
            prewarm=(
                None if args.prewarm is None else bool(args.prewarm)
            ),
            use_mxu=_mxu_arg(args),
            megakernel=(
                None if args.megakernel is None else bool(args.megakernel)
            ),
            superstep=args.superstep,
            audit=args.audit,
            audit_retries=args.audit_retries,
            watchdog=args.watchdog,
            telemetry=(
                None if args.telemetry is None else bool(args.telemetry)
            ),
            profile=args.profile,
            dev_bytes=(
                int(args.dev_bytes) if args.dev_bytes else None
            ),
            warm_bytes=(
                int(args.warm_bytes) if args.warm_bytes else None
            ),
            progress=progress,
            out=out,
            install_signals=(args.backend != "oracle"),
        )
    except resilience.Preempted as e:
        return _report_preempted(e, out, logf)
    except resilience.integrity.IntegrityError as e:
        # the whole integrity family is exit 4: an audit mismatch that
        # reproduced across its rewind budget (AuditFailStop) AND the
        # always-on conservation checks (exchange count reconciliation,
        # slab occupancy, corrupt fp stream) — none of these is a model
        # verdict, and exiting 1 would report a fake violation to the
        # supervisor and every fleet scheduler watching the code
        print(f"Integrity fail-stop: {e}", file=out)
        if logf:
            logf.close()
        return 4
    except Exception as e:  # graftlint: waive[GL003] — classifier
        # catch: device-loss errors map to exit 75, everything else
        # re-raises unchanged two lines down
        if resilience.elastic.is_device_loss(e):
            # a mesh participant failed: committed levels are durable,
            # so this is RESUMABLE — exit 75 (EX_TEMPFAIL) like a
            # preemption; --supervise relaunches and the elastic
            # resume re-shards onto the surviving devices
            print(f"Device loss: {type(e).__name__}: {e}.", file=out)
            if args.checkpoint_dir:
                print(
                    f"Resume with --recover {args.checkpoint_dir} "
                    "(any surviving device count: owner remap "
                    "re-shards the log)",
                    file=out,
                )
            if logf:
                logf.close()
            return 75
        raise
    res = summary["_res"]
    chk = summary["_chk"]
    sanitizer = summary["_sanitizer"]
    tsan = summary.get("_tsan")
    hub = summary.get("_hub")

    if pline is not None:
        pline.done()
    dt = time.monotonic() - t0
    print(file=out)
    if hub is not None and hub.path:
        print(
            f"Telemetry: {hub.n_events} events -> {hub.path} "
            "(timeline: python -m tla_raft_tpu.obs trace "
            f"{os.path.dirname(hub.path)})",
            file=out,
        )
    if sanitizer is not None:
        sanitizer.print_report(out)
    if tsan is not None:
        tsan.print_report(out)
    if res.ok:
        print("Model checking completed. No error has been found.", file=out)
    else:
        kind, trace = res.violation
        print(f"Error: {kind}.", file=out)
        if trace is not None:
            print_trace(cfg, trace, out)
    print(
        f"{res.generated} states generated, {res.distinct} distinct states "
        f"found, depth {res.depth}.",
        file=out,
    )
    # TLC prints the odds its 64-bit fingerprint set silently collided; the
    # rebuild dedups on the same 64-bit-universe hash, so report the same
    # birthday bound: E[collisions] ~= n(n-1)/2^65 (myrun.sh raft.log contract)
    coll = res.distinct * max(res.distinct - 1, 0) / 2.0**65
    print(
        f"The probability of a fingerprint collision is calculated to be "
        f"{coll:.3g}.",
        file=out,
    )
    if args.coverage and res.action_counts:
        print("Action coverage (transitions fired):", file=out)
        for name, n in sorted(res.action_counts.items(), key=lambda kv: -kv[1]):
            print(f"  {name}: {n}", file=out)
    print(f"Finished in {dt:.1f}s ({res.distinct / max(dt, 1e-9):,.0f} distinct states/s).", file=out)
    if args.json:
        # the one schema (summarize): ok/distinct/generated/depth/
        # level_sizes/mxu/seconds/violation/telemetry — shared with
        # run_check and the sweep service's result.json records
        print(json.dumps(summarize(res, chk, dt, hub=hub)), file=out)
    if logf:
        logf.close()
    if res.ok and (
        (sanitizer is not None and not sanitizer.ok)
        or (tsan is not None and not tsan.ok)
    ):
        # sanitizer/tsan findings on an otherwise-clean run: distinct
        # exit code so CI can tell "model violation" from "runtime
        # hygiene"
        return 3
    return 0 if res.ok else 1


if __name__ == "__main__":
    sys.exit(main())
