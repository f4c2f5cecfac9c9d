"""Async intra-level pipeline: overlapped expand / fetch / insert windows.

docs/PERF.md's round-5 silicon budget shows the deep-level wall clock
as a strict serial chain — expand spans, the device->host fetch over
the host link, the host-side filter/insert tail — with the
device idle during every host stage and vice versa.  This module holds
the two mechanisms that break the chain:

* :class:`AsyncFetchWindow` — a bounded in-flight window of
  device->host fetch groups.  The main thread dispatches group g+1's
  device programs immediately after *starting* group g's copies with
  ``copy_to_host_async()``; group g's host arrays are consumed (through
  the LEDGERED ``jax.device_get`` path, so the GRAFT_SANITIZE transfer
  ledger counts every async fetch) only when the window is full or the
  level ends.  Two invariants from docs/PERF.md carry over by
  construction: all device dispatch stays on the main thread (the
  window never spawns threads — overlap comes from the asynchronous
  copy engine, not from concurrent dispatch), and the window DRAINS at
  the level boundary, so store inserts never see a level's candidates
  early (``AsyncFetchWindow.live`` is the cross-instance assertion
  hook the tests pin this with).

* :class:`Prewarmer` — a forecast-driven AOT compile thread.  The
  engines emit a shape plan (engine/forecast.py predicts the
  power-of-two capacity ladder) and the prewarmer compiles the
  deep-level program set (``jit(...).lower(...).compile()``) in ONE
  background daemon thread while the cheap shallow levels run.
  Lower/compile never dispatches a device program (inputs are
  ``jax.ShapeDtypeStruct`` avals), so the no-worker-dispatch rule is
  not in play; the thread marks itself via
  :func:`analysis.sanitize.mark_thread_compiles_declared` so its
  compiles land in the sanitizer's *declared prewarm* ledger instead
  of tripping the per-level silent-retrace check.  The compiled
  executables are dropped — the payoff routes through JAX's persistent
  compilation cache (platform.setup_jax wires it), which also means a
  supervised relaunch (``--supervise``) never re-pays a compile this
  or any earlier incarnation already did.

Serial fallback: ``TLA_RAFT_PIPELINE=0`` (or a window of 0) makes
every submit complete immediately — bit-identical control flow to the
pre-pipeline engines, which is what the A/B parity gates diff against.

Module import is device-free (jax is imported lazily), matching the
package's import contract (graftlint GL001).
"""

from __future__ import annotations

import atexit
import os
import sys
import threading
from collections import deque

from .. import resilience
from ..analysis import sanitize as graft_sanitize

# bounded in-flight fetch groups: 2 keeps one group streaming over the
# host link while the next group's device programs run, which is the
# whole overlap — deeper windows only add peak memory (each in-flight
# group pins its padded fetch buffers on both sides of the link)
DEFAULT_WINDOW = 2


def enabled_by_env() -> bool:
    """Pipeline default: ON; ``TLA_RAFT_PIPELINE=0`` reverts to serial."""
    return os.environ.get("TLA_RAFT_PIPELINE", "1") != "0"


def window_from_env() -> int:
    v = os.environ.get("TLA_RAFT_PIPELINE_WINDOW")
    return int(v) if v else DEFAULT_WINDOW


def async_start(tree) -> None:
    """Start device->host copies for every jax array leaf of ``tree``.

    Pure hint: the copy engine begins moving bytes as soon as the
    producing program finishes, so the later (ledgered) ``fetch``
    completes without stalling the dispatch pipeline.  Non-device
    leaves (numpy, None) pass through untouched; a backend without
    ``copy_to_host_async`` degrades to a no-op.
    """
    import jax

    for leaf in jax.tree.leaves(tree):
        start = getattr(leaf, "copy_to_host_async", None)
        if start is not None:
            try:
                start()
            except Exception:  # graftlint: waive[GL003] — the hint must
                # never take the checker down; the ledgered fetch below
                # still works (it just blocks for the full copy)
                return


def fetch(tree):
    """Complete a fetch through the LEDGERED sync path.

    ``jax.device_get`` is looked up at call time so the sanitizer's
    wrapper (the transfer ledger) sees every pipeline fetch; with
    ``async_start`` already issued the call returns as soon as the
    in-flight copy lands instead of round-tripping from scratch.
    Telemetry: every ledgered fetch publishes its measured wait + byte
    count to the hub (the "fetch window" timeline track).
    """
    import time as _time

    import jax

    from ..obs import telemetry as _obs

    t0 = _time.monotonic() if _obs.current() is not None else 0.0
    with _obs.span("raft.fetch"):
        # THE intended sync point of the async pipeline: every window
        # fetch funnels through this site
        # graftlint: waive[GL006]
        out = jax.device_get(tree)
    if _obs.current() is not None:
        _obs.fetch_done(
            _time.monotonic() - t0, graft_sanitize._nbytes(out)
        )
    # --profile N capture: one completed ledgered fetch IS one dispatch
    # window (a superstep on the fused path, a level elsewhere) — tick
    # the jax-profiler session so it stops after its budgeted windows
    from ..analysis import devprof as _devprof

    _devprof.profile_tick()
    return out


class AsyncFetchWindow:
    """Bounded in-flight window of device->host fetch groups.

    ``submit(arrays, consume)`` starts the async copies and queues the
    group; when more than ``window`` groups are in flight the OLDEST
    completes (ledgered fetch + ``consume(host_arrays)`` on the calling
    thread).  ``drain()`` completes everything — call it at the level
    boundary, BEFORE any store insert that level gates on.  ``window=0``
    degenerates to the serial fetch-after-dispatch chain.

    ``AsyncFetchWindow.live`` counts submitted-but-unconsumed groups
    across every instance — the test hook asserting store inserts never
    overlap an open window.
    """

    live = 0  # class-wide in-flight groups (level-boundary assertion)

    def __init__(self, window: int = DEFAULT_WINDOW):
        self.window = max(0, int(window))
        self._q: deque = deque()
        self.submitted = 0
        self.max_inflight = 0

    @property
    def inflight(self) -> int:
        return len(self._q)

    def submit(self, arrays, consume) -> None:
        """Queue one fetch group; completes older groups past the window.

        ``consume(host_arrays)`` runs on the submitting (main) thread —
        handing its host-side work to a pool is the consumer's choice;
        the window itself never spawns threads.
        """
        resilience.fault_fire("pipeline.window")
        graft_sanitize.note_async_fetch_start()
        async_start(arrays)
        self._q.append((arrays, consume))
        AsyncFetchWindow.live += 1
        self.submitted += 1
        self.max_inflight = max(self.max_inflight, len(self._q))
        while len(self._q) > self.window:
            self._complete_one(run_consume=True)

    def _complete_one(self, run_consume: bool) -> None:
        arrays, consume = self._q.popleft()
        AsyncFetchWindow.live -= 1
        host = fetch(arrays)
        graft_sanitize.note_async_fetch_complete()
        # progress heartbeat: a completed fetch group proves the level
        # is still moving, so the hang watchdog re-earns its budget —
        # long multi-window levels never false-trip on total wall time
        resilience.elastic.watchdog_touch()
        if run_consume:
            consume(host)

    def drain(self) -> None:
        """Complete every in-flight group (the level-boundary barrier)."""
        while self._q:
            self._complete_one(run_consume=True)

    def discard(self) -> None:
        """Complete in-flight fetches WITHOUT consuming (abort paths).

        The fetches still finish through the ledgered path so the
        sanitizer's start/complete accounting balances even when a
        level is thrown away (abort, capacity-overflow redo).
        """
        while self._q:
            self._complete_one(run_consume=False)


class DeferredFetch:
    """One-group deferred fetch — the level-tail specialization.

    ``DeferredFetch(enabled, arrays)`` starts the copies immediately
    (ledgered start); ``get()`` completes them through the ledgered
    path — place it AFTER the device work the fetch should overlap and
    BEFORE the level boundary — and returns the host arrays (idempotent
    after the first call).  ``discard()`` balances the ledger on abort
    paths.  ``enabled=False`` fetches at construction: the serial
    chain.  Keeps the submit/drain contract of every single-group tail
    site in one place instead of five hand-rolled window+dict copies.
    """

    def __init__(self, enabled: bool, arrays):
        self._win = AsyncFetchWindow(1 if enabled else 0)
        self._h: dict = {}
        self._win.submit(arrays, lambda h: self._h.update(h=h))

    def get(self):
        self._win.drain()
        return self._h["h"]

    def discard(self) -> None:
        self._win.discard()


class Prewarmer:
    """Background AOT compiler for the forecast shape ladder.

    ``submit(plan)`` takes ``(key, thunk)`` pairs; thunks run
    ``jit(...).lower(shapes...).compile()`` for one program at one
    forecast capacity.  Keys dedupe across submissions (the engines
    re-emit the plan every level as the forecast sharpens; only fresh
    shapes compile).  One daemon thread, never joined by the run loop
    — a prewarm that has not finished by the time the main thread
    needs the shape simply means that compile is paid in line, exactly
    the pre-prewarm behavior.  Thunk failures are logged and counted,
    never raised: prewarm is an optimization, not a correctness gate.
    """

    def __init__(self, name: str = "tla-raft-prewarm"):
        self._name = name
        self._lock = threading.Lock()
        self._seen: set = set()
        self._pending: list = []
        self._thread: threading.Thread | None = None
        self._running = False  # worker loop live (flips under _lock)
        self._stopping = False
        self.n_ok = 0
        self.n_failed = 0
        # a daemon thread still inside an XLA compile when the
        # interpreter tears down segfaults (the compiler calls back into
        # a dying runtime), so interpreter exit drops the queue and
        # joins the one in-flight compile before teardown begins
        atexit.register(self.shutdown)

    def submit(self, plan) -> int:
        """Queue fresh (key, thunk) pairs; returns how many were new."""
        with self._lock:
            fresh = [(k, t) for k, t in plan if k not in self._seen]
            for k, _t in fresh:
                self._seen.add(k)
            self._pending.extend(fresh)
            # _running (not Thread.is_alive) gates the restart: the
            # worker clears it under THIS lock in the same critical
            # section that decides to exit, so a submit landing between
            # that decision and the thread's actual death still starts
            # a fresh worker instead of stranding the queue
            if self._pending and not self._running and not self._stopping:
                self._running = True
                self._thread = threading.Thread(
                    target=self._run, name=self._name, daemon=True
                )
                self._thread.start()
        return len(fresh)

    def _run(self) -> None:
        # compiles from this thread are DECLARED: the sanitizer books
        # them to the prewarm ledger, not the per-level retrace check
        graft_sanitize.mark_thread_compiles_declared()
        while True:
            with self._lock:
                if self._stopping or not self._pending:
                    self._running = False
                    return
                key, thunk = self._pending.pop(0)
            try:
                thunk()
                self.n_ok += 1
            except Exception as e:  # graftlint: waive[GL003] — a failed
                # prewarm costs only the compile it tried to hide; the
                # main loop compiles the shape in line as before
                self.n_failed += 1
                print(
                    f"[pipeline] prewarm {key!r} failed: "
                    f"{type(e).__name__}: {e}",
                    file=sys.stderr,
                )

    @property
    def pending(self) -> int:
        with self._lock:
            return len(self._pending)

    @property
    def stopped(self) -> bool:
        """True once shutdown ran — a stopped prewarmer never compiles
        again; owners build a fresh one instead."""
        with self._lock:
            return self._stopping

    def join(self, timeout: float | None = None) -> None:
        """Wait for the compile queue to empty (tests; never the run loop)."""
        t = self._thread
        if t is not None:
            t.join(timeout)

    def shutdown(self, timeout: float = 120.0) -> None:
        """Drop queued thunks and wait out the in-flight compile.

        Remaining queue entries are abandoned (their compiles would now
        be paid in line, the pre-prewarm behavior); only the one compile
        already inside XLA must finish before the interpreter may tear
        down.  Idempotent — the atexit hook and any explicit caller can
        both run it."""
        # the worker holds _lock only for the O(1) queue pop / exit
        # decision, never across a compile, so this atexit-time acquire
        # always completes in microseconds:
        # graftsync: waive[GL016]
        with self._lock:
            self._stopping = True
            self._pending.clear()
        self.join(timeout)
        # a shut-down prewarmer has nothing left for interpreter exit
        # to wait on — unpinning it lets long-lived processes (pytest,
        # sweep drivers) that build many checkers release each one
        atexit.unregister(self.shutdown)
