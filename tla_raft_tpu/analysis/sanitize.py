"""graftlint layer 3: runtime sanitizer for check runs (GRAFT_SANITIZE=1).

Three runtime ledgers the static layers cannot see:

* **host-transfer ledger** — explicit ``jax.device_get``/``device_put``
  are wrapped to count calls and bytes (the *intended* syncs); implicit
  device->host conversions (``bool()``/``int()``/``float()``/
  ``np.asarray`` on a device array — the *accidental* syncs that stall
  the dispatch pipeline mid-level) raise at the offending site (strict,
  default) or are counted (GRAFT_SANITIZE_STRICT=0).  ``jax``'s own
  ``transfer_guard`` is also armed, but it is a no-op on the CPU
  backend (host arrays are zero-copy), so the dunder interception is
  what makes the guarantee portable to the virtual-mesh CI.
* **compile-count ledger** — every XLA backend compile is counted via
  the jax monitoring events.  The engines tick the sanitizer once per
  BFS level and declare shape events (capacity growth, presize, new
  program shapes); a compile in a post-warmup level with NO declared
  shape event is a violation — that is precisely the "one silent
  retrace per level erases the kernel wins" regression class.
* **dispatch-thread guard** — worker threads marked by
  :func:`forbid_device_dispatch_in_thread` (the sharded checker's
  ``_io_pool``/``_ck_pool`` initializers do this unconditionally) must
  never reach a device dispatch: concurrently dispatched collectives
  interleave differently across devices and deadlock the mesh
  rendezvous (the PR 1 deep-tail incident).  The marking is always on
  and costs one thread-local read; under the sanitizer the wrapped
  ``device_get``/``device_put`` also assert it.

Module import is stdlib-only (device-free import contract); jax is
imported lazily when a :class:`Sanitizer` is entered.
"""

from __future__ import annotations

import os
import threading

# telemetry hub (obs/telemetry.py, stdlib-only): the dispatch /
# superstep / shape hooks below are ALREADY the choke points every
# level loop calls, so the flight recorder publishes from here instead
# of adding a second set of call sites to the engines
from ..obs import telemetry as _obs

_tl = threading.local()

# the active sanitizer (None = every hook below is a cheap no-op)
CURRENT: "Sanitizer | None" = None


# -- always-on dispatch-thread guard --------------------------------------

def forbid_device_dispatch_in_thread() -> None:
    """Mark the CURRENT thread as never-dispatching (pool initializer)."""
    _tl.no_dispatch = True


def device_dispatch_forbidden() -> bool:
    return getattr(_tl, "no_dispatch", False)


def assert_device_dispatch_ok(what: str = "device dispatch") -> None:
    """Raise if called from a thread marked no-dispatch.

    Cheap enough to be always on (one thread-local read): guards the
    program-dispatch helpers of parallel/sharded.py against a worker
    thread ever launching a device program."""
    if getattr(_tl, "no_dispatch", False):
        if CURRENT is not None:
            CURRENT.n_worker_dispatch += 1
        raise RuntimeError(
            f"graftlint: {what} from worker thread "
            f"{threading.current_thread().name!r} — worker threads must "
            "never launch device programs (concurrent collectives "
            "deadlock the mesh rendezvous; do the dispatch on the main "
            "thread and hand workers numpy buffers)"
        )


def mark_thread_compiles_declared() -> None:
    """Mark the CURRENT thread's XLA compiles as declared.

    The AOT prewarm thread (engine/pipeline.Prewarmer) calls this once:
    its compiles are the POINT of the thread, so the compile listener
    books them to the prewarm ledger instead of the per-level
    silent-retrace check (which audits the main dispatch thread)."""
    _tl.declared_compiles = True


def thread_compiles_declared() -> bool:
    return getattr(_tl, "declared_compiles", False)


class DispatchLog:
    """Lightweight per-level engine-dispatch counter.

    The engines note every device PROGRAM dispatch of their level loops
    at the call site (choke-point accounting, like the GL006 host-sync
    ledger — eager op dispatches are out of scope by design), and tick
    the level boundary through :func:`level_tick`.  Consumed by the
    GL011 dispatch-budget audit (analysis/dispatch_audit.py) and the
    bench's dispatches/level report without arming the full Sanitizer.
    """

    def __init__(self):
        self.total = 0
        self._cur = 0
        self.per_level: list[int] = []
        self.tags: dict[str, int] = {}
        # per-superstep accounting (engine/superstep.py): one entry per
        # superstep dispatch window — (programs dispatched, levels
        # covered).  The GL011 superstep budget and the bench's
        # levels_per_dispatch stat read these.
        self.per_superstep: list[int] = []
        self.superstep_levels: list[int] = []
        self._ss_mark: int | None = None

    def note(self, tag: str) -> None:
        self.total += 1
        self._cur += 1
        self.tags[tag] = self.tags.get(tag, 0) + 1

    def tick(self) -> None:
        self.per_level.append(self._cur)
        self._cur = 0

    def superstep_begin(self) -> None:
        self._ss_mark = self.total

    def superstep_tick(self, levels: int) -> None:
        mark = self._ss_mark if self._ss_mark is not None else self.total
        self.per_superstep.append(self.total - mark)
        self.superstep_levels.append(int(levels))
        self._ss_mark = None

    def steady_max_superstep(self) -> int:
        """Worst dispatches/superstep (each window is post-compile by
        construction — the dispatch count is shape-independent)."""
        return max(self.per_superstep) if self.per_superstep else 0

    def close(self) -> None:
        """Fold a trailing partial level (the fixpoint-discovery level
        never reaches the engine's tick) into the ledger."""
        if self._cur:
            self.tick()

    def steady_max(self, warmup: int = 2) -> int:
        """Worst dispatches/level past the compile-warmup prefix."""
        per = self.per_level[warmup:] or self.per_level
        return max(per) if per else 0


_DISPATCH_SINK: DispatchLog | None = None


def set_dispatch_sink(sink: DispatchLog | None) -> None:
    """Attach a :class:`DispatchLog` (bench / GL011 measurement)."""
    global _DISPATCH_SINK
    _DISPATCH_SINK = sink


def dispatch_sink() -> DispatchLog | None:
    return _DISPATCH_SINK


def tracking() -> bool:
    """Is any per-level ledger (sanitizer or dispatch sink) active?"""
    return CURRENT is not None or _DISPATCH_SINK is not None


def note_dispatch(tag: str) -> None:
    """Engines note one device-program dispatch of the level loop."""
    if CURRENT is not None:
        CURRENT.note_dispatch(tag)
    if _DISPATCH_SINK is not None:
        _DISPATCH_SINK.note(tag)
    _obs.dispatch(tag)


def superstep_begin() -> None:
    """The engine is about to dispatch one multi-level superstep."""
    if CURRENT is not None:
        CURRENT.superstep_begin()
    if _DISPATCH_SINK is not None:
        _DISPATCH_SINK.superstep_begin()
    _obs.superstep_begin()


def superstep_tick(levels: int) -> None:
    """One superstep's fetch completed, covering ``levels`` committed
    levels — snapshots the dispatch/fetch counters for the
    per-superstep ledger (the 1-dispatch-+-1-fetch-per-superstep
    acceptance surface)."""
    if CURRENT is not None:
        CURRENT.superstep_tick(levels)
    if _DISPATCH_SINK is not None:
        _DISPATCH_SINK.superstep_tick(levels)
    _obs.superstep_commit(levels)


def note_async_fetch_start() -> None:
    """The async pipeline started one fetch group (copy_to_host_async)."""
    if CURRENT is not None:
        CURRENT.n_async_started += 1


def note_async_fetch_complete() -> None:
    """One async fetch group completed through the ledgered get path."""
    if CURRENT is not None:
        CURRENT.n_async_completed += 1


# -- engine hooks (no-ops unless a Sanitizer is active) -------------------

def level_tick() -> None:
    """Engines call this once per completed BFS level."""
    if CURRENT is not None:
        CURRENT.level_tick()
    if _DISPATCH_SINK is not None:
        _DISPATCH_SINK.tick()


def note_shape_event(reason: str) -> None:
    """Engines declare legitimate recompile causes (capacity growth,
    presize, a new program shape) for the level in flight."""
    if CURRENT is not None:
        CURRENT.note_shape_event(reason)
    _obs.shape(reason)


_OBS_COMPILE_ARMED = False


def obs_watch_compiles() -> None:
    """Publish XLA backend compiles into the telemetry hub, and record
    its spans (``telemetry.span``) as profiler ``TraceAnnotation``s.

    Registered ONCE per process (idempotent), independent of the full
    Sanitizer: the listener is a cheap no-op while no hub is
    installed, and the prewarm thread's declared marker tags its
    compiles so the timeline can tell background AOT work from a
    silent in-line retrace.  Lazy jax import — the device-free module
    import contract (GL001) holds, and callers arm this only after
    ``platform.setup_jax``."""
    global _OBS_COMPILE_ARMED
    if _OBS_COMPILE_ARMED:
        return
    import jax
    from jax._src import monitoring

    _obs.set_annotator(jax.profiler.TraceAnnotation)

    def on_hit(name, **kw):
        # recorded inside the compile's timed span, on its thread: the
        # duration event that closes the span was served by the cache
        if name == "/jax/compilation_cache/cache_hits":
            _tl.cache_hit = True

    def on_event(name, *a, **kw):
        if name == "/jax/core/compile/backend_compile_duration":
            secs = a[0] if a and isinstance(a[0], (int, float)) else (
                kw.get("duration_secs", 0.0)
            )
            hit = getattr(_tl, "cache_hit", False)
            _tl.cache_hit = False
            _obs.compile_done(
                float(secs or 0.0), thread_compiles_declared(), hit
            )

    monitoring.register_event_listener(on_hit)
    monitoring.register_event_duration_secs_listener(on_event)
    _OBS_COMPILE_ARMED = True


_UNSET = object()


class _AllowTransfers:
    """Reentrant thread-local allowance for the wrapped explicit paths."""

    def __enter__(self):
        _tl.allow = getattr(_tl, "allow", 0) + 1

    def __exit__(self, *exc):
        _tl.allow -= 1


def _allowed() -> bool:
    return getattr(_tl, "allow", 0) > 0


class Sanitizer:
    """Context manager wrapping one check run.  See module docstring."""

    def __init__(self, warmup_levels: int | None = None,
                 strict: bool | None = None):
        if warmup_levels is None:
            warmup_levels = int(os.environ.get("GRAFT_SANITIZE_WARMUP", "2"))
        if strict is None:
            strict = os.environ.get("GRAFT_SANITIZE_STRICT", "1") == "1"
        self.warmup_levels = warmup_levels
        self.strict = strict
        self.level = 0
        self.compiles_total = 0
        self._level_compiles = 0
        self._level_events: list[str] = []
        self._grace = 0
        self.n_ledgered_get = 0
        self.n_ledgered_put = 0
        self.ledgered_bytes = 0
        self.n_implicit = 0
        self.n_worker_dispatch = 0
        # per-level engine-program dispatch/fetch ledger: the engines
        # note every level-loop device program at its call site and the
        # level boundary snapshots both counters — the GL011 budget and
        # the megakernel's one-dispatch/one-fetch smoke read these
        self.n_dispatches = 0
        self._level_dispatches = 0
        self._gets_at_tick = 0
        self.per_level_dispatches: list[int] = []
        self.per_level_gets: list[int] = []
        # per-SUPERSTEP dispatch/fetch windows (engine/superstep.py):
        # the engine brackets each multi-level dispatch with
        # superstep_begin/superstep_tick, and the acceptance claim —
        # one device program + one ledgered fetch per superstep — is
        # asserted from these (steady state: every window past the
        # first, which may carry the compile-ladder's extra fetches)
        self.n_supersteps = 0
        self.superstep_levels = 0
        self.per_superstep_dispatches: list[int] = []
        self.per_superstep_gets: list[int] = []
        self._ss_disp_mark: int | None = None
        self._ss_gets_mark: int | None = None
        # async-pipeline fetch groups (engine/pipeline.py): every
        # copy_to_host_async group must complete through the ledgered
        # device_get path — started minus completed is the count of
        # fetches that bypassed the ledger (must be 0 on clean runs)
        self.n_async_started = 0
        self.n_async_completed = 0
        # declared background (prewarm-thread) compiles — counted apart
        # from the per-level retrace check, which audits the main thread
        self.compiles_prewarm = 0
        self.violations: list[str] = []
        self._patches: list[tuple[object, str, object]] = []
        self._listener = None
        self._active = False
        self._tg_prev = _UNSET  # the guard's default is None — a real value
        # GRAFT_SANITIZE_DEBUG=1: capture the NAMES of compiled programs
        # per level (via jax_log_compiles) so a flagged retrace says
        # which program retraced, not just that one did
        self.debug = os.environ.get("GRAFT_SANITIZE_DEBUG") == "1"
        self._level_names: list[str] = []
        self._log_handler = None

    # -- wiring ----------------------------------------------------------

    def __enter__(self):
        global CURRENT
        if CURRENT is not None:
            raise RuntimeError("a Sanitizer is already active")
        try:
            return self._arm()
        except BaseException:  # graftlint: waive[GL003] — unwind + re-raise
            # private jax APIs (monitoring, ArrayImpl dunders) can move
            # across releases: a partially-armed sanitizer must unwind
            # fully or every retry would see stale patches / CURRENT
            self._disarm()
            raise

    def _arm(self):
        global CURRENT
        import jax
        from jax._src import monitoring
        from jax._src.array import ArrayImpl

        def on_event(name, *a, **kw):
            if self._active and name == (
                "/jax/core/compile/backend_compile_duration"
            ):
                # the event fires ON the compiling thread, so the
                # prewarm thread's declared marker routes its compiles
                # race-free to the prewarm ledger
                if thread_compiles_declared():
                    self.compiles_prewarm += 1
                    return
                self.compiles_total += 1
                self._level_compiles += 1

        self._listener = on_event
        monitoring.register_event_duration_secs_listener(on_event)

        if self.debug:
            import logging

            class _H(logging.Handler):
                def emit(h, record):  # noqa: N805
                    msg = record.getMessage()
                    if (self._active and msg.startswith("Compiling ")
                            and not thread_compiles_declared()):
                        self._level_names.append(msg.split()[1])

            self._log_prev = jax.config.jax_log_compiles
            jax.config.update("jax_log_compiles", True)
            self._log_handler = _H()
            logging.getLogger("jax").addHandler(self._log_handler)

        san = self

        def _patch(obj, name, repl):
            self._patches.append((obj, name, getattr(obj, name)))
            setattr(obj, name, repl)

        orig_get, orig_put = jax.device_get, jax.device_put

        def device_get(x, *a, **kw):
            assert_device_dispatch_ok("jax.device_get")
            with _AllowTransfers():
                out = orig_get(x, *a, **kw)
            san.n_ledgered_get += 1
            san.ledgered_bytes += _nbytes(out)
            return out

        def device_put(x, *a, **kw):
            assert_device_dispatch_ok("jax.device_put")
            with _AllowTransfers():
                out = orig_put(x, *a, **kw)
            san.n_ledgered_put += 1
            return out

        _patch(jax, "device_get", device_get)
        _patch(jax, "device_put", device_put)

        def conv_wrapper(name, orig):
            def wrapped(self_arr, *a, **kw):
                if san._active and not _allowed():
                    san.n_implicit += 1
                    if san.strict:
                        raise RuntimeError(
                            f"graftlint: unledgered implicit host "
                            f"transfer ({name} on a device array of "
                            f"shape {getattr(self_arr, 'shape', '?')}) "
                            "— use jax.device_get at an intended sync "
                            "point, or set GRAFT_SANITIZE_STRICT=0 to "
                            "count instead of raise"
                        )
                return orig(self_arr, *a, **kw)
            return wrapped

        for name in ("__array__", "__bool__", "__int__", "__float__",
                     "__index__"):
            orig = getattr(ArrayImpl, name, None)
            if orig is not None:
                _patch(ArrayImpl, name, conv_wrapper(name, orig))

        # arm jax's own guard too: free on CPU (zero-copy, never fires),
        # real coverage of np.asarray paths on accelerator backends
        self._tg_prev = jax.config.jax_transfer_guard_device_to_host
        jax.config.update(
            "jax_transfer_guard_device_to_host",
            "disallow" if self.strict else "log",
        )
        self._active = True
        CURRENT = self  # last: everything fallible is armed by now
        return self

    def _disarm(self):
        global CURRENT
        import jax
        from jax._src import monitoring

        self._active = False
        for obj, name, orig in reversed(self._patches):
            setattr(obj, name, orig)
        self._patches.clear()
        if self._tg_prev is not _UNSET:
            jax.config.update(
                "jax_transfer_guard_device_to_host", self._tg_prev
            )
            self._tg_prev = _UNSET
        if self._log_handler is not None:
            import logging

            logging.getLogger("jax").removeHandler(self._log_handler)
            jax.config.update("jax_log_compiles", self._log_prev)
            self._log_handler = None
        if self._listener is not None:
            try:
                monitoring._unregister_event_duration_listener_by_callback(
                    self._listener
                )
            except (AttributeError, ValueError):
                # listener API drift across jax versions: a stale
                # listener is inert anyway (gated on self._active)
                pass
            self._listener = None
        CURRENT = None

    def __exit__(self, *exc):
        # close the final (partial) level's accounting — the fixpoint-
        # discovery level dispatches and fetches but never reaches the
        # engine's tick (it breaks on n_new == 0)
        if self._level_compiles or self._level_dispatches:
            self.level_tick()
        self._disarm()
        return False

    # -- per-level accounting --------------------------------------------

    def note_shape_event(self, reason: str) -> None:
        self._level_events.append(reason)

    def note_dispatch(self, tag: str) -> None:
        self.n_dispatches += 1
        self._level_dispatches += 1

    def superstep_begin(self) -> None:
        self._ss_disp_mark = self.n_dispatches
        self._ss_gets_mark = self.n_ledgered_get

    def superstep_tick(self, levels: int) -> None:
        dm = (self._ss_disp_mark if self._ss_disp_mark is not None
              else self.n_dispatches)
        gm = (self._ss_gets_mark if self._ss_gets_mark is not None
              else self.n_ledgered_get)
        self.per_superstep_dispatches.append(self.n_dispatches - dm)
        self.per_superstep_gets.append(self.n_ledgered_get - gm)
        self.n_supersteps += 1
        self.superstep_levels += int(levels)
        self._ss_disp_mark = None
        self._ss_gets_mark = None

    def _steady(self, per_level: list[int]) -> list[int]:
        return per_level[self.warmup_levels:] or per_level

    def level_tick(self) -> None:
        self.per_level_dispatches.append(self._level_dispatches)
        self._level_dispatches = 0
        self.per_level_gets.append(
            self.n_ledgered_get - self._gets_at_tick
        )
        self._gets_at_tick = self.n_ledgered_get
        self.level += 1
        excused = bool(self._level_events) or self._grace > 0
        # a shape event declared in level N excuses level N+1 as well:
        # engines observe shape changes at level END (the new frontier/
        # store widths), while the programs built against those widths
        # first compile early in the NEXT level
        if self._level_events:
            self._grace = 1
        elif self._grace:
            self._grace -= 1
        if (
            self.level > self.warmup_levels
            and self._level_compiles > 0
            and not excused
        ):
            names = (
                f" ({', '.join(self._level_names)})"
                if self._level_names else ""
            )
            self.violations.append(
                f"level {self.level}: {self._level_compiles} XLA "
                f"compile(s) with no declared shape event{names} — a "
                "silent retrace in the steady-state level loop"
            )
        self._level_compiles = 0
        self._level_events = []
        self._level_names = []

    # -- reporting -------------------------------------------------------

    @property
    def unledgered_async_fetches(self) -> int:
        """Async fetch groups started but never completed through the
        ledgered get path (a drain/discard hole in the pipeline)."""
        return max(0, self.n_async_started - self.n_async_completed)

    @property
    def ok(self) -> bool:
        return (
            not self.violations
            and self.n_implicit == 0
            and self.n_worker_dispatch == 0
            and self.unledgered_async_fetches == 0
        )

    def report(self) -> dict:
        sd = self._steady(self.per_level_dispatches)
        sg = self._steady(self.per_level_gets)
        return dict(
            ok=self.ok,
            levels=self.level,
            warmup_levels=self.warmup_levels,
            compiles_total=self.compiles_total,
            prewarm_compiles=self.compiles_prewarm,
            unexpected_recompiles=len(self.violations),
            ledgered_device_get=self.n_ledgered_get,
            ledgered_device_put=self.n_ledgered_put,
            ledgered_bytes=self.ledgered_bytes,
            unledgered_transfers=self.n_implicit,
            async_fetches=self.n_async_completed,
            unledgered_async_fetches=self.unledgered_async_fetches,
            worker_thread_dispatches=self.n_worker_dispatch,
            engine_dispatches=self.n_dispatches,
            per_level_dispatches=list(self.per_level_dispatches),
            per_level_fetches=list(self.per_level_gets),
            steady_max_dispatches_per_level=max(sd) if sd else 0,
            steady_max_fetches_per_level=max(sg) if sg else 0,
            supersteps=self.n_supersteps,
            superstep_levels=self.superstep_levels,
            per_superstep_dispatches=list(self.per_superstep_dispatches),
            per_superstep_fetches=list(self.per_superstep_gets),
            steady_max_dispatches_per_superstep=(
                max(self.per_superstep_dispatches)
                if self.per_superstep_dispatches else 0
            ),
            steady_max_fetches_per_superstep=(
                max(self.per_superstep_gets)
                if self.per_superstep_gets else 0
            ),
            violations=list(self.violations),
        )

    def print_report(self, out) -> None:
        r = self.report()
        print(
            f"Sanitizer: {r['compiles_total']} XLA compiles over "
            f"{r['levels']} levels (warmup {r['warmup_levels']}), "
            f"{r['unexpected_recompiles']} post-warmup unexpected "
            "recompiles.",
            file=out,
        )
        print(
            f"Sanitizer: {r['ledgered_device_get']} ledgered fetches / "
            f"{r['ledgered_device_put']} puts "
            f"({r['ledgered_bytes']:,} B), "
            f"{r['unledgered_transfers']} unledgered host transfers, "
            f"{r['worker_thread_dispatches']} worker-thread device "
            "dispatches.",
            file=out,
        )
        print(
            f"Sanitizer: {r['async_fetches']} async pipeline fetches "
            f"({r['unledgered_async_fetches']} unledgered), "
            f"{r['prewarm_compiles']} declared prewarm compiles.",
            file=out,
        )
        print(
            f"Sanitizer: {r['engine_dispatches']} engine program "
            f"dispatches; steady-state max "
            f"{r['steady_max_dispatches_per_level']} dispatch(es) and "
            f"{r['steady_max_fetches_per_level']} ledgered fetch(es) "
            "per level.",
            file=out,
        )
        if r["supersteps"]:
            lvls = r["superstep_levels"]
            avg = lvls / max(r["supersteps"], 1)
            print(
                f"Sanitizer: {r['supersteps']} supersteps covering "
                f"{lvls} levels ({avg:.1f} levels/dispatch); "
                f"steady-state max "
                f"{r['steady_max_dispatches_per_superstep']} "
                f"dispatch(es) and "
                f"{r['steady_max_fetches_per_superstep']} ledgered "
                "fetch(es) per superstep.",
                file=out,
            )
        for v in r["violations"]:
            print(f"Sanitizer: VIOLATION — {v}", file=out)
        print(
            "Sanitizer: OK" if r["ok"] else "Sanitizer: FAIL",
            file=out,
        )


def _nbytes(tree) -> int:
    total = 0
    stack = [tree]
    while stack:
        x = stack.pop()
        if isinstance(x, dict):
            stack.extend(x.values())
        elif isinstance(x, (list, tuple)):
            stack.extend(x)
        elif hasattr(x, "_fields"):  # NamedTuple
            stack.extend(tuple(x))
        else:
            total += int(getattr(x, "nbytes", 0) or 0)
    return total
