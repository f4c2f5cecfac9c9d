"""Multi-level resident supersteps: N fused BFS levels per dispatch.

PR 9's megakernel cut a level to ONE device program + ONE ledgered
control fetch, but the host is still in the loop once per level — and
docs/PERF.md's gather-cliff analysis pins the residual floor on the
~38 ms FIXED dispatch/queue latency, not FLOPs, so shallow levels and
the sweep service's small configs remain pure launch tax.  This module
amortizes that floor to 1/N: a jitted, buffer-donating driver runs up
to N consecutive levels inside one ``lax.while_loop`` around the
megakernel's ``fused_level_core`` (expand -> probe-and-insert ->
materialize -> invariant — the SAME traced body, so the two paths
cannot drift), with each committed level's trace/delta record spooled
into a preallocated on-device ring buffer.  The host does ONE dispatch
+ ONE ledgered fetch per superstep; the fetch unpacks the ring into
exactly the per-level (pidx, slot, fps, mult, n_new) records the
checkpoint writer, trace reconstruction and resume already consume —
counts and violation stop points stay bit-identical on every golden
fixpoint.  BLEST and "Graph Traversal on Tensor Cores" (PAPERS.md)
keep BFS iterations accelerator-resident the same way when the
frontier fits.

**Commit discipline.**  A level inside the loop COMMITS (slab adopted,
frontier swapped, ring appended, loop continues) only when it is
totally clean: no abort, no invariant violation, no overflow of any
class (cap_x compaction, slab probe window, cap_m message width,
cap_f output seating, ring high-water).  Anything else stops the loop
BEFORE that level commits — the returned control vector names the stop
level and reason, the committed prefix is adopted as usual, and the
stopped level replays through the per-level megakernel (retained
verbatim as the A/B and audit reference; ``--superstep 1`` reverts to
it entirely), whose existing grow-and-redo machinery re-enters against
the original slab exactly as before.  A clean level with zero new
states commits as the terminal FIXPOINT record (its mult still counts
toward ``generated``, matching the staged loop's break order).

**Static shapes.**  One frontier capacity ``cap_f`` (forecast max over
the span, quantized through the engine's one capacity ladder) seats
every level of the superstep — the expand while_loop's trip count is
data-bounded on the live ``n_f``, and so are the probe-and-insert's
loops over the live lane blocks (``megakernel.probe_live_prefix``), so
overshoot costs little.  The ring capacity chains from the forecast
cap_out sequence (one rung per level, margin-inflated, clamped at
span * cap_f); ring appends are drop-mode scatters at a dynamic
offset, so the high-water check is exact (off + n_new > R) and a
stopped-for-ring level is clean — the next superstep simply restarts
there with a fresh ring (a fresh ring always seats at least one level,
so progress is guaranteed).

Default ON at span ``DEFAULT_SPAN`` wherever the per-level megakernel
is eligible; ``TLA_RAFT_SUPERSTEP=N`` / ``--superstep N`` set the span
(0/1 = off).  The ``--audit`` legacy re-expansion needs every level's
parent frontier alive on device, which the resident loop consumes by
design — audit runs force the per-level path (documented in
docs/PERF.md).
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np

from . import forecast
from . import megakernel as mk
from ..ops import tables as _tables

U64 = jnp.uint64
I64 = jnp.int64
I32 = jnp.int32
# numpy scalars: module import stays device-free (graftlint GL001)
SENT = np.uint64(0xFFFFFFFFFFFFFFFF)

# default levels per dispatch: deep enough to amortize the dispatch
# floor by 4x, shallow enough that a forecast miss (one stopped level
# replayed per-level) stays cheap against the span it saved
DEFAULT_SPAN = 4

# control-vector layout (i64[SS_LEN]) — the one scalar bundle the host
# reads per superstep
SS_LEVELS = 0     # committed levels (incl. a terminal fixpoint level)
SS_REASON = 1     # stop reason (REASON_* below)
SS_NF = 2         # frontier rows after the last committed level
SS_OFF = 3        # ring entries used by the committed prefix
SS_SLAB_LIVE = 4  # live slots of the returned slab (conservation)
SS_FLAGS = 5      # the STOPPED level's cause bits (FLAG_* below) —
#                   the host grows the overflowed budget BEFORE the
#                   per-level replay, so a stopped level costs one
#                   attempt + one redo exactly like the per-level path
SS_PROBE_LANES = 6  # candidate lanes probed, summed over every level
#                     the loop ran (committed or not)
SS_LEN = 7

FLAG_OVF_X = 1      # a chunk overflowed its cap_x compaction budget
FLAG_OVF_SLAB = 2   # a probe window filled (grow + redo)
FLAG_OVF_M = 4      # a child overflowed the cap_m msg-id width
FLAG_OVF_OUT = 8    # n_new > cap_f (cannot seat the next frontier)
FLAG_ABORT = 16     # split-brain abort in the stopped level
FLAG_BAD = 32       # invariant violation in the stopped level
# host-synthesized refinement of FLAG_OVF_SLAB (never set on device:
# the device reports slab PRESSURE; the budget is host policy): the
# grow the stop asked for would exceed the tiered store's device
# budget, so the host DEMOTES a generation instead of growing and the
# stopped level replays per-level against the drained slab — "demote,
# then redo" where the untiered path would "grow or die"
# (store/tiered.py; once a generation exists, supersteps stand down to
# span 1 — the resident loop cannot host-correct mid-window)
FLAG_OVF_SLAB_TIER = 64
# the spill sieve flagged POSSIBLE generation revisits in the stopped
# level (tier_hits > 0): the level is otherwise clean, but its counts
# are provisional until the host's exact tier probe corrects it — the
# per-level replay needs NO budget growth, just the tiered filter.
# Levels with ZERO sieve hits provably contain no spilled revisits
# (blooms have no false negatives) and commit in-window — that is what
# restores span-N residency under spill (ops/sieve.py)
FLAG_TIER = 128

# stop reasons: RUN means the while_loop exhausted its span — every
# level committed clean (the steady state).  STOP marks an uncommitted
# level (abort / violation / any overflow class): the host replays it
# through the per-level megakernel.  RING marks a CLEAN level that did
# not fit the ring: the next superstep restarts there.  FIX is the
# committed terminal fixpoint level.
REASON_RUN = 0
REASON_STOP = 1
REASON_RING = 2
REASON_FIX = 3

REASON_NAMES = {
    REASON_RUN: "span",
    REASON_STOP: "stop",
    REASON_RING: "ring",
    REASON_FIX: "fixpoint",
}


def span_from_env(default: int = DEFAULT_SPAN) -> int:
    """Levels per dispatch; <= 1 reverts to the per-level megakernel.
    Env wins; an installed autotuner plan's ``superstep_span`` is the
    fallback (tune/plans.py precedence)."""
    v = os.environ.get("TLA_RAFT_SUPERSTEP")
    if v is None or v == "":
        from ..tune import active

        return max(1, int(active.get("superstep_span", default)))
    return max(1, int(v))


# shared jit cache, the megakernel's (megakernel.cached_program): the
# traced body is fully determined by the engine's LevelSpec, the span
# and the donation flag plus the static (cap_f, ring) arguments, so
# same-config engines at the same budgets share one program set
_PROG_CACHE: "dict" = {}


def superstep_program_for(spec, span: int, donate: bool,
                          stats: dict | None = None):
    def build():
        # flight-recorder breadcrumb (see megakernel.level_program_for)
        from ..obs import telemetry as _obs

        prog = build_superstep_program(spec, span, donate)
        _obs.emit("program", kind="superstep", span=int(span),
                  chunk=spec.chunk, cap_x=spec.cap_x, cap_m=spec.cap_m,
                  table_bytes=prog.table_bytes)
        return prog

    return mk.cached_program(
        _PROG_CACHE, (spec, int(span), bool(donate)), build, stats
    )


def build_superstep_program(spec, span: int, donate: bool):
    """The jitted N-level driver for one ``LevelSpec`` (closes over the
    spec alone, never an engine, and takes the kernel's tables as its
    first operand — see megakernel.build_level_program).

    Static arguments: ``cap_f`` (the one frontier capacity every level
    of the superstep runs at — a chunk multiple >= the input frontier's
    capacity; smaller inputs are zero-padded in-trace) and ``ring``
    (the trace-spool capacity, >= cap_f).  ``sieve`` is a traced
    operand — the spill sieve's device words (the 1-word sentinel while
    tiering is off); jit retraces automatically when its shape changes,
    so one cached program serves each filter size.  Returns

      ``(frontier_out [cap_f], slab_out, ctrl i64[SS_LEN],
         meta_n i64[span], meta_mult i64[span, K],
         ring_fps u64[R], ring_pidx u32[R], ring_slot u16|u32[R])``

    where ``frontier_out`` is the last committed level's frontier — on
    a STOP it is the stopped level's PARENT, which is exactly what the
    per-level replay needs.  Ring/meta content beyond the committed
    prefix is garbage by contract (the host slices by the per-level
    counts).
    """
    chunk = spec.chunk
    K = spec.K
    span = int(span)
    slot_dt = jnp.uint16 if K <= 0xFFFF else jnp.uint32

    def superstep_body(tables, frontier, slab, n_f, lvl_cap, sieve,
                       cap_f: int, ring: int):
        ops = _tables.bound(spec, tables)
        cap_in = frontier.voted_for.shape[0]
        if cap_in > cap_f or cap_f % chunk or ring < 1:
            raise RuntimeError(
                f"superstep statics invalid: cap_in={cap_in}, "
                f"cap_f={cap_f}, chunk={chunk}, ring={ring}"
            )
        if cap_in < cap_f:
            # seat the input in the span-wide frontier buffer (zero
            # padding = the staged path's dead-tail convention; the
            # data-bounded expand never reads past n_f)
            frontier = jax.tree.map(
                lambda x: jnp.concatenate(
                    [x, jnp.zeros((cap_f - cap_in,) + x.shape[1:],
                                  x.dtype)]
                ),
                frontier,
            )

        R = ring
        lane = jnp.arange(cap_f, dtype=I64)

        def cond(c):
            lvl, _off, reason = c[0], c[1], c[2]
            # lvl_cap is a TRACED operand (min(span, levels remaining
            # to --max-depth)): one compiled program serves every
            # remainder instead of minting a program per residual span
            # — depth-capped sweep jobs would otherwise pay a fresh
            # XLA compile for each distinct cap % span
            return (
                (reason == REASON_RUN) & (lvl < span)
                & (lvl.astype(I64) < lvl_cap)
            )

        def body(c):
            (lvl, off, _reason, _flags, n_f, fr, slab, rf, rp, rs, mn,
             mm, probed) = c
            (new_fr, slab2, n_new, abort_at, ovf_x, ovf_slab, ovf_m,
             bad, mult, fps_out, pay_out, tier_hits,
             probe_lanes) = mk.fused_level_core(
                ops, fr, slab, n_f, sieve, cap_f
            )
            abort = abort_at < n_f
            ovf_out = n_new > cap_f  # next frontier cannot seat
            ring_ovf = off + n_new > R
            # sieve hits = POSSIBLE spilled revisits: the level must
            # not commit until the host's exact tier probe corrects it
            # (zero hits = provably clean, commits in-window)
            tier_stop = tier_hits > 0
            stop = (abort | ovf_x | ovf_slab | (ovf_m & (n_new > 0))
                    | ovf_out | (bad >= 0) | tier_stop)
            commit = ~stop & ~ring_ovf
            # ring append: drop-mode scatter at the dynamic offset —
            # writes beyond the committed prefix (an uncommitted
            # level's lanes, dead lanes past n_new) land out of range
            # or in garbage territory the host never reads
            idx = jnp.where(lane < n_new, off + lane, R)
            rf = rf.at[idx].set(fps_out, mode="drop")
            rp = rp.at[idx].set(
                (pay_out // K).astype(jnp.uint32), mode="drop"
            )
            rs = rs.at[idx].set(
                (pay_out % K).astype(slot_dt), mode="drop"
            )
            mn = mn.at[lvl].set(n_new)
            mm = jax.lax.dynamic_update_slice(
                mm, mult[None, :], (lvl, jnp.zeros((), I32))
            )
            fix = commit & (n_new == 0)
            reason2 = jnp.where(
                stop, REASON_STOP,
                jnp.where(
                    ring_ovf, REASON_RING,
                    jnp.where(fix, REASON_FIX, REASON_RUN),
                ),
            ).astype(I32)
            flags2 = (
                ovf_x.astype(I32) * FLAG_OVF_X
                + ovf_slab.astype(I32) * FLAG_OVF_SLAB
                + (ovf_m & (n_new > 0)).astype(I32) * FLAG_OVF_M
                + ovf_out.astype(I32) * FLAG_OVF_OUT
                + abort.astype(I32) * FLAG_ABORT
                + (bad >= 0).astype(I32) * FLAG_BAD
                + tier_stop.astype(I32) * FLAG_TIER
            )
            sel = lambda a, b: jnp.where(commit, a, b)  # noqa: E731
            fr2 = jax.tree.map(sel, new_fr, fr)
            return (
                lvl + commit.astype(I32),
                off + jnp.where(commit, n_new, 0),
                reason2,
                jnp.where(stop, flags2, jnp.zeros((), I32)),
                jnp.where(commit, n_new, n_f),
                fr2,
                sel(slab2, slab),
                rf, rp, rs, mn, mm,
                probed + probe_lanes,
            )

        init = (
            jnp.zeros((), I32),                      # lvl
            jnp.zeros((), I64),                      # off
            jnp.full((), REASON_RUN, I32),           # reason
            jnp.zeros((), I32),                      # stop flags
            n_f.astype(I64),
            frontier,
            slab,
            jnp.full((R,), SENT, U64),               # ring fps
            jnp.zeros((R,), jnp.uint32),             # ring pidx
            jnp.zeros((R,), slot_dt),                # ring slot
            jnp.zeros((span,), I64),                 # meta n_new
            jnp.zeros((span, K), I64),               # meta mult
            jnp.zeros((), I64),                      # lanes probed
        )
        (lvl, off, reason, flags, n_f_out, fr, slab, rf, rp, rs, mn,
         mm, probed) = jax.lax.while_loop(cond, body, init)
        ctrl = jnp.stack([
            lvl.astype(I64),
            reason.astype(I64),
            n_f_out,
            off,
            (slab != SENT).sum().astype(I64),
            flags.astype(I64),
            probed,
        ])
        return fr, slab, ctrl, mn, mm, rf, rp, rs

    return _tables.TabledProgram(
        superstep_body, spec.kern.device_tables,
        static_argnames=("cap_f", "ring"),
        donate_argnums=(1,) if donate else (),
    )


def unpack_ring(ctrl, meta_n, meta_mult, ring_fps, ring_pidx,
                ring_slot):
    """The superstep fetch -> per-level delta/trace records.

    Returns ``(recs, reason, n_f, slab_live, flags)`` — ``flags`` is
    the SS_FLAGS stop-cause bitmask — where ``recs`` is one
    dict per committed level — ``n_new``, ``mult`` i64[K], ``fps``
    u64[n_new], ``pidx``/``slot`` i64[n_new] — in level order, exactly
    the record shape the per-level megakernel fetch produces (the
    checkpoint writer, trace reconstruction and resume consume either
    verbatim)."""
    ctrl = np.asarray(ctrl, np.int64)
    levels = int(ctrl[SS_LEVELS])
    recs = []
    off = 0
    mn = np.asarray(meta_n, np.int64)
    mm = np.asarray(meta_mult, np.int64)
    for i in range(levels):
        n_new = int(mn[i])
        recs.append(dict(
            n_new=n_new,
            mult=mm[i],
            fps=np.asarray(
                ring_fps[off:off + n_new], np.uint64
            ),
            pidx=np.asarray(
                ring_pidx[off:off + n_new]
            ).astype(np.int64),
            slot=np.asarray(
                ring_slot[off:off + n_new]
            ).astype(np.int64),
        ))
        off += n_new
    reason = REASON_NAMES.get(int(ctrl[SS_REASON]), "stop")
    return (recs, reason, int(ctrl[SS_NF]), int(ctrl[SS_SLAB_LIVE]),
            int(ctrl[SS_FLAGS]))


def ring_capacity(fut, span: int, cap_f: int, pow2) -> int:
    """Ring slots for one superstep, chained from the forecast cap_out
    sequence: one rung per forecast level (1.25-margined like the
    prewarm ladder, clamped at cap_f — a level can never commit more
    than it can seat), padded with the last rung (or cap_f outright
    when there is no signal yet), quantized pow2 and clamped to
    [cap_f, span * cap_f].  Small capacities pin the ring at the
    span * cap_f ceiling outright: the fetch overage is kilobytes
    while a forecast-wiggled ring would mint a fresh compiled program
    per rung — compile count, not memory, is the cost down there
    (the same reasoning as the megakernel's 4*chunk floor)."""
    if span * cap_f <= (1 << 16):
        return pow2(span * cap_f)
    if fut:
        m = forecast.cap_margin()
        rungs = [min(int(f * m) + 1, cap_f) for f in fut[:span]]
        rungs += [rungs[-1]] * (span - len(rungs))
        est = sum(rungs)
    else:
        est = span * cap_f
    est = max(est, cap_f)
    return min(pow2(est), pow2(span * cap_f))


def ledger_trace(cfg=None, span: int = 2):
    """Closed jaxpr of the superstep driver at the audit's tiny
    reference shapes — the graftlint layer-2 (GL010) registration: the
    while_loop wraps the megakernel's fused_level_core, so the budget
    pins the same residue (hashstore probe rounds + materialize
    parent gathers) and the ring spool must stay scatter-drop only."""
    from ..config import RaftConfig
    from ..models.raft import init_batch
    from ..ops import hashstore
    from .bfs import JaxChecker

    if cfg is None:
        cfg = RaftConfig(
            n_servers=2, n_vals=1, max_election=1, max_restart=1,
        )
    eng = JaxChecker(cfg, chunk=64, use_hashstore=True, megakernel=True)
    fr0, _ovf = eng._deflate(init_batch(cfg, 1))
    fr = eng._frontier_struct(fr0, 64)
    slab = jax.ShapeDtypeStruct((hashstore.MIN_CAP,), jnp.uint64)
    n_f = jax.ShapeDtypeStruct((), jnp.int64)
    sieve = jax.ShapeDtypeStruct((1,), jnp.uint64)
    prog = build_superstep_program(eng.level_spec(), span, donate=False)
    return jax.make_jaxpr(
        lambda f, s, n, lc, sv: prog(f, s, n, lc, sv, cap_f=64, ring=128)
    )(fr, slab, n_f, jax.ShapeDtypeStruct((), jnp.int64), sieve)
