"""Level-synchronous BFS on device — TLC's exploration engine, TPU-native.

Replaces the reference checker's core runtime (SURVEY.md §3.1: the BFS
loop, worker pool, FPSet dedup table, invariant evaluation, trace
reconstruction and checkpointing of the external TLC jar driven by
/root/reference/myrun.sh:3) with:

* a **device-resident compact frontier**: full states minus the message
  bitmask, which is stored as a sparse id list (``msg_ids``) — a
  reachable state carries at most a few dozen of the universe's
  thousands of message bits, so the sparse form is ~3x smaller
  (~250 B/state), which is what lets multi-million-state frontiers and
  their children coexist in HBM.  Chunks inflate ids -> bitmask on
  device (scatter-free one-hot OR); materialized children deflate via a
  ``top_k`` bit-position extraction.  Nothing state-sized ever crosses
  the host link (streaming states through the host cost ~100 us/state
  on the round-5 device),
* the successor kernel's masked fan-out (ops/successor.py) run in chunks,
* **compact-then-dedup**, all on device:
    1. per chunk: a ``top_k`` partial sort compacts the ~0.5%-dense valid
       lanes of the |chunk|*K fan-out into a fixed cap_x lane budget
       (no dedup, no visited access — the expand program stays
       shape-stable for the whole run);
    2. per level: one lexsort over all chunks' compacted candidates
       picks the min-(fp_full, payload) representative per view
       fingerprint (the deterministic refinement of TLC's
       first-writer-wins — see oracle/explicit.py) and drops states
       already in the sorted visited store (``searchsorted``).
  Compaction shrinks the level-wide sort from |frontier|*K dense lanes
  to the ~3.5 valid candidates per frontier state (measured on the
  reference config) padded to the cap_x budget — the difference between
  sorting ~10^8 and ~10^6 keys per level at full scale.
* **materialization** of only the surviving (parent, slot) pairs,
* batched invariant kernels (engine/invariants.py) on each new level,
* per-level (parent, slot) spill to the host for counterexample traces
  (SURVEY.md §3.4: TLC's predecessor-chain walk),
* per-level snapshots for checkpoint/resume (SURVEY.md §3.5: TLC's
  ``states/`` metadir + ``-recover``).

Host/device discipline: the chunk loop runs with **zero host syncs**
(the split-brain abort flag and per-slot multiplicities accumulate on
device); each level fetches one small stats bundle (new-state count,
abort/overflow flags, generated count) and the (parent, slot) trace
spill.  Round 1 synced the abort flag per chunk, serializing host and
device every 256 states (ADVICE.md round 1).

Deadlock states (no action enabled) are not reported, matching the
``-deadlock`` flag in myrun.sh:3 which *disables* deadlock checking.

All device computations run at power-of-two padded shapes so XLA compiles
a logarithmic number of program variants; every array is explicitly
dtyped (u8 state, u64 fingerprints, i64 payloads).
"""

from __future__ import annotations

import dataclasses
import functools
import os
import sys
import time
import weakref
import zipfile
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import resilience
from ..analysis import sanitize as graft_sanitize
from ..obs import telemetry as graft_obs
from ..config import RaftConfig
from ..models.raft import RaftState, init_batch, to_oracle
from ..ops import hashstore
from ..ops import sieve as graft_sieve
from ..ops import tables as _tables
from ..ops.successor import SuccessorKernel, get_kernel
from ..store import tiered as graft_tiered
from . import megakernel as graft_megakernel
from . import superstep as graft_superstep
from . import pipeline as graft_pipeline
from ..analysis import devprof as graft_devprof
from . import forecast as graft_forecast
from .forecast import MIN_LEVELS as PRESIZE_MIN_LEVELS, pow2ceil as _pow2
from .invariants import resolve_invariant_kernel

U64 = jnp.uint64
I64 = jnp.int64
I32 = jnp.int32
U32C = jnp.uint32
# numpy scalars, not jnp: a module-scope jnp.uint64(...) call would force
# XLA client creation at IMPORT time, aborting pytest collection on hosts
# with no usable backend (numpy scalars promote identically inside jit)
SENT = np.uint64(0xFFFFFFFFFFFFFFFF)
BIG = np.int64(1 << 62)


class CheckResult(NamedTuple):
    """Same shape as oracle.explicit.CheckResult for differential tests."""

    ok: bool
    distinct: int
    generated: int
    depth: int
    level_sizes: tuple[int, ...]
    violation: tuple | None  # (kind, trace=[(action, OState), ...])
    action_counts: dict | None = None  # TLC -coverage analog (see oracle)


class Frontier(NamedTuple):
    """Compact device frontier: RaftState minus ``msgs``, plus sparse ids.

    ``msg_ids``: ascending message ids, -1 padded, width ``cap_m``."""

    voted_for: jnp.ndarray
    current_term: jnp.ndarray
    role: jnp.ndarray
    log_term: jnp.ndarray
    log_val: jnp.ndarray
    log_len: jnp.ndarray
    match_index: jnp.ndarray
    next_index: jnp.ndarray
    commit_index: jnp.ndarray
    election_count: jnp.ndarray
    restart_count: jnp.ndarray
    pending: jnp.ndarray
    val_sent: jnp.ndarray
    msg_ids: jnp.ndarray


_CORE_FIELDS = [f for f in RaftState._fields if f != "msgs"]


class _HostSeg:
    """A frontier segment demoted to host RAM (numpy field dict).

    The single-chip deep sweep walls when one level's frontier outgrows
    HBM (level 29 of the reference config: ~15 GB of children at a
    16 GB chip — BASELINE.md).  TLC's answer is disk spill
    (/root/reference/.gitignore:2); ours is this tier: sealed
    destination segments demote to host RAM under a device-byte budget
    (TLA_RAFT_DEV_BYTES) and page back in on demand — the expand and
    materialize walks both consume segments in ascending payload order,
    so residency is a moving window, not a working set.

    Below host RAM sits the WARM tier: a segment past the host budget
    (TLA_RAFT_FSEG_BYTES) spills its field dict to disk through the
    tiered store's FrontierPager (kind="fseg" via the atomic writer)
    and reloads lazily the first time ``fields`` is touched again —
    the same moving-window residency, one tier further down."""

    __slots__ = ("_fields", "_rows", "pager", "token", "__weakref__")

    def __init__(self, fields: dict):
        self._fields = fields
        self._rows = fields["voted_for"].shape[0]
        self.pager = None
        self.token = None

    @property
    def fields(self) -> dict:
        if self._fields is None:
            self._fields = self.pager.load(self.token)
        return self._fields

    @property
    def rows(self) -> int:
        return self._rows

    @property
    def resident_bytes(self) -> int:
        """Host-RAM footprint (0 while spilled to the warm tier)."""
        if self._fields is None:
            return 0
        return sum(
            int(np.prod(v.shape)) * v.dtype.itemsize
            for v in self._fields.values()
        )

    def spill(self, pager, depth: int = -1) -> None:
        """Commit the field dict to the warm tier and drop the RAM copy
        (idempotent re-spill: a reloaded segment already has a token —
        its artifact is still on disk, so dropping the copy is free)."""
        if self.token is None:
            self.token = pager.spill(self._fields, depth=depth)
            self.pager = pager
        self._fields = None


def _seg_rows(seg) -> int:
    return seg.rows if isinstance(seg, _HostSeg) else seg.voted_for.shape[0]


# _pow2 is forecast.pow2ceil (imported above) — one next-power-of-two
# helper shared by the engines and the capacity forecaster.

# Uniform segment size for external-store frontiers (rows).  ONE fixed
# buffer shape per field across every deep level serves two masters:
# the BFC allocator recycles identical slabs instead of fragmenting HBM
# over a replay's worth of odd-sized trees (measured: a fresh process
# can allocate 15.7 GB in one piece, but the replay OOMed at ~12 GB of
# live accounting), and the two-segment materialize gather compiles ONCE
# instead of once per frontier magnitude (remote compiles are minutes).
SEG_ROWS = 1 << 21


def _concat_fields(segs: list) -> Frontier:
    """Collapse a segment list into one frontier, FIELD BY FIELD, consuming
    the list: the naive tree-level concat holds the whole parent twice
    (inputs + outputs across all fields at once); sequencing per field and
    dropping the source column as soon as its concat lands caps the spike
    at ~one parent plus its largest field (the message-id lanes, ~60% of
    state bytes) instead of two parents."""
    if len(segs) == 1:
        return segs[0]
    cols = {f: [getattr(s, f) for s in segs] for f in Frontier._fields}
    segs[:] = []  # drop the tuples so each column is the last reference
    out = {}
    for f in Frontier._fields:
        out[f] = jnp.concatenate(cols[f])
        cols[f] = None
    return Frontier(**out)


def _host_cap(n: int, chunk: int) -> int:
    """Frontier capacity on the external-store path: whole uniform
    segments once past one segment, else the small-level quantizer."""
    if n > SEG_ROWS:
        return -(-n // SEG_ROWS) * SEG_ROWS
    c = _cap_steps(n)
    if c % chunk:
        c = _pow2(n)
    return max(c, chunk)


def _cap_steps(n: int) -> int:
    """Smallest c >= n with c in {2^k, 3*2^(k-1)} — frontier capacities.

    Pure pow2 quantization wastes up to 50% of HBM in padding; at the
    deep-sweep frontiers (tens of GB) that waste IS the memory wall, so
    frontiers quantize on half-steps (~17% max waste) at the cost of at
    most one extra compiled shape per magnitude.  Callers must still
    enforce divisibility by their chunk (a half-step 3*2^(k-1) is only a
    chunk multiple when 2^(k-1) >= chunk — see _frontier_cap)."""
    p = _pow2(n)
    half = 3 * (p >> 2)
    return half if half >= n and half > 0 else p


def _cap4(n: int) -> int:
    """Next power of 4: capacities quantize coarser so the checker compiles
    ~half as many program shapes (remote TPU compiles are minutes each)."""
    c = 1
    while c < n:
        c <<= 2
    return c


def _pad_axis0(x: jnp.ndarray, cap: int) -> jnp.ndarray:
    pad = cap - x.shape[0]
    if pad <= 0:
        return x[:cap]
    return jnp.concatenate([x, jnp.zeros((pad, *x.shape[1:]), x.dtype)])


@functools.partial(jax.jit, static_argnames=("cap_x",))
def _compact_payloads(valid_flat, payload, cap_x: int):
    """Compact the valid fan-out lanes' payloads into cap_x lanes.

    The late-canonicalization variant of ``_chunk_compact``: keys on the
    validity mask alone (fingerprints don't exist yet at this point — they
    are computed afterwards from the materialized candidates).  Kept lanes
    preserve original lane order (payload-ascending).  Shared by the
    single-device and mesh engines; outputs are cap_x wide even when the
    fan-out is smaller (tiny mesh frontiers have C = cap_f*K < cap_x).
    Returns (payload[cap_x] with garbage beyond ``lane``, lane bool[cap_x],
    overflow).
    """
    C = valid_flat.shape[0]
    n_live = valid_flat.sum()
    k = min(cap_x, C)
    key = jnp.where(valid_flat, C - jnp.arange(C, dtype=I32), 0)
    vals, idx = jax.lax.top_k(key, k)
    lane = vals > 0
    cp = payload[idx]
    if cap_x > k:
        lane = jnp.concatenate([lane, jnp.zeros((cap_x - k,), bool)])
        cp = jnp.concatenate([cp, jnp.full((cap_x - k,), -1, cp.dtype)])
    return cp, lane, n_live > cap_x


@functools.partial(jax.jit, static_argnames=("cap_x",))
def _chunk_compact(fps_view, fps_full, payload, cap_x: int):
    """Compact one chunk's valid fan-out lanes into cap_x lanes (no dedup).

    fps_view/full u64[C] (SENT where invalid), payload i64[C] (global
    parent*K+slot).  ``top_k`` on an earliest-lane-first key is a partial
    sort — far cheaper than a full argsort over the ~0.5%-dense C lanes,
    and it keeps the visited store out of this (large, shape-stable)
    program so store growth never recompiles the expand kernel.  Kept
    lanes preserve original lane order (payload-ascending), matching the
    stable compaction the dedup's determinism contract assumes.
    """
    C = fps_view.shape[0]
    live = fps_view != SENT
    n_live = live.sum()
    key = jnp.where(live, C - jnp.arange(C, dtype=I32), 0)
    vals, idx = jax.lax.top_k(key, cap_x)  # descending = earliest lanes first
    lane = vals > 0
    return (
        jnp.where(lane, fps_view[idx], SENT),
        jnp.where(lane, fps_full[idx], SENT),
        jnp.where(lane, payload[idx], -1),
        n_live > cap_x,
    )


def _filter_compact(hit, cv, cf, cp, cap_g: int):
    """Shared tail of the two group filters: drop hit lanes, compact
    the survivors into cap_g lanes preserving lane order (stable top_k
    key).  ONE implementation so the hash and sorted membership tests
    can never drift on the compaction contract."""
    C = cv.shape[0]
    keep = (cv != SENT) & ~hit
    n = keep.sum()
    key = jnp.where(keep, C - jnp.arange(C, dtype=I32), 0)
    vals, idx = jax.lax.top_k(key, cap_g)
    lane = vals > 0
    return (
        jnp.where(lane, cv[idx], SENT),
        jnp.where(lane, cf[idx], SENT),
        jnp.where(lane, cp[idx], -1),
        n > cap_g,
    )


@functools.partial(jax.jit, static_argnames=("cap_g",))
def _group_filter(cv, cf, cp, visited, cap_g: int):
    """Drop already-visited candidates from a group of chunks and compact.

    At deep levels ~85-90% of candidate lanes are revisits of the sorted
    store; filtering a fixed-size group before the level-wide sort keeps
    that sort (and its working set) proportional to the NEW states, not
    the whole fan-out.  Dropping a visited view fingerprint removes its
    whole candidate group, so downstream representative choice is
    unaffected; compaction preserves lane order (stable top_k key).
    """
    pos = jnp.searchsorted(visited, cv)
    hit = visited[jnp.clip(pos, 0, visited.shape[0] - 1)] == cv
    return _filter_compact(hit, cv, cf, cp, cap_g)


@functools.partial(jax.jit, static_argnames=("cap_g",))
def _group_filter_hash(cv, cf, cp, slab, cap_g: int):
    """``_group_filter`` with the open-addressing store: the visited
    membership test is a depth-bounded hash probe (O(1) expected, 2-3
    gather rounds at the enforced <=1/2 load) instead of a binary
    search's ~22 rounds of random gathers against the sorted table —
    the membership-side gather cliff (docs/PERF.md "Hashed visited
    store").  Compaction is the SHARED ``_filter_compact`` tail."""
    hit = hashstore.probe_impl(slab, cv)
    return _filter_compact(hit, cv, cf, cp, cap_g)


@jax.jit
def _level_dedup_hash(cv, cf, cp, slab):
    """Hash-store replacement for ``_level_dedup`` + ``_merge_sorted``:
    ONE fused probe-and-insert resolves uniqueness, visited membership
    AND the store update — no 3-key lexsort over the level's lanes, no
    searchsorted, no whole-store re-sort.  The min-(fp_full, payload)
    representative per view fingerprint is chosen by the kernel's
    two-phase min-reduce (the group-min lemma), so counts are
    bit-identical to the sort path; survivors compact in LANE order
    (payload-ascending — the same order the external-store path pins).

    Returns (n_new, new_fps, new_payload, slab', overflow).  On
    overflow the caller grows the store and redoes the level against
    the ORIGINAL slab (the kernel is functional)."""
    slab2, fresh, n_new, ovf = hashstore.probe_and_insert_impl(
        slab, cv, cf, cp
    )
    new_fps, new_pay = hashstore.compact_fresh(fresh, cv, cp, cv.shape[0])
    return n_new, new_fps, new_pay, slab2, ovf


@jax.jit
def _level_dedup(cv, cf, cp, visited):
    """Global dedup over the level's compacted candidates, on device.

    One lexsort by (fp_view, fp_full, payload) across every chunk's
    candidates resolves uniqueness and picks the min-(fp_full, payload)
    representative per view fingerprint (the deterministic refinement of
    TLC's first-writer-wins); a searchsorted against the sorted visited
    store drops already-known states.  Doing this once per level instead
    of per chunk halves the sort work of the old two-stage scheme.
    Retraces when the visited capacity grows — acceptable, the program is
    small next to the expand kernel.

    Returns (n_new, new_fps u64[C] view-sorted SENT-padded, payload i64[C]).
    """
    order = jnp.lexsort((cp, cf, cv))
    sv, sp = cv[order], cp[order]
    first = jnp.concatenate([jnp.ones((1,), bool), sv[1:] != sv[:-1]])
    pos = jnp.searchsorted(visited, sv)
    hit = visited[jnp.clip(pos, 0, visited.shape[0] - 1)] == sv
    new = first & (sv != SENT) & ~hit
    n_new = new.sum()
    comp = jnp.argsort(~new, stable=True)
    keep = jnp.arange(sv.shape[0]) < n_new
    return (
        n_new,
        jnp.where(keep, sv[comp], SENT),
        jnp.where(keep, sp[comp], -1),
    )


def _group_unique_impl(cv, cf, cp):
    """Intra-group dedup for the external-store path.

    Picks the min-(fp_full, payload) representative per view fingerprint
    within one group of chunks and compacts the survivors to a fetchable
    prefix (cv-ascending) — the same ordering contract as
    ``_level_dedup`` but with no visited access: the visited filter
    happens host-side against the external store.  Keeping only the
    group-min per view is lossless for the level-global representative
    choice (the global min over candidates equals the min over
    group-mins), which is what makes the per-group host path bit-
    identical to the level-wide device dedup.
    """
    order = jnp.lexsort((cp, cf, cv))
    sv, sf, sp = cv[order], cf[order], cp[order]
    first = jnp.concatenate([jnp.ones((1,), bool), sv[1:] != sv[:-1]])
    keep = first & (sv != SENT)
    n_u = keep.sum()
    comp = jnp.argsort(~keep, stable=True)
    pref = jnp.arange(sv.shape[0]) < n_u
    return (
        n_u,
        jnp.where(pref, sv[comp], SENT),
        jnp.where(pref, sf[comp], SENT),
        jnp.where(pref, sp[comp], -1),
    )


_group_unique = jax.jit(_group_unique_impl)


@jax.jit
def _merge_sorted(visited, new_fps):
    """Insert a level's new fingerprints into the sorted store."""
    return jnp.sort(jnp.concatenate([visited, new_fps]))


# NOTE: destination frontiers are built by SEGMENT-bounded concats, not
# donated dynamic_update_slice writes — transient is 2 segments, never 2
# frontiers, with no reliance on donation semantics (a backend that
# ignores a donation would hold two destination copies).


class LevelOps:
    """The level's traced building blocks: message-set conversion, the
    chunk expand, the materialize slice and the invariant scan.

    Shared by ``JaxChecker`` and its frozen ``LevelSpec`` snapshot, so
    the fused level programs trace the same code as the staged path
    while closing over no engine.  They read only ``cfg``, ``kern``,
    ``fpr``, ``K``, ``uni_words``, ``id_dtype``, ``cap_m``, ``cap_x``,
    ``canon``, ``orbit`` and ``inv_fns``.
    """

    # -- sparse <-> dense message-set conversion ---------------------------

    def _ids_to_msgs(self, ids: jnp.ndarray) -> jnp.ndarray:
        """msg_ids [n, cap_m] -> packed u32 [n, n_words] (scatter-free).

        Ids are unique per state, so the per-word sum of one-hot bit
        contributions equals the bitwise OR.
        """
        n_words = self.uni_words
        idi = ids.astype(I32)
        live = idi >= 0
        w = jnp.clip(idi, 0, None) >> 5
        bit = jnp.where(live, U32C(1) << (idi & 31).astype(jnp.uint32), U32C(0))
        hit = jnp.arange(n_words, dtype=I32)[None, None, :] == w[:, :, None]
        return (jnp.where(hit, bit[:, :, None], U32C(0))).sum(1, dtype=jnp.uint32)

    def _msgs_to_ids(self, msgs: jnp.ndarray):
        """packed u32 [n, n_words] -> (ids [n, cap_m] ascending -1-padded,
        overflow bool[n]): top_k over bit-position keys.  Overflow is
        per-row so callers can mask out garbage/padding lanes (a padded
        materialize lane holds a clipped parent's garbage child, which
        must not abort a real run)."""
        M = self.kern.uni.M
        bits = self.fpr.unpack_bits(msgs).astype(I32)
        key = bits * (M - jnp.arange(M, dtype=I32))
        vals, _ = jax.lax.top_k(key, self.cap_m)
        ids = jnp.where(vals > 0, M - vals, -1)
        ovf = bits.sum(-1, dtype=I32) > self.cap_m
        return ids.astype(self.id_dtype), ovf

    def _ids_insert(self, ids: jnp.ndarray, added: jnp.ndarray):
        """Child msg-id lists by sorted insertion of the sent ids.

        ids i32ish[n, cap_m]: the PARENTS' ascending -1-padded id lists;
        added i32[n, A]: the ids the materialized action sent (-1 pads).
        Returns (child_ids [n, cap_m], overflow bool[n]) — bit-identical
        to ``_msgs_to_ids(children.msgs)`` (same set, ascending, -1-
        padded) but in A tiny elementwise passes instead of a top_k over
        the M-wide universe per row (182.9 ms vs ~2 ms per 32k-row slice
        on the v5e — the measured dominator of the materialize pass).
        Already-present ids re-sent by guard-free actions (e.g.
        FollowerAcceptEntry, Raft.tla:275-300 — set union semantics,
        Raft.tla:43-45) are skipped like the bitmask OR they mirror.
        """
        M = self.kern.uni.M
        cap_m = ids.shape[1]
        pos_iota = jnp.arange(cap_m, dtype=I32)[None, :]
        cur = jnp.where(ids < 0, I32(M), ids.astype(I32))  # pads sort last
        ovf = jnp.zeros(ids.shape[0], bool)
        for a in range(added.shape[1]):
            aid = added[:, a].astype(I32)[:, None]  # [n, 1]
            live = (aid >= 0) & ~jnp.any(cur == aid, axis=1, keepdims=True)
            pos = jnp.sum(cur < aid, axis=1, dtype=I32)[:, None]
            shifted = jnp.concatenate([cur[:, :1], cur[:, :-1]], axis=1)
            ins = jnp.where(
                pos_iota < pos, cur,
                jnp.where(pos_iota == pos, aid, shifted),
            )
            ovf = ovf | (live[:, 0] & (cur[:, -1] < M))
            cur = jnp.where(live, ins, cur)
        child = jnp.where(cur >= M, I32(-1), cur).astype(self.id_dtype)
        return child, ovf

    def _inflate(self, fr: Frontier) -> RaftState:
        """Frontier chunk -> full RaftState with the packed bitmask."""
        core = {f: getattr(fr, f) for f in _CORE_FIELDS}
        return RaftState(msgs=self._ids_to_msgs(fr.msg_ids), **core)

    def _deflate(self, st: RaftState):
        core = {f: getattr(st, f) for f in _CORE_FIELDS}
        ids, ovf = self._msgs_to_ids(st.msgs)
        return Frontier(msg_ids=ids, **core), ovf

    # -- device helpers ----------------------------------------------------

    def _mat_slice_impl(self, frontier: Frontier, pay, n_valid):
        """Materialize one survivor payload slice, entirely on device.

        Gathers parents from the (compact) frontier, inflates their
        message sets, materializes the children, deflates them back to
        the compact form, and scans invariants — only per-slice scalars
        ever reach the host.
        """
        K = self.K
        pidx = (pay // K).astype(I32)
        slots = pay % K
        parents_c = jax.tree.map(lambda x: x[jnp.clip(pidx, 0, None)], frontier)
        parents = self._inflate(parents_c)
        children, added = self.kern.materialize_added(parents, slots)
        child_ids, ovf_rows = self._ids_insert(parents_c.msg_ids, added)
        child_f = Frontier(
            msg_ids=child_ids,
            **{f: getattr(children, f) for f in _CORE_FIELDS},
        )
        in_range = jnp.arange(ovf_rows.shape[0], dtype=I64) < n_valid
        bad_at = self._inv_scan_impl(children, n_valid)
        return child_f, bad_at, (ovf_rows & in_range).any()

    def _mat_slice_seg_impl(self, seg_a: Frontier, seg_b: Frontier, base,
                            pay, n_valid):
        """_mat_slice over a two-segment parent window (external-store
        path).  Payload-sorted slices touch a narrow parent range, so a
        (segment j, segment j+1) window always covers one slice; parents
        gather from whichever side of the boundary they fall on.  With a
        single-segment frontier the window is (seg, seg) and the where
        collapses to a plain gather."""
        K = self.K
        L = seg_a.voted_for.shape[0]
        pidx = (pay // K).astype(I64) - base
        slots = pay % K
        lo = jnp.clip(pidx, 0, L - 1).astype(I32)
        hi = jnp.clip(pidx - L, 0, L - 1).astype(I32)
        in_a = pidx < L
        parents_c = jax.tree.map(
            lambda a, b: jnp.where(
                in_a.reshape((-1,) + (1,) * (a.ndim - 1)), a[lo], b[hi]
            ),
            seg_a, seg_b,
        )
        parents = self._inflate(parents_c)
        children, added = self.kern.materialize_added(parents, slots)
        child_ids, ovf_rows = self._ids_insert(parents_c.msg_ids, added)
        child_f = Frontier(
            msg_ids=child_ids,
            **{f: getattr(children, f) for f in _CORE_FIELDS},
        )
        in_range = jnp.arange(ovf_rows.shape[0], dtype=I64) < n_valid
        bad_at = self._inv_scan_impl(children, n_valid)
        return child_f, bad_at, (ovf_rows & in_range).any()

    def _expand_chunk_impl(self, part_f: Frontier, start, n_f):
        """One chunk: inflate + expand + mask + valid-lane compaction.

        start/n_f are device i64 scalars so chunk position doesn't force
        a recompile; the visited store is deliberately NOT an input (its
        capacity grows over the run and would retrace this — the largest —
        program).  Returns compacted candidates + chunk stats.

        canon="late": the expand is guards-only; the compacted candidate
        (parent, slot) pairs are materialized in-chunk and fingerprinted
        from their full states (feat matmul + message-set matmul, both
        P-folded) — the symmetry fold touches cap_x lanes, not cap*K.
        """
        K = self.K
        part = self._inflate(part_f)
        cap = part.voted_for.shape[0]
        if self.canon == "late":
            # orbit always goes through the split two-program route
            # (_expand_chunk_split); tracing the fused variant with the
            # orbit machinery inlined is exactly the program that overran
            # the round-5 compile window (see _jit_expand_programs)
            assert not self.orbit, "orbit uses _expand_chunk_split"
            (children, lane, cp_raw, mult_slots, abort_at,
             overflow) = self._expand_chunk_core_late(part, start, n_f)
            fv, ff, _msum = self.fpr.state_fingerprints(children)
            cv = jnp.where(lane, fv.astype(U64), SENT)
            cf = jnp.where(lane, ff.astype(U64), SENT)
            cp = jnp.where(lane, cp_raw, -1)
        else:
            msum_part = self.fpr.msg_hash(part.msgs)
            exp = self.kern.expand(part, msum_part)
            valid, payload, mult_slots, abort_at = self._chunk_bookkeeping(
                exp.valid, exp.mult, exp.abort, start, n_f, cap
            )
            fpv = jnp.where(valid, exp.fp_view, SENT).ravel()
            fpf = jnp.where(valid, exp.fp_full, SENT).ravel()
            cv, cf, cp, overflow = _chunk_compact(fpv, fpf, payload, self.cap_x)
        return cv, cf, cp, mult_slots, abort_at, overflow

    def _chunk_bookkeeping(self, valid, mult, ab_state, start, n_f, cap):
        """Shared chunk accounting: in-range mask, global payload ids,
        per-slot multiplicity totals, first-abort position."""
        K = self.K
        in_range = (start + jnp.arange(cap, dtype=I64) < n_f)[:, None]
        valid = valid & in_range
        base = ((start + jnp.arange(cap, dtype=I64)) * K)[:, None]
        payload = (base + jnp.arange(K, dtype=I64)[None]).ravel()
        mult_slots = jnp.where(valid, mult, 0).astype(I64).sum(0)
        ab = ab_state & in_range[:, 0]
        abort_at = jnp.where(
            ab.any(), start + jnp.argmax(ab).astype(I64), BIG
        )
        return valid, payload, mult_slots, abort_at

    def _expand_chunk_core_late(self, part, start, n_f):
        """canon='late' chunk body up to materialize — NO fingerprints.

        ``part`` is the already-inflated chunk.  Shared by the fused
        program and the orbit split path (see ``_jit_expand_programs``).
        """
        K = self.K
        cap = part.voted_for.shape[0]
        valid, mult, ab_state = self.kern.expand_guards(part)
        valid, payload, mult_slots, abort_at = self._chunk_bookkeeping(
            valid, mult, ab_state, start, n_f, cap
        )
        cp_raw, lane, overflow = _compact_payloads(
            valid.ravel(), payload, self.cap_x
        )
        lidx = jnp.clip(cp_raw // K - start, 0, cap - 1).astype(I32)
        slots = cp_raw % K
        parents = jax.tree.map(lambda x: x[lidx], part)
        children = self.kern.materialize(parents, slots)
        return children, lane, cp_raw, mult_slots, abort_at, overflow

    def _inv_scan_impl(self, children: RaftState, n_valid):
        """All configured invariants over a level; (first_bad_idx|-1)."""
        N = children.voted_for.shape[0]
        in_range = jnp.arange(N, dtype=I64) < n_valid
        bad = jnp.zeros(N, bool)
        for _name, fn in self.inv_fns:
            bad = bad | (~fn(self.cfg, children, self.kern.tables) & in_range)
        return jnp.where(bad.any(), jnp.argmax(bad).astype(I64), -1)


@dataclasses.dataclass(frozen=True)
class LevelSpec(LevelOps):
    """Everything the fused level body reads at trace time, frozen at
    one engine's budgets (``JaxChecker.level_spec``).

    The megakernel and superstep programs close over a spec, never an
    engine, and their caches key on it: equal specs trace equal
    programs, so every checker on one config at one set of budgets
    shares one jitted program and its traced shape rungs, and a checker
    whose budgets grow gets a new spec and a program of its own.  The
    fields after ``orbit`` follow from ``kern`` (one per config,
    ``ops/successor.get_kernel``) and stay out of the comparison."""

    kern: SuccessorKernel
    chunk: int
    cap_x: int
    cap_m: int
    canon: str
    orbit: bool
    cfg: RaftConfig = dataclasses.field(compare=False)
    K: int = dataclasses.field(compare=False)
    fpr: object = dataclasses.field(compare=False)
    uni_words: int = dataclasses.field(compare=False)
    id_dtype: object = dataclasses.field(compare=False)
    inv_fns: tuple = dataclasses.field(compare=False)


class JaxChecker(LevelOps):
    """The TPU model checker for one RaftConfig.

    Parameters:
      chunk: max parents expanded per kernel launch (memory knob; the
        per-launch working set is ~chunk * K * (F + hash) bytes).
      cap_x: per-chunk compacted-survivor lanes (grows on overflow).
      progress: optional callable(level_stats_dict) for per-level logging.
    """

    def __init__(
        self,
        cfg: RaftConfig,
        chunk: int = 1024,
        cap_x: int | None = None,
        progress: Callable[[dict], None] | None = None,
        host_store=None,
        cap_m: int = 96,
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
        watchdog=None,
        store_bytes: int | None = None,
        warm_bytes: int | None = None,
        sieve: bool | None = None,
    ):
        # canon="late": expand computes guards only; the compacted
        # candidates are materialized and fingerprinted with the full-state
        # path — the P-wide symmetry fold runs over ~3.5 candidates/state
        # instead of all K fan-out lanes (the enabler for big symmetry
        # groups, and faster even at S=3).  canon="expand": fold the
        # symmetry hash into every fan-out lane (the round-2 formulation,
        # kept as a differential reference).
        assert canon in ("late", "expand")
        self.canon = canon
        self.cfg = cfg
        # MXU-native expand (ops/mxu_expand.py): guards as the coefficient
        # matmul, materialize as gather-free select-matrix products.
        # Default ON; TLA_RAFT_MXU=0 / --no-mxu-expand / use_mxu=False
        # reverts to the legacy per-lane kernels — counts are
        # bit-identical either way (the MXU parity suite diffs the two).
        self.kern: SuccessorKernel = get_kernel(cfg, mxu=use_mxu)
        self.use_mxu = self.kern.use_mxu
        self.fpr = self.kern.fpr
        self.K = self.kern.K
        self.uni_words = self.kern.uni.n_words
        # sparse-frontier width: max message-set size per reachable state
        # (grows ~1/level, saturating near a structural bound — 96 on the
        # reference family; overflow auto-grows it and re-materializes
        # the level, see _materialize_grow).  TLA_RAFT_CAP_M overrides —
        # deep sweeps start with headroom so growth never fires after
        # parent segments are released.
        env_capm = os.environ.get("TLA_RAFT_CAP_M")
        if env_capm is not None and cap_m == 96:
            # env overrides only the DEFAULT: a caller passing an explicit
            # cap_m (tests bounding HBM, the growth suite) keeps it
            cap_m = int(env_capm)
        self.cap_m = min(cap_m, self.kern.uni.M)
        self.id_dtype = jnp.int16 if self.kern.uni.M < (1 << 15) else jnp.int32
        if chunk & (chunk - 1):
            # power-of-two capacities divide evenly into the pow4-padded
            # materialize buffer; arbitrary chunks would mis-slice it
            raise ValueError(f"chunk must be a power of two, got {chunk}")
        self.chunk = chunk
        # a chunk's valid fan-out lanes average ~3.5 per parent on the
        # reference config, so chunk*4 covers the mean and overflow
        # detection grows the budget (with a re-jit) on skewed chunks
        self.cap_x = cap_x or 4 * chunk
        # chunks per visited-filter group, and the per-group post-filter
        # survivor budget (deep levels see <=50% fresh candidates;
        # overflow grows cap_g like cap_x)
        self.G = 16
        self.cap_g = self.G * self.cap_x // 2
        # chunks dispatched between queue-draining scalar fetches
        env_sync = os.environ.get("TLA_RAFT_SYNC_EVERY")
        self.sync_every = max(1, int(env_sync)) if env_sync else 8
        self.progress = progress
        # optional native external-memory visited store (native/fpstore.cpp);
        # when set, the device keeps no visited table at all — the level's
        # deduped candidates are filtered through the host store instead
        self.host_store = host_store
        # device-resident open-addressing visited store (ops/hashstore.py):
        # replaces the level's 3-key lexsort + searchsorted + sorted store
        # merge with one fused O(1) probe-and-insert on the device-store
        # path.  Default ON; TLA_RAFT_HASHSTORE=0 (or --no-hashstore /
        # use_hashstore=False) reverts to the sort-based path.  Moot when
        # an external host store is attached (membership lives host-side).
        if use_hashstore is None:
            use_hashstore = hashstore.enabled_by_env()
        self.use_hashstore = bool(use_hashstore) and host_store is None
        self.hstore = None  # DeviceHashStore, built in run()/resume
        self._hs_pending = None  # a level's updated slab awaiting adoption
        # tiered visited store (store/tiered.py): a device-byte budget
        # for the hot slab; growth past it DEMOTES a whole generation
        # to host RAM / disk instead of growing (or dying), and the
        # level tail probes the demoted runs host-side, dropping their
        # revisits from the fresh set — |visited| becomes
        # storage-bounded, TLC's disk FPSet move.  0 = off (the
        # hot-only engine, bit-identical counts either way).
        if store_bytes is None:
            store_bytes = graft_tiered.store_bytes_from_env()
        self.store_bytes = int(store_bytes)
        self.warm_bytes = warm_bytes  # None = TLA_RAFT_WARM_BYTES
        self.tiered = None  # TieredVisitedStore, built in run()/resume
        # device-resident spill sieve (ops/sieve.py): a blocked bloom
        # over every demoted fingerprint, probed INSIDE the fused
        # megakernel/superstep body — a level with zero sieve hits
        # provably has no spilled revisits and commits in-window, which
        # restores span-N supersteps under spill (the PR 12 stand-down
        # becomes the sieve-off fallback).  Default ON wherever tiering
        # is; TLA_RAFT_SIEVE=0 / sieve=False reverts to span-1.
        # the arm decision is governed at RUNTIME when TLA_RAFT_SIEVE
        # is unset: recent sieve-dirty windows stand the span down
        # (the replay tax never amortizes — ~14% in a round-20 CPU A/B),
        # a probation of per-level progress re-arms it.  =0 / =1 (or an
        # explicit argument) still force either mode unconditionally.
        self.sieve_governor = graft_sieve.SieveGovernor(
            graft_sieve.mode_from_env(sieve)
        )
        self.sieve_enabled = self.sieve_governor.mode != "off"
        self._dev_sieve = None      # device u64[M] copy of the filter
        self._dev_sieve_ver = -1    # host filter version it mirrors
        self._dev_sieve_empty = None  # the 1-word all-miss sentinel
        # device-byte budget for frontier segments (0 = paging off): when
        # one level's parent+child segments would exceed it, sealed child
        # segments demote to host RAM and page back in on demand — the
        # tier that breaks the single-frontier-in-HBM wall at level 29 of
        # the reference sweep (BASELINE.md).  The budget prices LIVE
        # buffers only — MULTI-SEGMENT HEADROOM IS REQUIRED: the expand
        # walk's one-entry parent page cache and the paged-parent fetch
        # buffer are transient extras the estimate does not count, and
        # with the async pipeline on, each in-flight window group pins
        # its group-output fetch buffers and keeps its parent segment
        # referenced ~window groups longer (the estimate below adds the
        # window to the live count, the page caches stay uncounted) —
        # so set the budget several segments below physical HBM
        # (run_sweep.sh's 11 GB of 16 GB leaves ~45 segments' worth)
        self.dev_budget = int(float(os.environ.get("TLA_RAFT_DEV_BYTES", "0")))
        # spilled frontiers (the tier BELOW _HostSeg's host RAM): a
        # FrontierPager built in run() when a spill directory exists;
        # host segments past TLA_RAFT_FSEG_BYTES commit to the warm
        # tier (kind="fseg") and reload on demand.  fseg_rows is the
        # uniform segment size the streamed megakernel path cuts
        # oversized parents into (default SEG_ROWS; override for tests)
        self._fpager = None
        self.fseg_host_bytes = graft_tiered.fseg_bytes_from_env()
        fsr = int(os.environ.get("TLA_RAFT_FSEG_ROWS", "0") or 0)
        self.fseg_rows = max(
            -(-fsr // chunk) * chunk if fsr else SEG_ROWS, chunk
        )
        self._fseg_live = []    # weakrefs of admitted host segments
        self._fseg_retire = []  # consumed segments' tokens (retired at
        #                         the next level top — never mid-level,
        #                         so a degrade-redo still has parents)
        # async intra-level pipeline (engine/pipeline.py): overlap the
        # device expand dispatch, the device->host group fetches and the
        # host-side tail under a bounded in-flight window.  Default ON;
        # TLA_RAFT_PIPELINE=0 (or pipeline=False / a window < 1) reverts
        # to the serial fetch-after-dispatch chain — counts are
        # bit-identical either way (the parity tests diff the two).
        if pipeline is None:
            pipeline = graft_pipeline.enabled_by_env()
        if pipeline_window is None:
            pipeline_window = graft_pipeline.window_from_env()
        self.pipeline_window = int(pipeline_window)
        self.pipeline = bool(pipeline) and self.pipeline_window >= 1
        # forecast-driven AOT prewarm (engine/pipeline.Prewarmer): once
        # the growth model has signal, compile the deep-level program
        # set at the forecast capacity ladder in a background thread
        # while the cheap shallow levels run (the payoff routes through
        # the persistent compilation cache, so supervised relaunches
        # also skip them).  Off by default: local compiles are cheap.
        env_pw = os.environ.get("TLA_RAFT_PREWARM")
        if prewarm is None:
            prewarm = bool(int(env_pw)) if env_pw else False
        self.prewarm = bool(prewarm)
        self._prewarmer = None  # built lazily at first plan submit
        self.paged_out = 0   # sealed child segments demoted to host RAM
        self.paged_disk = 0  # host segments spilled on to the warm tier
        if host_store is not None and chunk > SEG_ROWS:
            # the segment walkers assume chunks never straddle segment
            # boundaries (chunk is pow2 and <= SEG_ROWS => SEG_ROWS % chunk
            # == 0); a larger chunk would make divmod-based slices read
            # past segment bounds (clamped dynamic_slice re-reads wrong
            # parent rows silently)
            raise ValueError(
                f"chunk ({chunk}) must be <= SEG_ROWS ({SEG_ROWS}) "
                "when an external host store is attached"
            )
        self.inv_fns = [
            (n, resolve_invariant_kernel(n)) for n in cfg.invariants
        ]
        # G-chunk span programs replace per-chunk dispatch at real chunk
        # sizes: each per-chunk round costs ~13 host->device dispatches
        # (12 eager field slices + the program), which was most of the
        # warm steady-state cost on the round-5 device.  Tests drive tiny
        # chunks through the
        # per-chunk path (some monkeypatch _expand_chunk); lower this to
        # exercise spans at test scale.
        self.span_min_chunk = 2048
        # predictive capacity pre-sizing (VERDICT r4 #7): forecast-floor
        # the frontier/visited pow2 ladders from the measured growth
        # model (engine/forecast.py) so a deep run compiles each program
        # ONCE instead of once per magnitude.  Floors only ratchet up
        # (shrinking would mint new shapes).  Off by default: local
        # compiles are cheap and tests drive tiny shapes.
        env_ps = os.environ.get("TLA_RAFT_PRESIZE")
        self.presize = bool(int(env_ps)) if env_ps else False
        self._presize_fcap = 0  # frontier-capacity floor (pow2, >= chunk)
        self._presize_vcap = 0  # visited-store trim floor (pow4)
        self._presize_merge = 0  # store merge-input width floor (pow2)
        # orbit pruning (VERDICT r4 #6, ops/fingerprint.py "orbit
        # pruning"): canonical-relabel fingerprints for color-discrete
        # candidates; only the (few) tied states pay the P-fold, on a
        # cap_x/4 compacted budget.  Changes fingerprint VALUES (not
        # counts), so it must stay consistent across a run and its
        # checkpoints — opt-in via TLA_RAFT_ORBIT=1, late canon only.
        env_orb = os.environ.get("TLA_RAFT_ORBIT")
        self.orbit = bool(int(env_orb)) if env_orb else False
        if self.orbit and canon != "late":
            raise ValueError("TLA_RAFT_ORBIT requires canon='late'")
        # whole-level megakernel (engine/megakernel.py): expand ->
        # probe-and-insert -> materialize -> invariant scan fused into
        # ONE jitted program per level, with one ledgered control fetch.
        # Default ON; --megakernel 0 / TLA_RAFT_MEGAKERNEL=0 reverts to
        # the staged program chain (retained as the A/B and audit
        # reference — counts are bit-identical either way).  The fused
        # program needs the functional hash store (its probe-and-insert
        # IS the dedup stage) and the single-program orbit split is
        # structurally incompatible; the host-store path gets the
        # partial fusion (expand span + group dedup in one program —
        # everything up to the host-store probe) under the same flag.
        if megakernel is None:
            megakernel = graft_megakernel.enabled_by_env()
        self._mega_flag = bool(megakernel) and not self.orbit
        self.megakernel = (
            self._mega_flag and self.use_hashstore and host_store is None
        )
        self._mega_donate = (
            self.megakernel and graft_megakernel.donation_supported()
        )
        # program_hits/program_builds: this engine's lookups in the
        # shared program caches (megakernel/superstep ``program_for``);
        # table_bytes: the kernel tables those programs take as operand
        # probe_lanes/lane_budget: candidate lanes the fused programs'
        # probe-and-insert ran over, and the lanes their buffers hold
        self._mega_stats = dict(
            levels=0, redo_out=0, redo_x=0, redo_slab=0, redo_m=0,
            program_hits=0, program_builds=0, table_bytes=0,
            probe_lanes=0, lane_budget=0,
        )
        # multi-level resident supersteps (engine/superstep.py): run up
        # to N fused levels inside ONE device program + ONE ledgered
        # ring fetch wherever the per-level megakernel is eligible.
        # Default span DEFAULT_SPAN; --superstep 1 / TLA_RAFT_SUPERSTEP
        # reverts to the per-level fused path.  The --audit legacy
        # re-expansion needs every level's parent frontier on device,
        # which the resident loop consumes — audit runs stay per-level.
        if superstep is None:
            superstep = graft_superstep.span_from_env()
        self.superstep_span = (
            max(1, int(superstep)) if self.megakernel and not audit
            else 1
        )
        self._ss_stats = dict(
            supersteps=0, levels=0, stops=0, ring_stops=0,
            program_hits=0, program_builds=0, table_bytes=0,
            probe_lanes=0, lane_budget=0,
        )
        self._ss_sig = None  # declared superstep static-shape signature
        self._degraded_visited = None  # sorted store handoff on degrade
        # why the hash store degraded (None = never): reported in the
        # --json summary so a grow failure (device OOM) is never silent
        self.hashstore_degraded = None
        # semantic run fingerprint for the checkpoint manifests: spec
        # constants only — NOT tunables like chunk (a resume may retune
        # those freely), NOT the store tier (the three tiers share one
        # delta-log format), and NOT the fingerprint definition: orbit
        # mixing is guarded one layer down by the per-record fp_def
        # check, whose specific "fingerprint-definition mismatch" error
        # tells the operator which knob to flip.
        self._run_fp = resilience.run_config_fingerprint(cfg, log="delta")
        # sampled recomputation audit (resilience/integrity.py): every
        # level, ``audit`` deterministic new-frontier rows re-expand
        # through the retained *_legacy kernels and cross-check guards/
        # fingerprints against the (MXU) hot path AND the frontier as
        # materialized on device; a mismatch quarantines the level and
        # rewinds to the last committed checkpoint, fail-stopping after
        # ``audit_retries`` reproducible strikes.
        self.audit = max(0, int(audit))
        self.audit_retries = max(1, int(audit_retries))
        self.audit_stats = dict(
            levels=0, sampled=0, mismatches=0, rewinds=0
        )
        self._audit_strikes = 0
        self._audit_strike_depth = None  # level the strikes belong to
        self._audit_keys: set = set()  # declared audit program shapes
        # per-level hang watchdog (resilience/elastic.py), armed by the
        # level loop; None = off
        self.watchdog = watchdog
        # every program of this checker takes the kernel's device tables
        # as its first operand (ops/tables.py); orbit checkers add the
        # canonical-relabel tables their fingerprints read
        self.kernel_tables = (
            self.kern.orbit_device_tables if self.orbit
            else self.kern.device_tables
        )
        self._mat_slice = self._program(self._mat_slice_impl)
        self._mat_slice_seg = self._program(self._mat_slice_seg_impl)
        self._inv_scan = self._program(self._inv_scan_impl)
        self._jit_expand_programs()

    def _program(self, method, **jit_kwargs):
        """``method`` of this checker as a program over its kernel tables."""
        return _tables.method_program(method, self.kernel_tables, **jit_kwargs)

    def _jit_expand_programs(self):
        """(Re-)jit the chunk expand programs (cap_x is baked in).

        Orbit runs the chunk as TWO programs — guards/compact/materialize,
        then fingerprints — because the fused variant (canonical-relabel
        machinery + the exact-fold fallback on top of the expand) made
        the S=7 compile too large for the round-5 device's compile window
        (the s7 campaign step died mid-compile).  Split, each program
        is no bigger than the non-orbit fused one, and at S=7 rates the
        extra dispatch is noise.  Spans stay off under orbit for the same
        reason (the scan multiplies program size by G).
        """
        self._expand_span = self._program(self._expand_span_impl)
        if self.orbit:
            self._expand_chunk_core = self._program(
                self._expand_chunk_core_impl
            )
            self._orbit_fps = self._program(self._orbit_fps_impl)
            # two programs, called in turn by the owner (weakly held)
            ref = weakref.WeakMethod(self._expand_chunk_split)
            self._expand_chunk = lambda *a: ref()(*a)
        else:
            self._expand_chunk = self._program(self._expand_chunk_impl)
        # the fused whole-level program (and the host path's fused
        # span+dedup slice) close over cap_x — rebuild with it
        if getattr(self, "megakernel", False):
            self._mega_prog = graft_megakernel.level_program_for(
                self.level_spec(), self._mega_donate, self._mega_stats
            )
        if getattr(self, "_mega_flag", False) and not self.orbit:
            self._expand_group_fused = self._program(
                self._expand_group_fused_impl
            )
            # grouped ultra-deep regime: span + visited pre-filter in
            # one program per group (cap_g static so its growth
            # retraces like the staged _group_filter_hash)
            self._expand_group_gfused = self._program(
                self._expand_group_gfused_impl,
                static_argnames=("cap_g",),
            )

    def level_spec(self) -> LevelSpec:
        """What the fused level programs read, frozen at the current
        budgets: their cache key and the only state they close over."""
        return LevelSpec(
            kern=self.kern, chunk=self.chunk, cap_x=self.cap_x,
            cap_m=self.cap_m, canon=self.canon, orbit=self.orbit,
            cfg=self.cfg, K=self.K, fpr=self.fpr,
            uni_words=self.uni_words, id_dtype=self.id_dtype,
            inv_fns=tuple(self.inv_fns),
        )

    def _expand_chunk_core_impl(self, part_f: Frontier, start, n_f):
        """Jit target for the orbit split's first program."""
        part = self._inflate(part_f)
        return self._expand_chunk_core_late(part, start, n_f)

    def _orbit_fps_impl(self, children, lane, cp_raw):
        """Jit target for the orbit split's second program."""
        fv, ff, nd_ovf = self._orbit_chunk_fps(children, lane)
        cv = jnp.where(lane, fv.astype(U64), SENT)
        cf = jnp.where(lane, ff.astype(U64), SENT)
        cp = jnp.where(lane, cp_raw, -1)
        return cv, cf, cp, nd_ovf

    def _expand_chunk_split(self, part_f: Frontier, start, n_f):
        """Orbit chunk expand as two dispatches (children stay on device)."""
        (children, lane, cp_raw, mult_slots, abort_at,
         overflow) = self._expand_chunk_core(part_f, start, n_f)
        cv, cf, cp, nd_ovf = self._orbit_fps(children, lane, cp_raw)
        return cv, cf, cp, mult_slots, abort_at, overflow | nd_ovf

    def _orbit_chunk_fps(self, children, lane):
        """Orbit-pruned fingerprints for one chunk's compacted candidates.

        Color-discrete rows (the vast majority on non-trivial levels) get
        the canonical-relabel hash; tied rows are compacted into a
        cap_x/4 sub-budget and run the exact min-over-P fold there.  If
        more than cap_x/4 rows are tied (early symmetric levels) the
        chunk reports overflow — the engine's existing redo then grows
        cap_x by half-steps (_cap_steps, ~1.5x), and with it this
        sub-budget, until the level fits.
        Returns (fp_view, fp_full, overflow)."""
        fv, ff, disc = self.fpr.state_fingerprints_orbit(children)
        need = lane & ~disc
        cap_nd = max(256, self.cap_x // 4)
        comp = jnp.argsort(~need, stable=True)[:cap_nd]
        sub = jax.tree.map(lambda x: x[comp], children)
        sv, sf, _ = self.fpr.state_fingerprints(sub)
        take = need[comp]
        fv = fv.at[comp].set(jnp.where(take, sv, fv[comp]))
        ff = ff.at[comp].set(jnp.where(take, sf, ff[comp]))
        return fv, ff, need.sum() > cap_nd

    def _fp_states(self, st):
        """(fp_view, fp_full) for a small batch, honoring the orbit flag.

        Root/trace/aux paths: computes both routes and selects — these
        batches are tiny, and the store must hold ONE consistent
        fingerprint definition per run."""
        if not self.orbit:
            fv, ff, _ = self.fpr.state_fingerprints(st)
            return fv, ff
        ov, of_, disc = self.fpr.state_fingerprints_orbit(st)
        sv, sf, _ = self.fpr.state_fingerprints(st)
        return jnp.where(disc, ov, sv), jnp.where(disc, of_, sf)

    def _expand_span_impl(self, frontier, slice_base, global_base, n_f):
        """G chunks in ONE program via lax.scan.

        The per-chunk host loop costs ~13 dispatches per chunk (12 eager
        per-field slices + the expand program); that dispatch latency —
        not compute — dominated warm levels on the round-5 device.
        Scanning G chunks inside one jitted program cuts
        the level's dispatch count by ~G*13.

        ``frontier`` is the whole frontier (or one uniform segment on the
        external-store path); ``slice_base`` is the row offset of the
        span's first chunk within it, ``global_base`` the same position
        in global frontier coordinates (they differ on segment operands —
        payloads and in-range masks are global).  Returns stacked
        [G, cap_x] candidate arrays + span-reduced stats.
        """

        def body(carry, i):
            mult_acc, ab_min, ovf_any = carry
            part_f = jax.tree.map(
                lambda x: jax.lax.dynamic_slice_in_dim(
                    x, slice_base + i * self.chunk, self.chunk
                ),
                frontier,
            )
            cv, cf, cp, mult, ab, ovf = self._expand_chunk_impl(
                part_f, global_base + i * self.chunk, n_f
            )
            return (
                (mult_acc + mult, jnp.minimum(ab_min, ab), ovf_any | ovf),
                (cv, cf, cp),
            )

        init = (jnp.zeros((self.K,), I64), BIG, jnp.zeros((), bool))
        (mult, ab, ovf), (cvs, cfs, cps) = jax.lax.scan(
            body, init, jnp.arange(self.G, dtype=I64)
        )
        return cvs, cfs, cps, mult, ab, ovf

    def _expand_group_fused_impl(self, seg, slice_base, global_base, n_f):
        """Span + intra-group dedup in ONE program — the host-store
        path's megakernel slice (everything up to the host-store probe
        fuses; the probe itself lives host-side by design).  Identical
        outputs to ``_expand_span`` followed by ``_group_unique``: the
        dedup body is the SAME ``_group_unique_impl``."""
        cvs, cfs, cps, mult, ab, ovf = self._expand_span_impl(
            seg, slice_base, global_base, n_f
        )
        n_u, gv, gf, gp = _group_unique_impl(
            cvs.reshape(-1), cfs.reshape(-1), cps.reshape(-1)
        )
        return n_u, gv, gf, gp, mult, ab, ovf

    def _expand_group_gfused_impl(self, seg, slice_base, global_base,
                                  n_f, hslab, cap_g: int):
        """The grouped ultra-deep regime's per-group chain — G-chunk
        span expand + the visited PRE-FILTER (hash probe + compact) —
        in ONE program per group (the staged chain was two).  The
        filter body is the SAME probe + ``_filter_compact`` tail as
        ``_group_filter_hash``, so outputs are bit-identical; the
        pre-filter stays in place because it is what bounds the
        candidate working set to O(groups * cap_g) in this regime
        (the whole-level fusion deliberately does not apply here)."""
        cvs, cfs, cps, mult, ab, ovf = self._expand_span_impl(
            seg, slice_base, global_base, n_f
        )
        hit = hashstore.probe_impl(hslab, cvs.reshape(-1))
        gv, gf, gp, ovf_g = _filter_compact(
            hit, cvs.reshape(-1), cfs.reshape(-1), cps.reshape(-1),
            cap_g,
        )
        return gv, gf, gp, mult, ab, ovf, ovf_g

    # -- whole-level megakernel (engine/megakernel.py) ---------------------

    def _mega_level_ok(self, frontier, n_f) -> bool:
        """Is this level eligible for the fused whole-level program?

        Grouped ultra-deep levels keep the staged path: there the group
        filter's visited pre-probe bounds the candidate working set to
        O(groups * cap_g) before the level-wide buffers exist, which is
        the memory regime the grouping threshold was tuned for."""
        if not self.megakernel or not self.use_hashstore:
            return False
        if isinstance(frontier, list) or self.host_store is not None:
            return False
        n_chunks = -(-max(n_f, 1) // self.chunk)
        return n_chunks <= 16 * self.G

    def _mega_cap_out(self, n_f, level_sizes, max_depth, n_lanes,
                      floor):
        """The fused program's static new-frontier capacity: forecast
        when there is signal (the same 1.25 margin the prewarm ladder
        bakes in, so the AOT-compiled rung is the one requested), the
        early fan-out bound (growth ratios stay under 4 on this family)
        otherwise, quantized through the one frontier-capacity ladder.
        ``floor`` carries an exact redo bound (n_new from the control
        fetch); n_new can never exceed the candidate lane budget, so
        clamping at ``n_lanes`` makes the ladder's top rung overflow-
        free."""
        from .forecast import MIN_LEVELS, forecast_new_states

        est = 0
        if len(level_sizes) > MIN_LEVELS:
            fut = forecast_new_states(level_sizes, max_depth)
            if fut:
                # the 2x floor covers forecast undershoot through the
                # whole sub-2x-growth regime: dead output lanes cost
                # nothing (the materialize scan skips whole-dead
                # slices), a redo costs a full level
                est = max(
                    int(fut[0] * graft_forecast.cap_margin()) + 1,
                    2 * max(n_f, 1),
                )
        if not est:
            est = 4 * max(n_f, 1)
        est = max(est, floor)
        # the quantizer keeps every capacity a chunk multiple >= chunk;
        # clamping the ESTIMATE (not the result) at the lane budget
        # keeps the ladder's top rung overflow-free without ever
        # violating that invariant (the kernel pads when cap_out
        # exceeds the lane count — tiny cap_x configs).  The 4*chunk
        # floor mirrors the staged payload width (max(_pow2(n_new),
        # 4*chunk)): levels below it share ONE program shape instead of
        # stepping through every tiny rung — compile count, not memory,
        # is the cost down there (dead slices are cond-skipped)
        return max(
            self._frontier_cap(min(est, max(n_lanes, 1))),
            4 * self.chunk,
        )

    def _expand_level_mega(self, frontier, n_f, max_depth, level_sizes):
        """One fused device program + ONE ledgered fetch for a whole
        level.  Every overflow class re-enters the engine's existing
        grow-and-redo machinery against the ORIGINAL slab (the pending
        slab is discarded; the kernels are functional).  Returns None
        when the hash store degraded mid-level (the caller adopts the
        rebuilt sorted store from ``_degraded_visited`` and redoes the
        level staged), else the level-result dict; the pending slab
        lands in ``_hs_pending`` for the common adopt path."""
        mk = graft_megakernel
        n_f_dev = jnp.asarray(n_f, I64)
        out_floor = 0
        while True:
            cap_f = frontier.voted_for.shape[0]
            n_lanes = (cap_f // self.chunk) * self.cap_x
            cap_out = self._mega_cap_out(
                n_f, level_sizes, max_depth, n_lanes, out_floor
            )
            # re-resolve through the shared cache EVERY attempt: a grown
            # budget is a new spec and so a new program (a dict hit
            # costs nothing)
            self._mega_prog = graft_megakernel.level_program_for(
                self.level_spec(), self._mega_donate, self._mega_stats
            )
            # device-cost observatory: harvest the fused program's XLA
            # cost/memory ledger once per shape (compile-time only —
            # the lower+compile lands in the cache this call then hits)
            sieve_dev = self._sieve_operand()
            graft_devprof.profile_program(
                "megakernel.level", self._mega_prog,
                frontier, self.hstore.slab, n_f_dev, sieve_dev,
                statics=dict(cap_out=cap_out),
            )
            outs = self._mega_prog(
                frontier, self.hstore.slab, n_f_dev, sieve_dev,
                cap_out=cap_out,
            )
            if self._mega_donate:
                (new_frontier, slab2, ctrl_d, mult_d, fps_d, pidx_d,
                 slot_d, frontier) = outs
            else:
                (new_frontier, slab2, ctrl_d, mult_d, fps_d, pidx_d,
                 slot_d) = outs
            graft_sanitize.note_dispatch("megakernel.level")
            self._san_lanes = n_lanes
            # THE level fetch: control vector + trace/delta arrays in
            # one ledgered get, routed through the pipeline's deferred
            # path (transfer ledger, pipeline.window fault site and the
            # watchdog heartbeat all still see it)
            tail = graft_pipeline.DeferredFetch(
                self.pipeline, (ctrl_d, mult_d, fps_d, pidx_d, slot_d)
            )
            ctrl, mult_np, fps_np, pidx_np, slot_np = tail.get()
            ctrl = np.asarray(ctrl, np.int64)
            n_new = int(ctrl[mk.CTRL_N_NEW])
            self._mega_stats["probe_lanes"] += int(ctrl[mk.CTRL_PROBE_LANES])
            self._mega_stats["lane_budget"] += n_lanes
            if ctrl[mk.CTRL_OVF_SLAB]:
                self._hs_pending = None
                try:
                    how = self._slab_grow_or_demote(
                        len(level_sizes), expected=max(n_new, n_f)
                    )
                except Exception as e:  # graftlint: waive[GL003] — any
                    # grow failure (device OOM, injected fault) degrades
                    # to the sort path; the level redoes staged.  The
                    # degrade result MUST carry the pass-through parent:
                    # under donation the caller's frontier buffers were
                    # consumed by the dispatch above, and the staged
                    # redo would otherwise expand a deleted array
                    self._degraded_visited = self._degrade_hashstore(e)
                    return dict(degraded=True, parent=frontier)
                self._mega_stats["redo_slab"] += 1
                if how == "demoted":
                    # the tier form of the slab redo: demote, then redo
                    # against the drained slab (store/tiered.py)
                    self.tiered.stats["tier_redos"] += 1
                    graft_obs.redo("slab_tier")
                else:
                    graft_obs.grow("slab", self.hstore.cap)
                    graft_obs.redo("slab")
                continue
            if ctrl[mk.CTRL_OVF_X]:
                # a chunk overflowed its compaction budget: the same
                # half-step growth + re-jit as the staged redo
                self.cap_x = _cap_steps(self.cap_x + 1)
                self.cap_g = max(self.cap_g, self.G * self.cap_x // 2)
                self._jit_expand_programs()
                self._mega_stats["redo_x"] += 1
                graft_obs.grow("cap_x", self.cap_x)
                graft_obs.redo("cap_x")
                continue
            if n_new > cap_out:
                # exact capacity is now known — one redo lands it
                out_floor = n_new
                self._mega_stats["redo_out"] += 1
                graft_obs.grow("cap_out", n_new)
                graft_obs.redo("cap_out")
                continue
            if int(ctrl[mk.CTRL_ABORT]) < n_f:
                break  # violation: counts are final, nothing is adopted
            if ctrl[mk.CTRL_OVF_M] and n_new:
                if self.cap_m >= self.kern.uni.M:
                    raise RuntimeError(
                        "message-set width exceeds the whole universe — "
                        "corrupt payloads?"
                    )
                self.cap_m = min(self.cap_m + 32, self.kern.uni.M)
                print(
                    f"[engine] cap_m overflow: growing to {self.cap_m} "
                    "and redoing the fused level", file=sys.stderr,
                )
                frontier = self._widen_msg_ids(frontier)
                # re-resolve the fused program under the grown cap_m:
                # the widened shapes drive the retrace, and the shared
                # program cache keys on the spec, which holds cap_m
                self._jit_expand_programs()
                self._mega_stats["redo_m"] += 1
                graft_obs.grow("cap_m", self.cap_m)
                graft_obs.redo("cap_m")
                continue
            break
        self._hs_pending = slab2
        self._mega_stats["levels"] += 1
        return dict(
            n_new=n_new,
            abort_at=int(ctrl[mk.CTRL_ABORT]),
            bad_idx=int(ctrl[mk.CTRL_BAD]),
            slab_live=int(ctrl[mk.CTRL_SLAB_LIVE]),
            tier_hits=int(ctrl[mk.CTRL_TIER_HITS]),
            level_mult=np.asarray(mult_np, np.int64),
            new_frontier=new_frontier,
            parent=frontier,
            fps=np.asarray(fps_np, np.uint64)[:n_new],
            pidx=np.asarray(pidx_np)[:n_new].astype(np.int64),
            slot=np.asarray(slot_np)[:n_new].astype(np.int64),
            cap_out=cap_out,
        )

    def _mega_segs_ok(self, frontier, n_f) -> bool:
        """Is this level eligible for the SEGMENT-STREAMED fused path?

        The single-frontier fused program needs parent + children
        resident at once; a level past the paging budget streams the
        parent through the same program one uniform segment at a time
        instead (``_expand_level_mega_segs``), so a frontier that
        outgrows HBM still runs fused.  Eligible when the frontier is
        already a segment list, or a single device frontier whose
        level working set (parent + like-sized children) would bust
        TLA_RAFT_DEV_BYTES.  The audit path re-expands sampled rows
        from live parents and keeps the unsegmented routes."""
        if not self.megakernel or not self.use_hashstore:
            return False
        if self.host_store is not None or self.audit:
            return False
        # per-segment dispatch bound: same 16*G grouping threshold the
        # whole-level gate applies, against ONE segment's chunk count
        if -(-self.fseg_rows // self.chunk) > 16 * self.G:
            return False
        if isinstance(frontier, list):
            return True
        if not self.dev_budget or n_f <= self.fseg_rows:
            return False
        return 2 * self._tree_bytes(frontier) > self.dev_budget

    def _cut_frontier(self, frontier, n_f: int, depth: int) -> list:
        """Cut one device frontier into uniform host segments of
        ``fseg_rows`` (the streamed path's input form).  One D2H fetch;
        the device copy is released so the level's HBM peak is one
        segment + its children, not the whole parent."""
        L = self.fseg_rows
        host = {
            f: np.asarray(jax.device_get(getattr(frontier, f)))
            for f in Frontier._fields
        }
        del frontier
        n_seg = -(-max(n_f, 1) // L)
        segs = []
        for j in range(n_seg):
            flds = {}
            for f, v in host.items():
                part = v[j * L:(j + 1) * L]
                if part.shape[0] < L:
                    part = np.concatenate([
                        part,
                        np.zeros((L - part.shape[0],) + part.shape[1:],
                                 part.dtype),
                    ])
                flds[f] = part
            hs = _HostSeg(flds)
            self._fseg_admit(hs, depth)
            segs.append(hs)
        return segs

    def _expand_level_mega_segs(self, segs, n_f, max_depth, level_sizes,
                                depth):
        """Spilled-frontier streaming: one fused level, one PARENT
        SEGMENT at a time through ``_expand_level_mega``, the hash slab
        adopted between segments so later segments dedup against
        earlier segments' children on device.  The generation probe
        (sieve fast path + exact tier filter) runs PER SEGMENT here —
        the combined result reports ``tier_done`` so the level tail
        does not re-probe.  Children seal host-side (trimmed to live
        rows) and collapse back to one device frontier when the next
        level fits the budget, else re-segment through the pager.
        Counts are bit-identical to the unsegmented path: same kernels,
        same slab, same probes — only the dispatch granularity differs.

        Returns an ``_expand_level_mega``-shaped dict, or the degraded
        marker (with this level's committed children rolled back OUT of
        the degraded sorted store, so the staged redo re-finds them)."""
        tier = self._tier_active()
        L = self.fseg_rows
        # hold every parent on host for the degrade-redo (a device seg
        # would be consumed by the donated dispatch below)
        for j, s in enumerate(segs):
            if not isinstance(s, _HostSeg):
                segs[j] = self._seg_to_host(s, depth)
        fps_parts, pidx_parts, slot_parts = [], [], []
        kept_children = []  # per-seg host field dicts, live rows only
        mult_total = None
        total_new = 0
        n_done = 0
        slab_live = 0
        cap_out_last = 0
        abort_global = None
        bad_global = -1
        self._mega_stats["seg_levels"] = (
            self._mega_stats.get("seg_levels", 0) + 1
        )
        for j, seg in enumerate(segs):
            n_seg = min(seg.rows, n_f - n_done)
            if n_seg <= 0:
                break
            mres = self._expand_level_mega(
                self._seg_to_dev(seg), n_seg, max_depth, level_sizes
            )
            if mres.get("degraded"):
                if fps_parts:
                    # un-commit the streamed prefix's children from the
                    # degraded sorted store: the staged redo expands the
                    # WHOLE level and must re-find them as new (the
                    # re-heated generation members stay — they fold in
                    # through the generations and were visited before)
                    done = np.concatenate(fps_parts)
                    vb = np.asarray(jax.device_get(self._degraded_visited))
                    vb = np.setdiff1d(vb[vb != SENT], done)
                    pad = _cap4(len(vb) + 1) - len(vb)
                    self._degraded_visited = jnp.concatenate([
                        jnp.asarray(vb), jnp.full((pad,), SENT, U64),
                    ])
                return dict(degraded=True, parent=segs)
            self._mega_stats["seg_dispatches"] = (
                self._mega_stats.get("seg_dispatches", 0) + 1
            )
            # adopt NOW (kernel-fresh count): the next segment's probe
            # must see this segment's children as visited
            self.hstore.adopt(self._hs_pending, mres["n_new"])
            self._hs_pending = None
            slab_live = mres["slab_live"]
            cap_out_last = mres["cap_out"]
            mult_total = (
                mres["level_mult"] if mult_total is None
                else mult_total + mres["level_mult"]
            )
            n_new_seg = mres["n_new"]
            fps = np.asarray(mres["fps"], np.uint64)
            pidx = mres["pidx"] + n_done
            slot = mres["slot"]
            bad_seg = mres["bad_idx"]
            nf_new = mres["new_frontier"]
            if mres["abort_at"] < n_seg:
                # split-brain abort: counts are final, streaming stops
                # (same early-exit as the unsegmented path's break)
                abort_global = n_done + mres["abort_at"]
                break
            # per-segment tiered tail: sieve fast path, else the exact
            # generation probe + row compaction (store/tiered.py)
            if tier and n_new_seg:
                if (self._sieve_ready()
                        and mres.get("tier_hits", -1) == 0):
                    self.tiered.stats["sieve_skips"] = (
                        self.tiered.stats.get("sieve_skips", 0) + 1
                    )
                else:
                    n_keep, keep, nf_new = self._tier_filter_level(
                        depth, n_new_seg, fps, nf_new,
                        nf_new.voted_for.shape[0],
                    )
                    if keep is not None:
                        fps = fps[:n_new_seg][keep]
                        pidx = pidx[keep]
                        slot = slot[keep]
                        if bad_seg >= 0:
                            assert keep[bad_seg], (
                                "invariant violation attributed to an "
                                "already-visited (generation) row"
                            )
                            bad_seg = int(
                                np.count_nonzero(keep[:bad_seg])
                            )
                    n_new_seg = n_keep
            if bad_seg >= 0 and bad_global < 0:
                bad_global = total_new + bad_seg
            if n_new_seg:
                kept_children.append({
                    f: np.asarray(
                        jax.device_get(getattr(nf_new, f))
                    )[:n_new_seg]
                    for f in Frontier._fields
                })
                fps_parts.append(fps[:n_new_seg])
                pidx_parts.append(pidx[:n_new_seg])
                slot_parts.append(slot[:n_new_seg])
                total_new += n_new_seg
            del nf_new
            n_done += n_seg
        # queue the spilled parents' warm-tier artifacts for retirement
        # at the next level top (never here: a degrade in a LATER call
        # cannot reach back past the committed level, but this one's
        # staged redo still can until the commit lands)
        self._fseg_retire.extend(
            s.token for s in segs
            if isinstance(s, _HostSeg) and s.token is not None
        )
        empty_u64 = np.empty(0, np.uint64)
        empty_i64 = np.empty(0, np.int64)
        out = dict(
            n_new=total_new,
            abort_at=n_f if abort_global is None else abort_global,
            bad_idx=bad_global,
            slab_live=slab_live,
            tier_hits=0,
            tier_done=True,
            level_mult=(
                mult_total if mult_total is not None
                else np.zeros(self.K, np.int64)
            ),
            parent=segs,
            fps=(
                np.concatenate(fps_parts) if fps_parts else empty_u64
            ),
            pidx=(
                np.concatenate(pidx_parts).astype(np.int64)
                if pidx_parts else empty_i64
            ),
            slot=(
                np.concatenate(slot_parts).astype(np.int64)
                if slot_parts else empty_i64
            ),
            cap_out=cap_out_last,
        )
        if abort_global is not None or total_new == 0:
            out["new_frontier"] = None  # never read on abort/fixpoint
            return out
        # seal the combined child frontier: back to ONE device frontier
        # while the next level's working set fits, else stay segmented
        # (uniform L-row host segments, pager-admitted past the budget)
        row_b = sum(
            v.dtype.itemsize * int(np.prod(v.shape[1:], dtype=np.int64))
            for v in kept_children[0].values()
        )
        cap_f = self._frontier_cap(total_new)
        collapse = (
            total_new <= L
            or not self.dev_budget
            or 2 * cap_f * row_b <= self.dev_budget
        )
        cols = {
            f: np.concatenate([c[f] for c in kept_children])
            for f in Frontier._fields
        }
        kept_children = None
        if collapse:
            pad = cap_f - total_new
            out["new_frontier"] = Frontier(**{
                f: jnp.asarray(np.concatenate([
                    v, np.zeros((pad,) + v.shape[1:], v.dtype),
                ]))
                for f, v in cols.items()
            })
            return out
        n_seg_d = -(-total_new // L)
        child_segs = []
        for j in range(n_seg_d):
            flds = {}
            for f, v in cols.items():
                part = v[j * L:(j + 1) * L]
                if part.shape[0] < L:
                    part = np.concatenate([
                        part,
                        np.zeros((L - part.shape[0],) + part.shape[1:],
                                 part.dtype),
                    ])
                flds[f] = part
            hs = _HostSeg(flds)
            self._fseg_admit(hs, depth + 1)
            child_segs.append(hs)
        out["new_frontier"] = child_segs
        return out

    # -- multi-level resident supersteps (engine/superstep.py) -------------

    def _superstep_span_at(self, max_depth, depth) -> int:
        """The span this superstep may cover: the configured span,
        clamped so the resident loop never expands past --max-depth
        (the per-level loop breaks BEFORE expanding at the cap).
        Under spill the full span holds only while the SIEVE covers the
        demoted generations: a level with zero in-program sieve hits
        provably has no generation revisits and commits in-window, and
        a level WITH hits stops on FLAG_TIER for the exact host
        correction (ops/sieve.py).  With the sieve off the PR 12
        stand-down applies — span 1, because a resident window cannot
        host-correct a mid-span level's generation revisits (every
        level after it would have expanded stale rows)."""
        span = self.superstep_span
        if self._tier_active() and not self._sieve_ready():
            return 1
        if max_depth is not None:
            span = min(span, max_depth - depth)
        return span

    def _superstep_shapes(self, fut, span, n_rows, cap_cur):
        """One superstep window's static ``(cap_f, ring)`` — the ONE
        copy of the shape math shared by ``_run_superstep`` and the
        prewarm walk, so the AOT ``("sstep", ...)`` keys always match
        the shapes the runtime requests (a desynchronized margin would
        compile dead programs and pay every window's XLA compile
        synchronously)."""
        if fut:
            # same margins as the per-level _mega_cap_out, applied to
            # the span max: one static seat for every level in flight
            est = max(
                int(max(fut) * graft_forecast.cap_margin()) + 1,
                2 * max(n_rows, 1),
            )
        else:
            est = 4 * max(n_rows, 1)  # early fan-out bound
        cap_f = max(
            self._frontier_cap(est), 4 * self.chunk, cap_cur,
        )
        # resident levels must stay inside the grouping threshold: a
        # frontier the per-level loop would route grouped-staged
        # (n_chunks > 16*G, _mega_level_ok) must never be expanded
        # resident mid-span — cap the seats so such a level overflows
        # FLAG_OVF_OUT (a clean stop) and re-enters the per-level
        # routing, which sends it grouped-staged like the level loop
        cap_f = min(cap_f, max(16 * self.G * self.chunk,
                               4 * self.chunk, cap_cur))
        ring = graft_superstep.ring_capacity(fut, span, cap_f, _pow2)
        return cap_f, ring

    def _run_superstep(self, frontier, n_f, max_depth, depth,
                       level_sizes):
        """ONE device dispatch + ONE ledgered ring fetch for up to N
        consecutive levels.  Returns the committed per-level records
        (the same delta/trace record shape the per-level megakernel
        fetch produces), the carried frontier (the stopped level's
        parent on a STOP), the pending slab and the stop reason; the
        caller adopts the prefix and routes any stopped level through
        the per-level machinery."""
        from .forecast import forecast_new_states

        with graft_obs.span("raft.superstep.prepare"):
            ss = graft_superstep
            # span: the EFFECTIVE level bound this window may cover (the
            # --max-depth clamp) — a traced operand of the program, so one
            # compiled span-N driver serves every remainder
            span = self._superstep_span_at(max_depth, depth)
            cap_cur = frontier.voted_for.shape[0]
            fut = forecast_new_states(level_sizes, max_depth)[:span]
            # shape statics always use the CONFIGURED span (the clamped
            # span is only the traced lvl_cap operand below), so a
            # --max-depth remainder window reuses the span-N program AND
            # the prewarmed ("sstep", ...) ring/cap_f rungs instead of
            # minting a one-off smaller-ring compile
            cap_f, ring = self._superstep_shapes(
                fut, self.superstep_span, n_f, cap_cur
            )
            # slab headroom for the WHOLE span: a superstep inserts up to
            # the sum of its levels' new states before the host can grow
            # the store, so the between-superstep reserve must budget the
            # span's forecast inserts (margined like the ring rungs), not
            # one level's — otherwise every growing span stops on a probe-
            # window fill and replays per-level, eroding the amortization.
            # reserve() grows to FIT (a single doubling can be short of a
            # 4-level span on a >2x-growth run).
            if fut:
                m = graft_forecast.cap_margin()
                ins_bound = sum(
                    min(int(f * m) + 1, cap_f) for f in fut
                )
            else:
                ins_bound = 2 * max(n_f, 1)
            try:
                # budget-clamped under the tiered store: a span whose
                # forecast inserts exceed the device budget stops on
                # FLAG_OVF_SLAB and the stop handler demotes
                self._tier_reserve(
                    self.hstore.count + max(ins_bound, 2 * max(n_f, 1))
                )
            except Exception as e:  # graftlint: waive[GL003] — grow
                # failure degrades to the sort path like every other
                # grow site; the caller redoes the level staged
                self._degraded_visited = self._degrade_hashstore(e)
                return dict(degraded=True, frontier=frontier)
            prog = ss.superstep_program_for(
                self.level_spec(), self.superstep_span, self._mega_donate,
                self._ss_stats,
            )
            # cap_cur (the input frontier's capacity) is part of the traced
            # shape via the in-program padding — a changed input rung is a
            # declared shape event like every other capacity step
            sieve_dev = self._sieve_operand()
            skey = (cap_cur, cap_f, ring, self.hstore.cap,
                    self.cap_x, self.cap_m, int(sieve_dev.shape[0]))
            if graft_sanitize.tracking() and skey != self._ss_sig:
                graft_sanitize.note_shape_event(f"superstep shapes {skey}")
                self._ss_sig = skey
            graft_sanitize.superstep_begin()
            # live-HBM gauge: the trace-spool ring (fps u64 + pidx u32 +
            # slot u16/u32 per entry) is the superstep's one extra
            # long-lived buffer
            graft_obs.buffer(
                "ring", ring * (12 + (2 if self.K <= 0xFFFF else 4))
            )
            n_f_dev = jnp.asarray(n_f, I64)
            span_dev = jnp.asarray(span, I64)
            # device-cost observatory (see the megakernel site)
            graft_devprof.profile_program(
                "superstep.levels", prog,
                frontier, self.hstore.slab, n_f_dev, span_dev, sieve_dev,
                statics=dict(cap_f=cap_f, ring=ring),
            )
        with graft_obs.span("raft.superstep.dispatch"):
            outs = prog(
                frontier, self.hstore.slab, n_f_dev, span_dev, sieve_dev,
                cap_f=cap_f, ring=ring,
            )
        (fr_out, slab_out, ctrl_d, mn_d, mm_d, rf_d, rp_d,
         rs_d) = outs
        graft_sanitize.note_dispatch("superstep.levels")
        lanes = (cap_f // self.chunk) * self.cap_x
        self._san_lanes = lanes
        # THE superstep fetch: control vector + per-level meta + the
        # trace/delta ring in one ledgered get through the pipeline's
        # deferred path (transfer ledger, pipeline.window fault site
        # and the watchdog heartbeat all still see it)
        tail = graft_pipeline.DeferredFetch(
            self.pipeline, (ctrl_d, mn_d, mm_d, rf_d, rp_d, rs_d)
        )
        ctrl, mn, mm, rf, rp, rs = tail.get()
        with graft_obs.span("raft.superstep.unpack"):
            recs, reason, n_f_out, slab_live, flags = ss.unpack_ring(
                ctrl, mn, mm, rf, rp, rs
            )
            graft_sanitize.superstep_tick(len(recs))
            self._ss_stats["supersteps"] += 1
            self._ss_stats["levels"] += len(recs)
            if reason == "stop":
                self._ss_stats["stops"] += 1
            elif reason == "ring":
                self._ss_stats["ring_stops"] += 1
            # a stopped or ring-stopped level ran without committing
            ran = len(recs) + (reason in ("stop", "ring"))
            self._ss_stats["probe_lanes"] += int(ctrl[ss.SS_PROBE_LANES])
            self._ss_stats["lane_budget"] += ran * lanes
        return dict(
            recs=recs,
            frontier=fr_out,
            slab=slab_out,
            n_total=sum(r["n_new"] for r in recs),
            n_f=n_f_out,
            reason=reason,
            slab_live=slab_live,
            flags=flags,
            cap_f=cap_f,
            span=span,
        )

    def _action_counts(self, mult_per_slot: np.ndarray) -> dict:
        """Fold per-slot fired-transition counts to action names (the TLC
        -coverage analog; UpdateTerm's two slot families sum together)."""
        out: dict[str, int] = {}
        fam = self.kern.slot_family
        for fi, (name, _fn, _c) in enumerate(self.kern.families):
            out[name] = out.get(name, 0) + int(mult_per_slot[fam == fi].sum())
        return {k: v for k, v in out.items() if v}

    def _bad_invariant_name(self, children: RaftState, idx: int) -> str:
        """Identify which invariant a known-bad state violates (cold path)."""
        one = jax.tree.map(lambda x: x[idx : idx + 1], children)
        for name, fn in self.inv_fns:
            ok = jax.device_get(fn(self.cfg, one, self.kern.tables))
            if not bool(np.asarray(ok)[0]):
                return name
        return self.inv_fns[0][0]

    # -- trace reconstruction ---------------------------------------------

    def _trace(self, levels: list[tuple[np.ndarray, np.ndarray]], level: int, idx: int):
        """Walk (parent, slot) spills back to Init, then replay forward.

        levels[d] = (pidx, slot) arrays for the states created at depth d+1;
        ``idx`` indexes into level ``level``'s arrays (level 0 = init).
        """
        chain = []  # slots to apply, init -> violation
        d, j = level, idx
        while d > 0:
            pidx, slots = levels[d - 1]
            chain.append(int(slots[j]))
            j = int(pidx[j])
            d -= 1
        chain.reverse()
        st = init_batch(self.cfg, 1)
        out = [("Init", to_oracle(self.cfg, st)[0])]
        for slot in chain:
            st = self.kern.materialize(st, jnp.asarray([slot], I64))
            fam = int(self.kern.slot_family[slot])
            name = self.kern.families[fam][0]
            server = int(self.kern.slot_coords[slot, 0]) + 1
            out.append((f"{name}({server})", to_oracle(self.cfg, st)[0]))
        return out

    # -- checkpoint / resume (TLC's states/ metadir + -recover) ------------
    #
    # Two formats:
    #
    # * **delta log** (the default; ``checkpoint_dir`` is a directory of
    #   ``delta_####.npz`` files): each BFS level appends only its
    #   (parent, slot) payloads and new canonical fingerprints —
    #   ~14 B/state, all of which the level already fetched to the host
    #   for trace reconstruction (plus the fps).  Resume REPLAYS the
    #   materialize pass level by level from Init — minutes of device
    #   compute instead of a multi-GB frontier fetch.  The monolith
    #   format's full-frontier ``device_get`` (~2.7 GB at a 6M-state
    #   frontier) repeatedly crashed the round-5 device worker.
    #
    # * **monolith** (``latest.npz``, back-compat): full frontier +
    #   visited store in one file; O(1) resume but O(frontier) fetch.

# -- end-to-end integrity audit (resilience/integrity.py) --------------

    def _flip_frontier_row(self, frontier):
        """Apply the ``tensor.flip`` fault: XOR bit 0 of the first live
        frontier row's ``current_term[0]`` on device.  Row 0 is always
        live (frontier rows compact to a prefix) and is always in the
        audit sample (integrity.audit_indices) — the injected flip is
        deterministically catchable."""

        def flip_first(x):
            return x.at[0, 0].set(x[0, 0] ^ jnp.asarray(1, x.dtype))

        if isinstance(frontier, list):
            seg = frontier[0]
            if isinstance(seg, _HostSeg):
                f = dict(seg.fields)
                ct = np.array(f["current_term"], copy=True)
                ct[0, 0] ^= 1
                f["current_term"] = ct
                return [_HostSeg(f)] + frontier[1:]
            return [
                seg._replace(current_term=flip_first(seg.current_term))
            ] + frontier[1:]
        return frontier._replace(
            current_term=flip_first(frontier.current_term)
        )

    def _audit_impl_rows(self, par_rows: Frontier, kid_rows: Frontier,
                         slots):
        """The audit cross-check over pre-gathered rows: (1) the legacy
        guard must admit the recorded slot, (2) legacy materialize +
        fingerprint must equal the recorded fp, (3) the frontier row as
        materialized on device must re-fingerprint to the recorded fp
        (the bit-flip catch).  Returns (guard_ok, fv_legacy, fv_now)."""
        parents = self._inflate(par_rows)
        kids_now = self._inflate(kid_rows)
        valid, _mult, _ab = self.kern.expand_guards_legacy(parents)
        guard_ok = valid[jnp.arange(slots.shape[0]), slots]
        kids_legacy = self.kern.materialize_legacy(parents, slots)
        fv_leg, _ff_leg = self._fp_states(kids_legacy)
        fv_now, _ff_now = self._fp_states(kids_now)
        return guard_ok, fv_leg.astype(U64), fv_now.astype(U64)

    def _audit_impl(self, parents_f, new_frontier, pidx, idx, slots):
        par_rows = jax.tree.map(lambda x: x[pidx], parents_f)
        kid_rows = jax.tree.map(lambda x: x[idx], new_frontier)
        return self._audit_impl_rows(par_rows, kid_rows, slots)

    @functools.cached_property
    def _audit_prog(self):
        return self._program(self._audit_impl)

    def _gather_frontier_rows(self, frontier, idx_np) -> Frontier:
        """Sampled rows of a frontier (tree or segment list) as one
        small device-resident Frontier batch."""
        if isinstance(frontier, list):
            L0 = _seg_rows(frontier[0])
            parts = []
            for i in idx_np:
                si, off = divmod(int(i), L0)
                seg = frontier[si]
                if isinstance(seg, _HostSeg):
                    parts.append(Frontier(**{
                        f: jnp.asarray(v[off: off + 1])
                        for f, v in seg.fields.items()
                    }))
                else:
                    parts.append(
                        jax.tree.map(lambda x: x[off: off + 1], seg)
                    )
            return jax.tree.map(lambda *xs: jnp.concatenate(xs), *parts)
        ii = jnp.asarray(np.asarray(idx_np, np.int64))
        return jax.tree.map(lambda x: x[ii], frontier)

    def _audit_level(self, parents_f, new_frontier, pidx_np, slot_np,
                     level_fps, n_new, depth):
        """Re-expand a deterministic sample through the legacy kernels
        and cross-check against the hot path; returns the list of
        problem strings (empty = level verified)."""
        idx = resilience.integrity.audit_indices(n_new, self.audit)
        if idx.size == 0:
            return []
        self.audit_stats["levels"] += 1
        self.audit_stats["sampled"] += int(idx.size)
        # pad the sample to the fixed --audit width (repeating row 0) so
        # the audit programs compile once per frontier shape, not once
        # per distinct sample size; comparisons only read the live lanes
        n_live = int(idx.size)
        if n_live < self.audit:
            idx = np.concatenate([
                idx, np.full(self.audit - n_live, idx[0], np.int64)
            ])
        # recorded level fingerprints at the sampled rows (host numpy on
        # the external-store path; a tiny device gather otherwise)
        if isinstance(level_fps, np.ndarray):
            ref = level_fps[idx].astype(np.uint64)
        else:
            # graftlint: waive[GL006] — audit-mode-only sampled fetch
            ref = np.asarray(jax.device_get(
                level_fps[jnp.asarray(idx)]
            )).astype(np.uint64)
        pidx_s = jnp.asarray(np.asarray(pidx_np)[idx], I64)
        slots = jnp.asarray(np.asarray(slot_np)[idx], I64)
        idx_d = jnp.asarray(idx, I64)
        if not isinstance(parents_f, list) and not isinstance(
            new_frontier, list
        ):
            # device-resident frontiers: the whole cross-check — row
            # gathers, inflate, legacy guards/materialize, fingerprints
            # — runs as ONE jitted program per (parent cap, child cap)
            # shape pair, so audit overhead is two small dispatches +
            # one fetch per level, not ~30 eager ops
            key = (
                parents_f.voted_for.shape[0],
                new_frontier.voted_for.shape[0], self.audit,
            )
            if key not in self._audit_keys:
                self._audit_keys.add(key)
                graft_sanitize.note_shape_event(f"audit program {key}")
            guard_ok, fv_leg, fv_now = self._audit_prog(
                parents_f, new_frontier, pidx_s, idx_d, slots
            )
        else:
            par_rows = self._gather_frontier_rows(
                parents_f, np.asarray(pidx_np)[idx]
            )
            kid_rows = self._gather_frontier_rows(new_frontier, idx)
            guard_ok, fv_leg, fv_now = self._audit_impl_rows(
                par_rows, kid_rows, slots
            )
        # graftlint: waive[GL006] — audit-mode-only verdict fetch
        guard_np, leg_np, now_np = jax.device_get((
            guard_ok, fv_leg.astype(U64), fv_now.astype(U64)
        ))
        guard_np = np.asarray(guard_np, bool)
        leg_np = np.asarray(leg_np, np.uint64)
        now_np = np.asarray(now_np, np.uint64)
        problems = []
        for j, row in enumerate(idx[:n_live]):
            if not guard_np[j]:
                problems.append(
                    f"row {int(row)}: legacy guard refutes recorded "
                    f"slot {int(np.asarray(slot_np)[row])}"
                )
            if leg_np[j] != ref[j]:
                problems.append(
                    f"row {int(row)}: legacy re-expansion fp "
                    f"{leg_np[j]:#x} != recorded {ref[j]:#x}"
                )
            if now_np[j] != ref[j]:
                problems.append(
                    f"row {int(row)}: materialized frontier row "
                    f"re-fingerprints to {now_np[j]:#x} != recorded "
                    f"{ref[j]:#x} (corrupted frontier tensor)"
                )
        if problems:
            self.audit_stats["mismatches"] += len(problems)
            for p in problems[:8]:
                print(f"[integrity] audit level {depth + 1}: {p}",
                      file=sys.stderr)
        return problems

    def _audit_rewind(self, problems, depth, max_depth, checkpoint_dir,
                      checkpoint_every):
        """Quarantine the mismatched level and rewind to the last
        committed checkpoint; fail-stop after ``audit_retries``
        reproducible strikes.

        The mismatched level never reached the delta log (the audit
        runs before the commit), so the rewind is a plain self-resume:
        the replay re-materializes every level from the durable
        (parent, slot) decisions — tensors are recomputed, so the
        corruption cannot survive the rewind unless it is
        deterministic, which is exactly what the strike budget
        detects.  "Reproducible" means AT THE SAME LEVEL: strikes
        count per mismatch depth and reset when a different level
        mismatches, so independent transient flips hours apart never
        sum into a fake fail-stop; a hard cap on TOTAL rewinds
        (4x the budget) still bounds a corruption source that hops
        between levels."""
        if self._audit_strike_depth == depth:
            self._audit_strikes += 1
        else:
            self._audit_strikes = 1
            self._audit_strike_depth = depth
        strikes = self._audit_strikes
        if self.audit_stats["rewinds"] >= 4 * self.audit_retries:
            raise resilience.integrity.AuditFailStop(
                f"audit mismatches forced {self.audit_stats['rewinds']} "
                f"rewinds in one run (cap {4 * self.audit_retries}): "
                "pervasive corruption — fail-stop; latest problem: "
                + problems[0]
            )
        if strikes >= self.audit_retries:
            raise resilience.integrity.AuditFailStop(
                f"audit mismatch at level {depth + 1} reproduced "
                f"{strikes} time(s) (budget {self.audit_retries}): "
                "deterministic corruption — fail-stop; first problem: "
                + problems[0]
            )
        import glob as _glob

        can_resume = bool(checkpoint_dir and checkpoint_every)
        has_records = can_resume and bool(
            _glob.glob(os.path.join(checkpoint_dir, "delta_*.npz"))
            or os.path.exists(os.path.join(checkpoint_dir, "base.npz"))
        )
        if not can_resume:
            raise resilience.integrity.AuditFailStop(
                f"audit mismatch at level {depth + 1} with no "
                "checkpoint directory to rewind to — fail-stop; first "
                "problem: " + problems[0]
            )
        self.audit_stats["rewinds"] += 1
        print(
            f"[integrity] quarantining level {depth + 1} and rewinding "
            f"to the last committed checkpoint (strike {strikes}/"
            f"{self.audit_retries})",
            file=sys.stderr,
        )
        # the in-memory run state (visited slab/store, frontier) is
        # polluted by the quarantined level — drop it all and rebuild
        # from the durable log
        self.hstore = None
        self._hs_pending = None
        if self.host_store is not None and not has_records:
            # a fresh restart re-inserts from Init; pre-crash inserts
            # would silently mark reachable states visited
            self.host_store.clear()
        return self._run(
            max_depth=max_depth,
            checkpoint_dir=checkpoint_dir,
            checkpoint_every=checkpoint_every,
            resume_from=checkpoint_dir if has_records else None,
        )

    def _save_delta(self, ckdir, depth, pidx_np, slot_np, fps_np,
                    level_mult, n_new):
        # slot ids must round-trip the log exactly; K grows with the
        # S/T/L/V bounds (3,696 at S=7), so widen past the u16 range
        # rather than silently wrapping (the loader reads either width)
        slot_dt = np.uint16 if self.K <= 0xFFFF else np.uint32
        resilience.commit_npz(
            ckdir,
            f"delta_{depth:04d}.npz",
            dict(
                pidx=pidx_np.astype(np.uint32),
                slot=slot_np.astype(slot_dt),
                fps=fps_np.astype(np.uint64),
                mult=level_mult.astype(np.int64),
                # meta[2] (fp definition: 0 = min-over-P fold, 1 = orbit
                # canonical-relabel) guards resume: the two definitions
                # produce different fingerprint VALUES and must never mix
                # in one visited store.  Old two-element logs read as 0.
                meta=np.asarray([depth, n_new, int(self.orbit)], np.int64),
            ),
            kind="delta",
            depth=depth,
            run_fp=self._run_fp,
        )

    def _materialize_payload_slices(self, frontier, new_payload, n_new):
        """Run _mat_slice over every survivor slice; returns the parts.

        (The device-store path's builder: parts + one concat.  The
        external-store path uses the segment-streamed builders instead —
        _materialize_segs / _materialize_fallback_segs — whose transients
        are segment-bounded.)
        """
        # 8x-chunk slices: with the sorted-insert deflate the per-slice
        # compute is light enough that slice count (dispatch + drain
        # round-trips) is the next cost; 64k rows
        # x ~240 B keeps the in-flight working set at ~16 MB/slice
        sl = min(8 * self.chunk, new_payload.shape[0])
        n_slices = -(-n_new // sl)
        child_parts, bad_ds, ovf_ds = [], [], []
        for si in range(n_slices):
            take = min(sl, n_new - si * sl)
            pay_slice = jax.lax.dynamic_slice_in_dim(new_payload, si * sl, sl)
            ch_f, bad_d, ovf_d = self._mat_slice(
                frontier, pay_slice, jnp.asarray(take, I64)
            )
            graft_sanitize.note_dispatch("device.mat")
            child_parts.append(ch_f)
            bad_ds.append(bad_d)
            ovf_ds.append(ovf_d)
            # bound the dispatch queue; at deep-sweep slice widths every
            # in-flight slice pins GB-scale working sets, so drain one at
            # a time there
            if sl >= 16384 or si % 4 == 3:
                jax.device_get(bad_d)
        return child_parts, bad_ds, ovf_ds, n_slices, sl

    def _frontier_cap(self, n: int) -> int:
        """Frontier capacity for n states: half-step quantized, but ONLY
        when the step divides evenly into chunks — the chunked expand
        carves the frontier with dynamic slices at chunk strides, and a
        non-multiple capacity would silently clamp the last slice onto
        re-read rows (wrong parents)."""
        c = _cap_steps(n)
        if c % self.chunk:
            c = _pow2(n)
        c = max(c, self.chunk)
        if self._presize_fcap > c:
            # forecast floor: pow2 and >= chunk, so still a chunk multiple
            c = self._presize_fcap
        return c

    def _hbm_note(self, frontier, level_sizes, max_depth,
                  depth) -> None:
        """Live-HBM gauge + predictive pre-OOM forecast (loop-top, one
        per level, telemetry-gated to a single global read when off).

        Registers the frontier's live device bytes beside the slab the
        hash store already registers; under a device budget
        (``--dev-bytes`` hot-tier, or the ``TLA_RAFT_DEV_BYTES`` paging
        budget) it also forecasts the NEXT level's working set — the
        slab after its forecast inserts, its quantized frontier, and
        the expand lane transient — and emits ``pre_oom_forecast``
        when that would bust the budget: the predictive twin of the
        reactive overflow-redo (the tier/pager still handles the real
        crossing; this event is the early warning --progress and the
        service can act on)."""
        if graft_obs.current() is None:
            return
        nbytes = 0
        for x in jax.tree.leaves(frontier):
            it = getattr(getattr(x, "dtype", None), "itemsize", None)
            nbytes += int(getattr(x, "size", 0)) * int(it or 0)
        graft_obs.buffer("frontier", nbytes)
        budget = (
            self.tiered.dev_bytes if self.tiered is not None
            else (self.store_bytes or self.dev_budget)
        )
        if not budget:
            return
        if not getattr(self, "_hbm_budget_noted", False):
            self._hbm_budget_noted = True
            graft_obs.hbm_budget(budget)
        cap_f = getattr(
            getattr(frontier, "voted_for", None), "shape", (0,)
        )[0]
        if not cap_f or getattr(self, "_pre_oom_level", None) == depth:
            return  # segmented external frontier (already paged) / dup
        fut = graft_forecast.forecast_new_states(
            level_sizes, max_depth
        )[:1]
        if not fut:
            return
        nrows = int(fut[0])
        row_b = max(nbytes // max(cap_f, 1), 1)
        cap_next = self._frontier_cap(
            int(nrows * graft_forecast.cap_margin()) + 1
        )
        slab_b = 0
        if self.use_hashstore and self.hstore is not None:
            want = hashstore.slab_rows(self.hstore.count + nrows)
            if self.tiered is not None:
                # the tier demotes rather than grow past the budget:
                # charge the hot slab at its budget-clamped size
                want = min(want, max(
                    hashstore.slab_rows(
                        self.tiered.max_hot_entries or 1
                    ), hashstore.MIN_CAP,
                ))
            slab_b = want * 8
        # expand transient: cv/cf u64 + cp i64 per candidate lane
        lanes_b = (cap_next // self.chunk) * self.cap_x * 24
        # the spill sieve's device image joins the forecast the moment
        # tiering is configured: it allocates at FULL size on the first
        # demotion, so the headroom must exist BEFORE spill starts
        sieve_b = (
            graft_forecast.sieve_bytes(self.tiered.dev_bytes)
            if self.tiered is not None and self.sieve_enabled else 0
        )
        need = slab_b + cap_next * row_b + lanes_b + sieve_b
        if need > budget:
            self._pre_oom_level = depth
            graft_obs.pre_oom(
                depth + 1, need, budget,
                slab=slab_b, frontier=cap_next * row_b,
                lanes=lanes_b, sieve=sieve_b, rows=nrows,
            )

    def _update_presize(self, level_sizes, distinct, max_depth, frontier):
        """Ratchet the forecast capacity floors (see __init__).

        Called once per level; floors only grow.  Frontier bytes are
        clamped (TLA_RAFT_PRESIZE_BYTES, default 4 GB) so a noisy early
        forecast cannot reserve more HBM than the run could use."""
        from .forecast import PRESIZE_HORIZON, forecast_new_states

        fut = forecast_new_states(level_sizes, max_depth)[:PRESIZE_HORIZON]
        if not fut:
            return
        peak = max(fut)
        budget = int(float(
            os.environ.get("TLA_RAFT_PRESIZE_BYTES", "4e9")
        ))
        want_f = max(
            _pow2(int(peak * graft_forecast.cap_margin()) + 1), self.chunk
        )
        if not isinstance(frontier, list):
            row_b = sum(
                int(np.prod(x.shape[1:])) * x.dtype.itemsize
                for x in jax.tree.leaves(frontier)
            )
            while want_f > self.chunk and want_f * row_b > budget:
                want_f //= 2
        self._presize_fcap = max(self._presize_fcap, want_f)
        self._presize_vcap = max(
            self._presize_vcap,
            min(_cap4(distinct + sum(fut) + 1), _cap4(budget // 8)),
        )
        # hash-slab sizing wants the ENTRY forecast, not a pow4 array
        # width (the slab layer applies its own load-factor/pow2 quantum;
        # 8 B/slot at <=1/2 load => entries <= budget/16)
        self._presize_entries = max(
            getattr(self, "_presize_entries", 0),
            min(distinct + sum(fut), budget // 16),
        )
        self._presize_merge = max(
            self._presize_merge,
            min(_pow2(int(peak * 1.05) + 1), _pow2(budget // 16)),
        )

    # -- forecast-driven AOT prewarm (engine/pipeline.Prewarmer) ----------

    def _frontier_struct(self, template, cap: int):
        """ShapeDtypeStruct tree of a ``cap``-row frontier, field shapes
        and dtypes taken from a live frontier/segment (``template``)."""
        if isinstance(template, list):
            template = template[0]
        if isinstance(template, _HostSeg):
            fields = template.fields
            return Frontier(**{
                f: jax.ShapeDtypeStruct((cap,) + v.shape[1:], v.dtype)
                for f, v in fields.items()
            })
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct((cap,) + x.shape[1:], x.dtype),
            template,
        )

    def _prewarm_plan(self, level_sizes, distinct, max_depth, frontier,
                      visited):
        """(key, thunk) pairs compiling the deep-level program set at the
        forecast shape ladder (``jit(...).lower(...).compile()``).

        The thunks never dispatch a device program (inputs are avals),
        so running them on the Prewarmer's background thread does not
        break the all-dispatch-on-the-main-thread rule; the payoff
        routes through the persistent compilation cache
        (platform.setup_jax), which a supervised relaunch also reads.
        Shapes come from the SAME quantizers the level loop uses
        (_frontier_cap/_cap_steps/_cap4/slab_rows), so a sharp forecast
        prewarms exactly the programs the deep levels will request."""
        from .forecast import pow2_ladder, shape_plan

        rows = shape_plan(level_sizes, max_depth)
        if not rows:
            return []
        plan: list = []
        s_i64 = jax.ShapeDtypeStruct((), jnp.int64)
        # the fused programs' sieve operand at its CURRENT shape (the
        # 1-word sentinel pre-spill; the full filter image after — it
        # is allocated at final size on first demotion, so the shape
        # the prewarm keys on is the shape the runtime will request)
        sv_struct = jax.ShapeDtypeStruct(
            (int(self._sieve_operand().shape[0]),), jnp.uint64
        )
        final = distinct + sum(rows)

        def u64(n):
            return jax.ShapeDtypeStruct((n,), jnp.uint64)

        def i64(n):
            return jax.ShapeDtypeStruct((n,), jnp.int64)

        def slab_ladder():
            # the ONE slab-capacity ladder both the fused and staged
            # hashstore plans rung through (drift here would split
            # their compiled shapes)
            return pow2_ladder(
                self.hstore.cap // 2, hashstore.slab_rows(final)
            ) or [self.hstore.cap]

        # forecast rows the FUSED program will serve: the prefix whose
        # levels stay under the grouping threshold (the level loop
        # routes bigger levels to the staged grouped path, so those
        # rows need the staged plan below instead)
        mega_rows = 0
        if self._mega_level_ok(frontier, max(int(rows[0]), 1)):
            prev = max(int(level_sizes[-1]), 1)
            for r in rows:
                if -(-max(prev, 1) // self.chunk) > 16 * self.G:
                    break
                mega_rows += 1
                prev = int(r)
        if mega_rows and self.superstep_span > 1:
            # superstep path: the multi-level driver's shape ladder
            # REPLACES the per-level fused keys (those programs are
            # dead while supersteps are on — compiling them would pay
            # compile time for nothing).  The walk mirrors
            # _run_superstep exactly: span-sized windows over the raw
            # forecast, one static cap_f per window (max rung, same
            # margins), the ring chained from the window's cap_out
            # sequence, the input rung chained from the previous
            # window's cap_f.
            from .forecast import forecast_new_states

            span = self.superstep_span
            scaps = slab_ladder()
            fut_all = forecast_new_states(level_sizes, max_depth)
            prev_cap = frontier.voted_for.shape[0]
            prev_rows = max(int(level_sizes[-1]), 1)
            s_i64_n = jax.ShapeDtypeStruct((), jnp.int64)
            i = 0
            while i < mega_rows:
                fut_w = fut_all[i:i + span]
                if not fut_w:
                    break
                cap_f, ring = self._superstep_shapes(
                    fut_w, span, prev_rows, prev_cap
                )
                prog = graft_superstep.superstep_program_for(
                    self.level_spec(), span, self._mega_donate,
                    self._ss_stats,
                )
                fs = self._frontier_struct(frontier, prev_cap)
                for sc in scaps:
                    plan.append((
                        ("sstep", prev_cap, cap_f, ring, sc, span,
                         self.cap_x, self.cap_m, self.use_mxu,
                         sv_struct.shape[0]),
                        lambda fs=fs, sc=sc, cap_f=cap_f, ring=ring,
                               prog=prog:
                            prog.lower(
                                fs, u64(sc), s_i64_n, s_i64_n,
                                sv_struct, cap_f=cap_f, ring=ring,
                            ).compile(),
                    ))
                prev_cap = cap_f
                prev_rows = max(int(fut_w[-1]), 1)
                i += span
            if mega_rows == len(rows):
                return plan
        elif mega_rows:
            # fused path: the megakernel ladder replaces the staged
            # span/dedup/gfilter program set for these rows — each
            # forecast level's program is keyed by (input cap, output
            # cap, slab cap): the input rung chains from the previous
            # level's output (the fused program's new frontier IS the
            # next level's input), the output rung runs through the
            # SAME capacity function as the runtime _mega_cap_out
            # (shape_plan's rows are already 1.25-margined; the 2x
            # floor, lane clamp and 4*chunk floor match), and the slab
            # ladder follows the store's growth.
            scaps = slab_ladder()
            prev_cap = frontier.voted_for.shape[0]
            prev_rows = max(int(level_sizes[-1]), 1)
            for r in rows[:mega_rows]:
                n_lanes = (prev_cap // self.chunk) * self.cap_x
                est = max(int(r), 2 * prev_rows)
                cout = max(
                    self._frontier_cap(min(est, max(n_lanes, 1))),
                    4 * self.chunk,
                )
                fs = self._frontier_struct(frontier, prev_cap)
                for sc in scaps:
                    plan.append((
                        ("mega", prev_cap, cout, sc, self.cap_x,
                         self.cap_m, self.use_mxu, sv_struct.shape[0]),
                        lambda fs=fs, sc=sc, cout=cout,
                               prog=self._mega_prog:
                            prog.lower(
                                fs, u64(sc), s_i64, sv_struct,
                                cap_out=cout
                            ).compile(),
                    ))
                prev_cap, prev_rows = cout, int(r)
            if mega_rows == len(rows):
                return plan
            # later rows cross into the grouped regime: fall through so
            # the staged span/dedup/gfilter ladder compiles ahead of
            # the regime switch too

        # 1) the expand-span program at the frontier-capacity ladder (the
        # big one: its compile is the round-3 minutes-class cost).  The
        # external-store path walks uniform SEG_ROWS segments once the
        # frontier exceeds one segment, so its ladder collapses there.
        if self.chunk >= self.span_min_chunk and not self.orbit:
            caps = set()
            for r in rows:
                if self.host_store is not None:
                    caps.add(min(_host_cap(r, self.chunk), SEG_ROWS))
                else:
                    caps.add(self._frontier_cap(r))
            for c in sorted(caps):
                fs = self._frontier_struct(frontier, c)
                # the span program traces the kernel's guards/materialize,
                # so its identity includes the MXU-vs-legacy selection
                plan.append((
                    ("span", c, self.use_mxu),
                    lambda fs=fs: self._expand_span.lower(
                        fs, s_i64, s_i64, s_i64
                    ).compile(),
                ))
        if self.host_store is not None:
            # host path: per-group dedup runs at the FIXED G*cap_x lane
            # width (compiled by the first big level); nothing else on
            # the device scales with depth
            return plan
        # 2) the level-dedup program at the lane-count ladder, against
        # the visited structure at its forecast capacity
        lanes = set()
        for r in rows:
            n_chunks = -(-max(int(r), 1) // self.chunk)
            if n_chunks > 16 * self.G:  # the level loop's grouping rule
                lanes.add(_cap_steps((-(-n_chunks // self.G)) * self.cap_g))
            else:
                lanes.add(_cap_steps(n_chunks * self.cap_x))
        if self.use_hashstore:
            scaps = slab_ladder()
            for sc in scaps:
                for L in sorted(lanes):
                    plan.append((
                        ("dedup_hash", L, sc),
                        lambda L=L, sc=sc: _level_dedup_hash.lower(
                            u64(L), u64(L), i64(L), u64(sc)
                        ).compile(),
                    ))
                plan.append((
                    ("gfilter_hash", sc, self.cap_g),
                    lambda sc=sc: _group_filter_hash.lower(
                        u64(self.G * self.cap_x), u64(self.G * self.cap_x),
                        i64(self.G * self.cap_x), u64(sc),
                        cap_g=self.cap_g,
                    ).compile(),
                ))
        else:
            vcap_now = visited.shape[0]
            vcaps = pow2_ladder(
                vcap_now // 2,
                max(_cap4(final + 1), self._presize_vcap),
            ) or [vcap_now]
            vcaps = [v for v in vcaps if v == _cap4(v)]  # store is pow4
            for vc in vcaps:
                for L in sorted(lanes):
                    plan.append((
                        ("dedup", L, vc),
                        lambda L=L, vc=vc: _level_dedup.lower(
                            u64(L), u64(L), i64(L), u64(vc)
                        ).compile(),
                    ))
                plan.append((
                    ("gfilter", vc, self.cap_g),
                    lambda vc=vc: _group_filter.lower(
                        u64(self.G * self.cap_x), u64(self.G * self.cap_x),
                        i64(self.G * self.cap_x), u64(vc),
                        cap_g=self.cap_g,
                    ).compile(),
                ))
                # 3) the store merge at its forecast input widths
                for r in set(rows):
                    w = max(_pow2(int(r)), self.chunk)
                    if self._presize_merge:
                        w = max(w, self._presize_merge)
                    plan.append((
                        ("merge", vc, w),
                        lambda vc=vc, w=w: _merge_sorted.lower(
                            u64(vc), u64(w)
                        ).compile(),
                    ))
        return plan

    def _submit_prewarm(self, level_sizes, distinct, max_depth, frontier,
                        visited):
        """Queue the forecast program set on the background compiler."""
        try:
            plan = self._prewarm_plan(
                level_sizes, distinct, max_depth, frontier, visited
            )
        except Exception as e:  # graftlint: waive[GL003] — plan building
            # is a pure optimization; a forecast edge case must never
            # take the run down (the shapes then compile in line)
            print(f"[pipeline] prewarm plan failed: {e}", file=sys.stderr)
            return
        if not plan:
            return
        if self._prewarmer is None or self._prewarmer.stopped:
            self._prewarmer = graft_pipeline.Prewarmer()
        self._prewarmer.submit(plan)
        # deliberately NO note_shape_event here: the background thread's
        # thread-local marker already diverts every prewarm compile to
        # the declared ledger before the per-level counter sees it, and
        # a submission note would blanket-excuse a genuine MAIN-thread
        # silent retrace at this level — the exact regression class the
        # sanitizer exists to catch

    def _materialize_segs(self, segs, pay_np, new_payload, n_new):
        """Segment-streamed materialize for the external-store path.

        Parents arrive as a list of equal-size segment buffers; payloads
        are sorted ascending (payload = pidx*K + slot), so consecutive
        slices walk the parent segments left to right: each slice
        gathers from a (j, j+1) window and every segment left of the
        window frees as soon as the walk passes it — the INPUT LIST IS
        MUTATED (entries set to None) so every holder drops the buffer.
        Children land in segmented destinations, allocated as the walk
        reaches them.  HBM peak ~ dst + the unconsumed parent tail,
        instead of whole parent + whole dst — the wall the reference
        sweep hit at its level-27 materialize (13+ GB of 14.7 usable).

        Returns (dst_segs, bad_ds, ovf_ds, n_slices, sl), or None when a
        precondition fails (a slice spanning more than two segments —
        practically impossible for payload-sorted deep levels — or a
        legacy record whose payloads aren't ascending, or slice tiling
        that doesn't fit the capacity): the caller then takes the
        window-less whole-parent path.
        """
        K = self.K
        # one-chunk slices at deep-sweep chunk sizes: the materialize
        # program's transient workspace (the scatter-free message-set
        # inflate is ~60 KB/state on this family) scales with slice width
        # — 4*chunk slices cost ~4 GB of HBM headroom for ~20 s/level of
        # dispatch savings, a bad trade close to the ceiling.  Tiny
        # (test-scale) chunks keep the wider slices: their workspace is
        # KBs and 4x the dispatch count quadruples CPU-suite wall time.
        sl_quantum = self.chunk if self.chunk >= 2048 else 4 * self.chunk
        sl = min(sl_quantum, new_payload.shape[0])
        n_slices = -(-n_new // sl)
        cap_f = _host_cap(n_new, self.chunk)
        if n_slices * sl > cap_f:
            return None
        # the window reasoning below is sound only for globally ascending
        # payloads — endpoint checks alone would let a legacy cv-ordered
        # record slip interior payloads outside the window, where the
        # gather clips onto WRONG PARENT ROWS with no error
        if not bool(np.all(np.diff(pay_np[:n_new].astype(np.int64)) > 0)):
            return None
        L = _seg_rows(segs[0])
        n_par = len(segs)
        j_los = []
        for si in range(n_slices):
            a, b = si * sl, min(si * sl + sl, n_new)
            p_lo = int(pay_np[a]) // K
            p_hi = int(pay_np[b - 1]) // K
            j_lo = min(p_lo // L, n_par - 1)
            if p_hi >= min(j_lo + 2, n_par) * L:
                return None  # parent span exceeds the 2-segment window
            j_los.append(j_lo)
        seg_d = SEG_ROWS if cap_f > SEG_ROWS else cap_f
        n_seg_d = cap_f // seg_d
        per_seg = seg_d // sl if n_seg_d > 1 else n_slices
        dst = [None] * n_seg_d
        parts_buf = []
        bad_ds, ovf_ds = [], []
        # host-paged parents transit through this cache (segs keeps the
        # numpy copy as the source of truth); seg_b prices one segment
        # for the demotion decision at seal time
        paged: dict[int, Frontier] = {}
        seg_b = None

        def par(j):
            s = segs[j]
            if isinstance(s, _HostSeg):
                d = paged.get(j)
                if d is None:
                    d = paged[j] = self._seg_to_dev(s)
                return d
            return s

        for si in range(n_slices):
            take = min(sl, n_new - si * sl)
            j = j_los[si]
            pay_slice = jax.lax.dynamic_slice_in_dim(new_payload, si * sl, sl)
            part, bad_d, ovf_d = self._mat_slice_seg(
                par(j), par(min(j + 1, n_par - 1)),
                jnp.asarray(j * L, I64), pay_slice, jnp.asarray(take, I64),
            )
            graft_sanitize.note_dispatch("device.mat_seg")
            parts_buf.append(part)
            if len(parts_buf) == per_seg or si == n_slices - 1:
                # seal one destination segment: a bounded concat (the
                # transient is two segments, never two frontiers — no
                # donation semantics assumed, see note at top)
                dj = (si * sl) // seg_d
                sealed = jax.tree.map(
                    lambda *xs: _pad_axis0(jnp.concatenate(xs), seg_d),
                    *parts_buf,
                )
                parts_buf = []
                if self.dev_budget:
                    if seg_b is None:
                        seg_b = self._tree_bytes(sealed)
                    live = (
                        sum(
                            1 for k, s in enumerate(segs)
                            if s is not None
                            and (not isinstance(s, _HostSeg) or k in paged)
                        )
                        + sum(
                            1 for d in dst
                            if d is not None and not isinstance(d, _HostSeg)
                        )
                        + 2  # the transient concat + one in-flight slice
                        # the async pipeline keeps up to a window's
                        # worth of group fetch buffers (and their parent
                        # segments) alive through the NEXT expand — price
                        # that peak here so demotion leaves room for it
                        + (self.pipeline_window if self.pipeline else 0)
                    )
                    if (live + 1) * seg_b > self.dev_budget:
                        sealed = self._seg_to_host(sealed)
                        self.paged_out += 1
                dst[dj] = sealed
            for k in range(j):  # the walk has passed these parents for good
                segs[k] = None
                paged.pop(k, None)
            bad_ds.append(bad_d)
            ovf_ds.append(ovf_d)
            if sl >= 16384 or si % 4 == 3:
                jax.device_get(bad_d)
        for dj in range(n_seg_d):  # untouched capacity tail
            if dst[dj] is None:
                proto = next(d for d in dst if d is not None)
                if isinstance(proto, _HostSeg):
                    dst[dj] = _HostSeg(
                        {f: np.zeros(v.shape, v.dtype)
                         for f, v in proto.fields.items()}
                    )
                else:
                    dst[dj] = jax.tree.map(jnp.zeros_like, proto)
        return dst, bad_ds, ovf_ds, n_slices, sl

    def _materialize_fallback_segs(self, whole, new_payload, n_new):
        """Whole-parent materialize that still emits a SEGMENTED
        destination with bounded concat transients — the external-store
        path for legacy (non-ascending) records and tiny levels."""
        sl_quantum = self.chunk if self.chunk >= 2048 else 4 * self.chunk
        sl = min(sl_quantum, new_payload.shape[0])  # see _materialize_segs
        n_slices = -(-n_new // sl)
        cap_f = _host_cap(n_new, self.chunk)
        if n_slices * sl > cap_f:
            seg_d, n_seg_d = cap_f, 1
        else:
            seg_d = SEG_ROWS if cap_f > SEG_ROWS else cap_f
            n_seg_d = cap_f // seg_d
        # a single-segment destination seals once, at the end (tiny levels
        # whose slice tiling overshoots the capacity get truncated there)
        per_seg = seg_d // sl if n_seg_d > 1 else n_slices
        dst = [None] * n_seg_d
        parts_buf = []
        bad_ds, ovf_ds = [], []
        for si in range(n_slices):
            take = min(sl, n_new - si * sl)
            pay_slice = jax.lax.dynamic_slice_in_dim(new_payload, si * sl, sl)
            ch_f, bad_d, ovf_d = self._mat_slice(
                whole, pay_slice, jnp.asarray(take, I64)
            )
            graft_sanitize.note_dispatch("device.mat")
            parts_buf.append(ch_f)
            if len(parts_buf) == per_seg or si == n_slices - 1:
                dj = min((si * sl) // seg_d, n_seg_d - 1)
                dst[dj] = jax.tree.map(
                    lambda *xs: _pad_axis0(jnp.concatenate(xs), seg_d),
                    *parts_buf,
                )
                parts_buf = []
            bad_ds.append(bad_d)
            ovf_ds.append(ovf_d)
            if sl >= 16384 or si % 4 == 3:
                jax.device_get(bad_d)
        for dj in range(n_seg_d):
            if dst[dj] is None:
                dst[dj] = jax.tree.map(jnp.zeros_like, dst[0])
        return dst, bad_ds, ovf_ds, n_slices, sl

    def _widen_msg_ids(self, frontier: Frontier) -> Frontier:
        """Pad the frontier's sparse message-id lanes out to self.cap_m."""
        if isinstance(frontier, _HostSeg):
            ids = frontier.fields["msg_ids"]
            pad = self.cap_m - ids.shape[1]
            if pad <= 0:
                return frontier
            f2 = dict(frontier.fields)
            f2["msg_ids"] = np.concatenate(
                [ids, np.full((ids.shape[0], pad), -1, ids.dtype)], axis=1
            )
            return _HostSeg(f2)
        ids = frontier.msg_ids
        pad = self.cap_m - ids.shape[1]
        if pad <= 0:
            return frontier
        return frontier._replace(
            msg_ids=jnp.concatenate(
                [ids, jnp.full((ids.shape[0], pad), -1, ids.dtype)], axis=1
            )
        )

    # -- host-RAM segment paging (the level-29 HBM wall breaker) -----------

    def _seg_to_host(self, seg: Frontier, depth: int = -1) -> _HostSeg:
        hs = _HostSeg(
            {f: np.asarray(jax.device_get(getattr(seg, f)))
             for f in Frontier._fields}
        )
        self._fseg_admit(hs, depth)
        return hs

    def _fseg_admit(self, hs: _HostSeg, depth: int = -1) -> None:
        """Host-budget admission for a paged-out segment: once the
        RAM-resident host segments exceed TLA_RAFT_FSEG_BYTES, the
        incoming segment spills straight to the warm tier (kind="fseg")
        — the walks consume segments in ascending order, so keeping the
        EARLIER segments resident and spilling the later ones is the
        moving-window policy (by the time a spilled segment reloads,
        its predecessors are consumed and freed)."""
        if self._fpager is None or not self.fseg_host_bytes:
            return
        live = [r for r in self._fseg_live if r() is not None]
        resident = sum(r().resident_bytes for r in live)
        if resident + hs.resident_bytes > self.fseg_host_bytes:
            hs.spill(self._fpager, depth)
            self.paged_disk += 1
        live.append(weakref.ref(hs))
        self._fseg_live = live

    def _fseg_retire_consumed(self) -> None:
        """Drop consumed segments' warm-tier artifacts (level top: the
        previous level is committed, its parents can never be replayed
        — a degrade-redo only ever reaches back one level)."""
        if self._fseg_retire and self._fpager is not None:
            self._fpager.retire(self._fseg_retire)
        self._fseg_retire = []

    def _seg_to_dev(self, seg) -> Frontier:
        if not isinstance(seg, _HostSeg):
            return seg
        return Frontier(**{f: jnp.asarray(v) for f, v in seg.fields.items()})

    @staticmethod
    def _tree_bytes(seg) -> int:
        vals = (
            seg.fields.values() if isinstance(seg, _HostSeg)
            else (getattr(seg, f) for f in Frontier._fields)
        )
        return sum(int(np.prod(x.shape)) * x.dtype.itemsize for x in vals)

    def _materialize_grow(self, frontier, new_payload, n_new, pay_np=None):
        """Materialize survivors, auto-growing cap_m on overflow.

        cap_m (the sparse-frontier message-set width) grows ~1 per BFS
        level on the reference family; a fixed budget would make deep
        sweeps die hours in (VERDICT round 2, weak #6).  Overflow is
        detected per slice by ``_ids_insert`` (an action's sent id finds
        the parent's id lanes full); the payloads are already
        known, so growing the width, widening the (parent) frontier's id
        lanes and re-materializing the level is pure re-computation —
        the same recovery shape as the cap_x growth redo.  EXCEPT on the
        segment-streamed path, where consumed parents are already freed:
        there overflow raises, and a restart with TLA_RAFT_CAP_M set
        resumes from the delta log (widths saturate at 96 on the
        reference family, so with the default headroom this is
        unreachable in practice).

        The host-store path passes ``frontier`` as a segment list (and
        ``pay_np``, the host-side sorted payloads); the result is then a
        segment list too.  Returns (new_frontier, bads, n_slices, sl,
        parent) — the new frontier is at its _frontier_cap capacity.
        """
        while True:
            segged = False
            retry_parent = None
            if isinstance(frontier, list):
                res = (
                    self._materialize_segs(frontier, pay_np, new_payload,
                                           n_new)
                    if pay_np is not None
                    else None
                )
                if res is not None:
                    out, bad_ds, ovf_ds, n_slices, sl = res
                    segged = True
                else:
                    # the window-less path concats on device; page any
                    # host-resident segments back in first (in place, so
                    # _concat_fields' list-emptying still frees them)
                    for i, s in enumerate(frontier):
                        frontier[i] = self._seg_to_dev(s)
                    whole = _concat_fields(frontier)
                    out, bad_ds, ovf_ds, n_slices, sl = (
                        self._materialize_fallback_segs(
                            whole, new_payload, n_new
                        )
                    )
                    retry_parent = whole
            else:
                parts, bad_ds, ovf_ds, n_slices, sl = (
                    self._materialize_payload_slices(
                        frontier, new_payload, n_new
                    )
                )
                cap_f = self._frontier_cap(n_new)
                out = jax.tree.map(
                    lambda *xs: _pad_axis0(jnp.concatenate(xs), cap_f),
                    *parts,
                )
                del parts
                retry_parent = frontier
            bads, ovfs = jax.device_get((bad_ds, ovf_ds))
            if not any(bool(np.asarray(o)) for o in ovfs):
                return out, bads, n_slices, sl, frontier
            if self.cap_m >= self.kern.uni.M:
                raise RuntimeError(
                    "message-set width exceeds the whole universe — "
                    "corrupt payloads?"
                )
            if segged and any(s is None for s in frontier):
                # only unrecoverable once the walk actually released
                # parent segments; a restart with TLA_RAFT_CAP_M set
                # resumes from the delta log
                raise RuntimeError(
                    f"cap_m={self.cap_m} overflowed after parent segments "
                    "were released; restart with TLA_RAFT_CAP_M="
                    f"{self.cap_m + 32} — the delta log resumes the run"
                )
            # widths grow ~1/level on this spec family and saturate near
            # the structural bound (measured 96 at depth 22 of the
            # reference sweep), so grow in small steps: doubling 96->192
            # doubles every deep frontier's bytes for ~10 lanes of need
            self.cap_m = min(self.cap_m + 32, self.kern.uni.M)
            print(f"[engine] cap_m overflow: growing to {self.cap_m} and "
                  f"re-materializing the level", file=sys.stderr)
            if isinstance(frontier, list):
                if retry_parent is not None:
                    # the fallback concat consumed the segment list in
                    # place (_concat_fields empties it); retry on the
                    # concatenated whole as a single segment, and drop
                    # pay_np so the retry takes the fallback path again
                    frontier = [self._widen_msg_ids(retry_parent)]
                    pay_np = None
                else:
                    frontier = [self._widen_msg_ids(s) for s in frontier]
            else:
                frontier = self._widen_msg_ids(retry_parent)

    def _resume_from_deltas(self, ckdir):
        """Rebuild the run state by replaying the delta log.

        The replay starts from Init, or from a ``base.npz`` monolith
        snapshot if one sits in the directory (written when a run that
        itself resumed from a monolith starts appending deltas)."""
        import glob

        # -- self-healing pass (resilience/recover.py): sweep orphaned
        # .tmp_* files, verify every record against the directory
        # manifest, quarantine corrupt/torn/unmanifested records and
        # truncate the chain to the last good contiguous prefix.  The
        # replay below then consumes only verified records; its gap
        # check stays as the backstop for the interior-hole case.
        base_path = os.path.join(ckdir, "base.npz")
        man = resilience.Manifest.load(ckdir)
        man.bind_run(self._run_fp)
        base_depth = 0
        if os.path.exists(base_path):
            st_base = man.verify("base.npz") if man.exists else "ok"
            if st_base == "unmanifested":
                # renamed/copied in before the manifest commit landed:
                # the meta read below is the structural probe; adopt so
                # the chain it anchors survives the next heal too
                resilience.adopt_file(
                    ckdir, "base.npz", kind="base", run_fp=self._run_fp
                )
                st_base = "ok"
            ok_base = st_base == "ok"
            if ok_base:
                try:
                    base_depth = int(np.load(base_path)["meta"][3])
                except (OSError, ValueError, KeyError, EOFError,
                        zipfile.BadZipFile):
                    ok_base = False
            if not ok_base:
                # the whole delta chain hangs off the base snapshot:
                # with it gone the deltas are orphans — quarantine
                # everything and restart from Init (the worst-case but
                # still hands-free recovery)
                resilience.quarantine(
                    ckdir, "base.npz", "corrupt base snapshot", man
                )
                for f in sorted(
                    glob.glob(os.path.join(ckdir, "delta_*.npz"))
                ):
                    resilience.quarantine(
                        ckdir, os.path.basename(f),
                        "orphaned by quarantined base", man,
                    )
                if man.exists:
                    man.commit()
        files = resilience.heal_log(
            ckdir, "delta", run_fp=self._run_fp, slabs=("hslab.npz",),
            start_depth=base_depth + 1,
        )
        if (
            not files and not os.path.exists(base_path) and not man.exists
        ):
            # a directory that was never one of ours (no manifest, no
            # records) is a caller error, not a healable crash
            raise ValueError(f"no delta_*.npz checkpoints under {ckdir}")
        if self.host_store is not None:
            # rebuild the external store from the log as the replay walks
            # it (level-at-a-time inserts keep the store's spill budget in
            # force — the whole point of the external tier is a visited
            # set bigger than host RAM).  clear() first: the store may
            # still hold pre-crash inserts, including a partially-
            # completed level that never reached the log, and those would
            # silently mark reachable states as already-visited.
            self.host_store.clear()
        cfg, K = self.cfg, self.K
        if os.path.exists(base_path):
            ck = self._load_checkpoint(
                base_path,
                device_visited=(
                    self.host_store is None and not self.use_hashstore
                ),
            )
            self._check_fp_def(ck["fp_def"], base_path)
            frontier, n_f = ck["frontier"], ck["n_f"]
            visited_base = ck["visited"]
            if self.host_store is not None:
                # a device-store monolith seeds the external store: its
                # visited array IS the fingerprint set (sorted, SENT-
                # padded).  The base may be a checkpoint of a device-store
                # run — the two tiers' contents are interchangeable; only
                # their location differs.
                self._seed_host_store(visited_base)
                visited_base = None
                # host-path frontiers are segment lists; split a monolith
                # frontier into uniform segments when it tiles evenly so
                # the replay's first materialize gathers through windows
                # (a whole-frontier gather materializes operand-sized
                # temps on this backend — the gather cliff, docs/PERF.md)
                rows = frontier.voted_for.shape[0]
                if rows % SEG_ROWS == 0 and rows > SEG_ROWS:
                    cut = jax.jit(
                        lambda t, s: jax.tree.map(
                            lambda x: jax.lax.dynamic_slice_in_dim(
                                x, s, SEG_ROWS
                            ),
                            t,
                        )
                    )
                    frontier = [
                        cut(frontier, jnp.asarray(i * SEG_ROWS, I32))
                        for i in range(rows // SEG_ROWS)
                    ]
                else:
                    frontier = [frontier]
            fps_parts = []
            trace_levels = ck["trace_levels"]
            level_sizes = list(ck["level_sizes"])
            mult_per_slot = np.asarray(ck["mult_per_slot"])
            depth = ck["depth"]
        else:
            st0 = init_batch(cfg, 1)
            fv0, _ff0 = self._fp_states(st0)
            frontier, _ovf = self._program(self._deflate)(st0)
            frontier = jax.tree.map(
                lambda x: _pad_axis0(x, self.chunk), frontier
            )
            if self.host_store is not None:
                frontier = [frontier]
            n_f = 1
            visited_base = None
            init_fps = np.asarray(fv0.astype(U64))
            if self.host_store is not None:
                self.host_store.insert(init_fps)
                fps_parts = []
            else:
                fps_parts = [init_fps]
            trace_levels, level_sizes = [], [1]
            mult_per_slot = np.zeros(K, np.int64)
            depth = 0
        for f in files:
            z = np.load(f)
            meta = [int(x) for x in z["meta"]]
            d, n_new = meta[0], meta[1]
            fp_def = meta[2] if len(meta) > 2 else 0
            if fp_def != int(self.orbit):
                raise ValueError(
                    f"fingerprint-definition mismatch: delta log {f} was "
                    f"written with {'orbit' if fp_def else 'min-over-P'} "
                    f"fingerprints but this run uses "
                    f"{'orbit' if self.orbit else 'min-over-P'} "
                    "(TLA_RAFT_ORBIT) — the two cannot share a visited "
                    "store; resume with the matching setting"
                )
            if d != depth + 1:
                raise ValueError(
                    f"delta log gap: expected level {depth + 1}, found "
                    f"level {d} ({f})"
                )
            pidx = z["pidx"].astype(np.int64)
            slot = z["slot"].astype(np.int64)
            payload_np = pidx * K + slot
            cap = max(_pow2(n_new), 4 * self.chunk)
            new_payload = _pad_axis0(jnp.asarray(payload_np, I64), cap)
            frontier, _bads, _ns, _sl, _parent = self._materialize_grow(
                frontier, new_payload, n_new,
                pay_np=payload_np if self.host_store is not None else None,
            )
            del _parent  # the replay keeps only the new frontier alive
            n_f = n_new
            if self.host_store is not None:
                self.host_store.insert(z["fps"])
            else:
                fps_parts.append(z["fps"])
            trace_levels.append((pidx, slot))
            level_sizes.append(n_new)
            mult_per_slot = mult_per_slot + z["mult"]
            depth = d
        distinct = int(sum(level_sizes))
        if self.host_store is not None:
            visited = jnp.full((64,), SENT, U64)
        elif self.use_hashstore and self._tier_on():
            # tiered resume: the dumped slab holds only the HOT tier
            # (its count deliberately mismatches distinct), so the
            # replayed per-level fps re-tier from scratch — whole
            # levels demote together, making the rebuilt generations
            # DISJOINT and the tier total exactly the distinct count
            parts = [np.asarray(p, np.uint64) for p in fps_parts]
            if visited_base is not None:
                vb = np.asarray(visited_base, np.uint64)
                parts.insert(0, vb[vb != SENT])
            hot = self.tiered.rebuild(
                list(enumerate(parts)),
                hot_slots=self.tiered.hot_slot_budget(),
            )
            self.hstore = hashstore.DeviceHashStore.from_fps(hot)
            total = self.hstore.count + self.tiered.spilled_distinct()
            if total != distinct:
                raise ValueError(
                    f"tiered resume rebuilt {total} distinct "
                    f"fingerprints across {1 + len(self.tiered.gens)} "
                    f"tier(s) for {distinct} recorded states — corrupt "
                    "or mixed log"
                )
            visited = jnp.full((64,), SENT, U64)
        elif self.use_hashstore:
            # slab checkpoint fast path: the dumped slab IS the visited
            # set at the resume depth — one device_put instead of a
            # host-side rebuild.  Any mismatch (depth, count, fp def,
            # version) falls back to rebuilding from the replayed fps.
            self.hstore = hashstore.DeviceHashStore.load(
                os.path.join(ckdir, "hslab.npz"), depth, distinct,
                int(self.orbit),
            )
            if self.hstore is None:
                parts = [np.asarray(p, np.uint64) for p in fps_parts]
                if visited_base is not None:
                    parts.insert(0, np.asarray(visited_base, np.uint64))
                allf = (
                    np.concatenate(parts) if parts
                    else np.empty(0, np.uint64)
                )
                self.hstore = hashstore.DeviceHashStore.from_fps(allf)
            if self.hstore.count != distinct:
                raise ValueError(
                    f"hash-store resume rebuilt {self.hstore.count} "
                    f"distinct fingerprints for {distinct} recorded "
                    "states — corrupt or mixed log"
                )
            visited = jnp.full((64,), SENT, U64)
        else:
            new_fp_count = int(sum(len(p) for p in fps_parts))
            parts_dev = (
                [jnp.asarray(np.concatenate(fps_parts), U64)] if fps_parts else []
            )
            if visited_base is not None:
                parts_dev.insert(0, visited_base)
                pad_to = _cap4(distinct + 1) - new_fp_count - visited_base.shape[0]
            else:
                pad_to = _cap4(distinct + 1) - new_fp_count
            if pad_to > 0:
                parts_dev.append(jnp.full((pad_to,), SENT, U64))
            visited = jnp.sort(jnp.concatenate(parts_dev))[: _cap4(distinct + 1)]
        return dict(
            frontier=frontier,
            visited=visited,
            n_f=n_f,
            distinct=distinct,
            generated=int(mult_per_slot.sum()),
            depth=depth,
            level_sizes=level_sizes,
            trace_levels=trace_levels,
            mult_per_slot=mult_per_slot,
        )

    def _save_checkpoint(self, path, frontier, visited, n_f, distinct,
                         generated, depth, level_sizes, trace_levels,
                         mult_per_slot):
        if self.use_hashstore and self.hstore is not None:
            # the run's visited set lives in the hash slab; the monolith
            # format pins a SORTED array (it seeds host stores and
            # sorted-mode resumes), so derive it from the live slots
            # graftlint: waive[GL006] — one slab fetch per monolith save
            vb = np.asarray(jax.device_get(self.hstore.slab))
            vb = vb[vb != SENT]
            if self._tier_active():
                # the monolith's visited array IS the fingerprint set:
                # fold the demoted generations back in (the hot slab
                # alone is only the top tier)
                vb = np.union1d(vb, self.tiered.all_fps())
            vb = np.sort(np.unique(vb)) if len(vb) else vb
            pad = _cap4(len(vb) + 1) - len(vb)
            visited = np.concatenate([vb, np.full(pad, SENT)])
        arrs = {f"st_{k}": np.asarray(v) for k, v in frontier._asdict().items()}
        for i, (p, s) in enumerate(trace_levels):
            arrs[f"trace_p{i}"] = p
            arrs[f"trace_s{i}"] = s
        payload = dict(
            visited=np.asarray(visited),
            mult_per_slot=mult_per_slot,
            meta=np.asarray([n_f, distinct, generated, depth], np.int64),
            fp_def=np.asarray([int(self.orbit)], np.int64),
            level_sizes=np.asarray(level_sizes, np.int64),
            n_trace=np.asarray([len(trace_levels)], np.int64),
            **arrs,
        )
        # zlib on multi-GB frontiers costs ~a minute of host time per
        # level; past 256 MB the disk is cheaper than the CPU
        total = sum(a.nbytes for a in payload.values())
        resilience.commit_npz(
            os.path.dirname(os.path.abspath(path)),
            os.path.basename(path),
            payload,
            kind="monolith",
            depth=depth,
            run_fp=self._run_fp,
            compressed=total < (256 << 20),
        )

    # -- tiered visited store (store/tiered.py) ---------------------------

    def _tier_on(self) -> bool:
        """Tiering configured: a device budget bounds the hot slab."""
        return (
            self.tiered is not None and self.use_hashstore
            and self.host_store is None
        )

    def _tier_active(self) -> bool:
        """At least one generation demoted: level tails must probe."""
        return self._tier_on() and self.tiered.active

    def _sieve_ready(self) -> bool:
        """The spill sieve covers every demoted fingerprint: fused
        levels may rely on zero-hit = provably-clean."""
        return (
            self.sieve_enabled and self.sieve_governor.armed
            and self._tier_active()
            and self.tiered.spill_sieve is not None
        )

    def _sieve_operand(self):
        """The fused programs' sieve operand: the spill sieve's device
        word image, refreshed exactly when the host filter changed (a
        demotion), else the cached 1-word all-miss sentinel — ONE
        traced operand serves both regimes (ops/sieve.py), and jit
        retraces only when the filter SHAPE changes (it never does:
        the filter is allocated at full size on first demotion)."""
        if not self._sieve_ready():
            if self._dev_sieve_empty is None:
                self._dev_sieve_empty = graft_sieve.empty_device_sieve()
            return self._dev_sieve_empty
        sv = self.tiered.spill_sieve
        if self._dev_sieve is None or self._dev_sieve_ver != sv.version:
            self._dev_sieve = jnp.asarray(sv.words)
            self._dev_sieve_ver = sv.version
            graft_obs.sieve_refresh(
                len(self.tiered.gens), len(sv.words), sv.n_added,
                sv.fp_rate(),
            )
            # live-HBM gauge: the filter image is a long-lived buffer
            graft_obs.buffer("sieve", sv.nbytes)
        return self._dev_sieve

    def _demote_generation(self, depth: int, expected: int = 0) -> None:
        """Flush the hot slab into one warm generation and restart hot.

        The restart slab is sized to SEAT the in-flight level's
        expected fresh set — even past the budget: the device budget
        bounds the store RESIDENT between levels (that is what makes
        |visited| storage-bounded), while one level's insert set is a
        transient working set exactly like the frontier is, and the
        between-level demote drains any soft overshoot right after the
        level commits."""
        # one slab fetch per demotion: the rare budget-crossing event,
        # same deliberate-sync class as the slab dump / degrade fetches
        # graftlint: waive[GL006] — demotion's one deliberate slab fetch
        vb = np.asarray(jax.device_get(self.hstore.slab))
        self.tiered.demote(vb, depth=depth)
        want = hashstore.slab_rows(max(2 * max(expected, 1),
                                       hashstore.MIN_CAP // 2))
        if not self.tiered.slab_fits(want):
            soft = hashstore.slab_rows(max(expected, 1))
            want = max(min(want, soft), hashstore.MIN_CAP)
        self.hstore = hashstore.DeviceHashStore(cap=want)
        self._hs_pending = None
        print(
            f"[tiered] hot slab demoted to generation "
            f"{self.tiered.gens[-1].gid if self.tiered.gens else '-'} "
            f"at level {depth} ({len(vb[vb != SENT])} fps spilled, "
            f"{self.tiered.spilled_distinct()} total across "
            f"{len(self.tiered.gens)} gen(s)); hot restarts at "
            f"{self.hstore.cap} slots"
            + ("" if self.tiered.slab_fits(self.hstore.cap) else
               " (soft over-budget: one level's fresh set exceeds the "
               "hot budget; drained again at the next level boundary)"),
            file=sys.stderr,
        )

    def _slab_grow_or_demote(self, depth: int, expected: int = 0,
                             min_cap: int | None = None) -> str:
        """The tier-aware form of ``hstore.grow()``: grow while the
        grown slab still fits the device budget, DEMOTE a generation
        otherwise ("demote, then redo" where the untiered path would
        grow or die).  A demotion only helps while the slab has content
        to flush — an (almost) empty slab that still overflows means
        ONE level's fresh set exceeds the budget, and the level must be
        seated transiently (soft overshoot, drained at the next level
        boundary) or it would redo forever.  Returns "grew" or
        "demoted"; grow failures propagate so callers keep their
        degrade-to-sorted ladder."""
        want = max(self.hstore.cap * 2, min_cap or 0)
        want = 1 << (want - 1).bit_length()
        if self._tier_on() and not self.tiered.slab_fits(want):
            if self.hstore.count > 0:
                self._demote_generation(depth, expected=expected)
                return "demoted"
            print(
                f"[tiered] level {depth}: fresh set exceeds the hot "
                f"budget even after demotion — seating it transiently "
                f"at {want} slots (drained at the level boundary)",
                file=sys.stderr,
            )
        self.hstore.grow(min_cap=min_cap)
        return "grew"

    def _tier_drain(self, depth: int, n_next: int) -> None:
        """Between-level demotion check, run at the loop top: drains a
        slab that sits over the budget (a transient soft-seat, or the
        MIN_CAP floor under a sub-minimum budget) or whose next growth
        would bust it.  The only drain site the superstep windows have
        — their commit path adopts without the staged path's
        between-level grow — and a no-op while the hot slab can keep
        growing inside the budget."""
        if not self._tier_on() or self.hstore.count == 0:
            return
        over = not self.tiered.slab_fits(self.hstore.cap)
        grow_needed = self.hstore.need_grow(extra=2 * max(n_next, 1))
        grow_busts = not self.tiered.slab_fits(self.hstore.cap * 2)
        if over or (grow_needed and grow_busts):
            self._demote_generation(depth, expected=2 * max(n_next, 1))

    def _tier_reserve(self, entries: int) -> None:
        """Budget-clamped ``hstore.reserve``: never presize the hot
        slab past the device budget (the overflow path demotes when
        the level actually needs the room)."""
        if self._tier_on():
            cap_e = self.tiered.max_hot_entries
            if cap_e:
                entries = min(entries, cap_e)
        self.hstore.reserve(int(entries))

    def _tier_filter_level(self, depth: int, n_new: int, fps_np,
                           new_frontier, cap_out: int):
        """The level-tail generation probe + row compaction.

        ``fps_np`` are the level's kernel-fresh fingerprints (hot-slab
        verdicts); revisits of demoted generations among them are
        dropped from the already-materialized frontier with ONE small
        device program (store.tiered.drop_rows), keeping counts
        bit-identical to the uncapped run.  The hit fingerprints stay
        in the hot slab (the fused probe re-inserted them) — that is
        the re-heat, so the next revisit resolves on device.  Returns
        ``(n_keep, keep_mask | None, new_frontier)``."""
        hits = self.tiered.probe(fps_np[:n_new], level=depth + 1)
        n_hit = int(hits.sum())
        if not n_hit:
            return n_new, None, new_frontier
        self.tiered.stats["reheats"] += n_hit
        keep = ~hits
        n_keep = n_new - n_hit
        if n_keep:
            keep_dev = jnp.asarray(
                np.concatenate([
                    keep, np.zeros(cap_out - n_new, bool),
                ])
            )
            new_frontier = graft_tiered.drop_rows(
                new_frontier, keep_dev, jnp.asarray(n_keep, I64)
            )
            graft_sanitize.note_dispatch("tiered.compact")
        return n_keep, keep, new_frontier

    def _degrade_hashstore(self, why) -> jnp.ndarray:
        """Hash-store grow failed (device OOM or an injected
        ``hashstore.grow`` fault): fall back to the sort-based visited
        path MID-RUN — the automatic form of the ``--no-hashstore``
        lever — instead of dying.  The slab's live slots hold exactly
        the visited set, so one fetch + sort rebuilds the sorted store
        losslessly and the run continues with identical counts."""
        print(
            f"[resilience] hash-store grow failed ({why}); degrading to "
            "the sort-based visited path (--no-hashstore equivalent) "
            "for the rest of the run",
            file=sys.stderr,
        )
        self.hashstore_degraded = f"{type(why).__name__}: {why}"
        # graftlint: waive[GL006] — one-time degradation fetch
        vb = np.asarray(jax.device_get(self.hstore.slab))
        vb = vb[vb != SENT]
        if self._tier_active():
            # the sorted fallback must hold the WHOLE union: fold every
            # demoted generation back in (host-side; the degraded run
            # is already off the fast path, correctness first)
            vb = np.union1d(vb, self.tiered.all_fps())
            self.tiered = None
        vb = np.sort(np.unique(vb)) if len(vb) else vb
        pad = _cap4(len(vb) + 1) - len(vb)
        visited = jnp.concatenate(
            [jnp.asarray(vb), jnp.full((pad,), SENT, U64)]
        )
        self.use_hashstore = False
        self.hstore = None
        self._hs_pending = None
        # the fused level program IS a hash-store consumer — the sorted
        # path runs staged for the rest of the run (and with it the
        # multi-level superstep driver, which wraps the fused body)
        self.megakernel = False
        self.superstep_span = 1
        return visited

    def _check_fp_def(self, fp_def: int, path: str) -> None:
        """Refuse to mix fingerprint definitions in one visited store."""
        if fp_def != int(self.orbit):
            raise ValueError(
                f"fingerprint-definition mismatch: {path} was written "
                f"with {'orbit' if fp_def else 'min-over-P'} fingerprints "
                f"but this run uses "
                f"{'orbit' if self.orbit else 'min-over-P'} "
                "(TLA_RAFT_ORBIT) — resume with the matching setting"
            )

    def _seed_host_store(self, visited):
        """Insert a visited array's real (non-SENT) fps into the store.

        Sliced inserts keep the store's spill budget in force; `visited`
        should be host-side (numpy) — pass ``device_visited=False`` to
        ``_load_checkpoint`` so multi-GB snapshots never round-trip
        through the device on a host-store resume.
        """
        vb = np.asarray(visited)
        vb = vb[vb != np.uint64(0xFFFFFFFFFFFFFFFF)]
        for i in range(0, len(vb), 1 << 22):
            self.host_store.insert(vb[i : i + (1 << 22)])

    @staticmethod
    def _load_checkpoint(path, device_visited=True):
        z = np.load(path)
        fields = {k[3:] for k in z.files if k.startswith("st_")}
        if fields != set(Frontier._fields):
            raise ValueError(
                f"incompatible checkpoint format at {path}: has fields "
                f"{sorted(fields)}, this build expects "
                f"{sorted(Frontier._fields)} (written by an older engine?)"
            )
        frontier = Frontier(
            **{k[3:]: jnp.asarray(z[k]) for k in z.files if k.startswith("st_")}
        )
        n_f, distinct, generated, depth = (int(x) for x in z["meta"])
        trace_levels = [
            (z[f"trace_p{i}"], z[f"trace_s{i}"]) for i in range(int(z["n_trace"][0]))
        ]
        return dict(
            fp_def=int(z["fp_def"][0]) if "fp_def" in z.files else 0,
            frontier=frontier,
            mult_per_slot=np.asarray(z["mult_per_slot"]),
            # host-store resumes read the (potentially multi-GB) visited
            # snapshot host-side only — it seeds the external store and
            # must not ride along on the device through the replay
            visited=jnp.asarray(z["visited"]) if device_visited else z["visited"],
            n_f=n_f,
            distinct=distinct,
            generated=generated,
            depth=depth,
            level_sizes=list(int(x) for x in z["level_sizes"]),
            trace_levels=trace_levels,
        )

    # -- the main loop -----------------------------------------------------

    def _expand_level(self, frontier: Frontier, n_f, visited, ckdir=None,
                      depth=None):
        """Expand all chunks of one level.

        Dispatches between the two dedup tiers: with an external host
        store the level runs per-group host filtering (device memory
        O(group) — the fix for the round-2 level-25 HBM ceiling, where
        the ungrouped level-wide candidate concat OOMed at an 11.1M-state
        frontier); with a device-resident visited table the level-wide
        on-device dedup is cheaper.  ``ckdir``/``depth`` enable
        intra-level (per-group) partial checkpoints on the host path.
        """
        if self.host_store is not None:
            return self._expand_level_host(frontier, n_f, ckdir, depth)
        return self._expand_level_device(frontier, n_f, visited)

    def _expand_level_device(self, frontier: Frontier, n_f, visited):
        """Expand all chunks; returns device arrays + one fused host fetch.

        The frontier is device-resident in compact form; chunks are
        carved out with dynamic slices (the frontier capacity is always a
        multiple of the chunk size).
        """
        n_f_dev = jnp.asarray(n_f, I64)
        use_hs = self.use_hashstore
        hslab = self.hstore.slab if use_hs else None

        def gfilter(av, af, ap):
            """Visited filter for one group: hash probe or searchsorted."""
            graft_sanitize.note_dispatch("device.gfilter")
            if use_hs:
                return _group_filter_hash(av, af, ap, hslab, self.cap_g)
            return _group_filter(av, af, ap, visited, self.cap_g)

        cvs, cfs, cps = [], [], []  # pending (unfiltered) chunk outputs
        gvs, gfs, gps = [], [], []  # filtered+compacted group outputs
        svs, sfs, sps = [], [], []  # ungrouped span outputs ([G*cap_x] each)
        mult_acc = jnp.zeros((self.K,), I64)
        abort_at = BIG
        overflow = jnp.zeros((), bool)
        overflow_g = jnp.zeros((), bool)
        G = self.G  # chunks per visited-filter group
        n_chunks = -(-max(n_f, 1) // self.chunk)
        synced = 0  # chunks dispatched since the last queue drain
        # Group-wise compaction bounds the level-wide candidate concat to
        # n_groups*cap_g lanes instead of n_chunks*cap_x, but ONLY
        # because the group filter drops candidates already in the
        # device visited table (deep levels are <=50% fresh; it does NO
        # intra-group dedup).  It stays off at small frontiers (the
        # level-wide sort is tiny and new/parent ratios up to ~2.5 would
        # overflow cap_g) — and the threshold matters for throughput: the
        # filter's searchsorted against the visited store costs ~0.7 s
        # per 1M-lane group on the v5e (binary search = 22 rounds of
        # random gathers; measured round 5), so levels small enough for
        # the level-wide sort to fit run ~25% faster without grouping.
        # 256 chunks * cap_x 64k * 24 B = ~1.2 GB of sort operands —
        # comfortably inside one chip's HBM next to frontier + visited.
        grouping = n_chunks > 16 * G

        def flush_group():
            while len(cvs) < G:  # pad the group to its fixed width
                cvs.append(jnp.full((self.cap_x,), SENT, U64))
                cfs.append(jnp.full((self.cap_x,), SENT, U64))
                cps.append(jnp.full((self.cap_x,), -1, I64))
            gv, gf, gp, ovf = gfilter(
                jnp.concatenate(cvs), jnp.concatenate(cfs),
                jnp.concatenate(cps),
            )
            gvs.append(gv)
            gfs.append(gf)
            gps.append(gp)
            cvs.clear()
            cfs.clear()
            cps.clear()
            return ovf

        # full G-chunk groups go through the scanned span program (one
        # dispatch per G chunks instead of ~13 per chunk); the tail — and
        # every test-scale chunk size — keeps the per-chunk path.  On
        # grouped (deep) levels the span output feeds the group filter
        # directly; on mid-size levels it joins the level-wide concat as
        # G per-chunk-shaped entries.
        start0 = 0
        if (self.chunk >= self.span_min_chunk and n_chunks >= G
                and not self.orbit):
            span_rows = G * self.chunk
            # grouped ultra-deep levels with the hash store: the whole
            # per-group staged chain (span expand + visited pre-filter
            # + compact) fuses into ONE program per group under the
            # megakernel flag — the regime the whole-level fusion
            # deliberately leaves staged (the pre-filter bounds the
            # candidate working set there)
            gfused = (
                grouping and use_hs
                and getattr(self, "_expand_group_gfused", None)
                is not None
            )
            for g in range(n_chunks // G):
                b = jnp.asarray(g * span_rows, I64)
                if gfused:
                    (gv, gf, gp, mult_s, ab_s, ovf_s,
                     ovf_g) = self._expand_group_gfused(
                        frontier, b, b, n_f_dev, hslab,
                        cap_g=self.cap_g,
                    )
                    graft_sanitize.note_dispatch("device.span_gfused")
                    mult_acc = mult_acc + mult_s
                    abort_at = jnp.minimum(abort_at, ab_s)
                    overflow = overflow | ovf_s
                    overflow_g = overflow_g | ovf_g
                    gvs.append(gv)
                    gfs.append(gf)
                    gps.append(gp)
                    synced += 1
                    if synced >= self.sync_every:
                        jax.device_get(abort_at)
                        synced = 0
                    continue
                cvs_s, cfs_s, cps_s, mult_s, ab_s, ovf_s = self._expand_span(
                    frontier, b, b, n_f_dev
                )
                graft_sanitize.note_dispatch("device.span")
                mult_acc = mult_acc + mult_s
                abort_at = jnp.minimum(abort_at, ab_s)
                overflow = overflow | ovf_s
                if grouping:
                    gv, gf, gp, ovf_g = gfilter(
                        cvs_s.reshape(-1), cfs_s.reshape(-1),
                        cps_s.reshape(-1),
                    )
                    overflow_g = overflow_g | ovf_g
                    gvs.append(gv)
                    gfs.append(gf)
                    gps.append(gp)
                else:
                    svs.append(cvs_s.reshape(-1))
                    sfs.append(cfs_s.reshape(-1))
                    sps.append(cps_s.reshape(-1))
                synced += 1
                if synced >= self.sync_every:
                    jax.device_get(abort_at)
                    synced = 0
            start0 = (n_chunks // G) * span_rows

        for start in range(start0, max(n_f, 1), self.chunk):
            part_f = jax.tree.map(
                lambda x: jax.lax.dynamic_slice_in_dim(x, start, self.chunk),
                frontier,
            )
            cv, cf, cp, mult_slots, ab_at, ovf = self._expand_chunk(
                part_f,
                jnp.asarray(start, I64),
                n_f_dev,
            )
            graft_sanitize.note_dispatch("device.chunk")
            cvs.append(cv)
            cfs.append(cf)
            cps.append(cp)
            mult_acc = mult_acc + mult_slots
            abort_at = jnp.minimum(abort_at, ab_at)
            overflow = overflow | ovf
            if grouping and len(cvs) == G:
                overflow_g = overflow_g | flush_group()
            # bound the async dispatch queue: queued chunk programs each
            # hold their input slices and outputs; a scalar drain every
            # sync_every chunks caps that working set (the round-1
            # regression was per-chunk fetches of whole result arrays
            # at 256-state chunks, not the drain itself)
            synced += 1
            if synced >= self.sync_every:
                jax.device_get(abort_at)
                synced = 0
        if grouping and cvs:
            overflow_g = overflow_g | flush_group()
        if grouping:
            lvs, lfs, lps = gvs, gfs, gps
            n_lanes = len(gvs) * self.cap_g
        else:
            # span outputs are [G*cap_x]-wide entries, chunk outputs
            # [cap_x]; lane order is irrelevant to the level dedup
            # (payloads are unique per lane, the sort is global)
            lvs = svs + cvs
            lfs = sfs + cfs
            lps = sps + cps
            n_lanes = (len(svs) * G + len(cvs)) * self.cap_x
        # pad the level-dedup input to a half-step-quantized lane count
        # ({2^k, 3*2^(k-1)}) so its sort program compiles O(log) times per
        # run, not once per level — and a just-over-pow2 level (the common
        # case after a 1.5x cap_x growth) pays a 12% pad, not 95%
        pad = _cap_steps(max(n_lanes, 1)) - n_lanes
        if pad:
            lvs.append(jnp.full((pad,), SENT, U64))
            lfs.append(jnp.full((pad,), SENT, U64))
            lps.append(jnp.full((pad,), -1, I64))
        # the level-dedup sort shape: part of the sanitizer's per-level
        # shape signature (a new lane count legitimately recompiles it)
        self._san_lanes = n_lanes + pad
        if use_hs:
            # fused probe-and-insert: uniqueness + visited filter + store
            # update in one O(lanes) program — the slab comes back as a
            # PENDING update so the overflow-redo loop can discard it
            (n_new_dev, new_fps, new_payload, slab2,
             ovf_h) = _level_dedup_hash(
                jnp.concatenate(lvs), jnp.concatenate(lfs),
                jnp.concatenate(lps), hslab,
            )
            graft_sanitize.note_dispatch("device.dedup_hash")
            self._hs_pending = slab2
        else:
            ovf_h = jnp.zeros((), bool)
            n_new_dev, new_fps, new_payload = _level_dedup(
                jnp.concatenate(lvs), jnp.concatenate(lfs),
                jnp.concatenate(lps), visited,
            )
            graft_sanitize.note_dispatch("device.dedup")
        # ONE host sync for the level's control state
        n_new, ab, ovf, ovf_g, ovf_hs, mult_np = jax.device_get(
            (n_new_dev, abort_at, overflow, overflow_g, ovf_h, mult_acc)
        )
        return (
            int(n_new), new_fps, new_payload, int(ab), bool(ovf), bool(ovf_g),
            bool(ovf_hs), mult_np,
        )

    # -- external-store path: per-group host filtering ---------------------
    #
    # The device never holds more than one group (G chunks) of candidates:
    # each group is deduped on device (min-(fp_full, payload) representative
    # per view fp, ``_group_unique``), its unique candidates are fetched,
    # and the level-global choice + visited filter run host-side — a numpy
    # lexsort with exactly ``_level_dedup``'s ordering, then one batched
    # ``host_store.insert``.  Device memory is O(G * cap_x) regardless of
    # frontier size, which removes the round-2 ceiling (11.1M-state level
    # 25 OOMed on the level-wide concat).  Per-group fetches double as
    # dispatch-queue drains.
    #
    # Groups are also the unit of intra-level durability: each completed
    # group's unique candidates land in ``partial_####_#####.npz`` before
    # the next group starts, so a mid-level crash costs one group, not the
    # level (TLC's mid-level ``states/`` queue spill analog; the level-23
    # corruption saga in BASELINE.md is the motivation).  Partials are
    # self-validating (level, chunk, G, K, n_f in the meta; cap_x is
    # recorded but deliberately not matched — see _load_partials) — BFS
    # determinism makes a matching partial's contents exact.

    def _expand_level_host(self, frontier, n_f, ckdir=None, depth=None):
        # the host path's frontier is a LIST of segment buffers (len >= 1;
        # see _materialize_segs); chunks never straddle segments (segment
        # sizes are chunk multiples by construction)
        seg_len = _seg_rows(frontier[0])
        n_f_dev = jnp.asarray(n_f, I64)
        G = self.G
        n_chunks = -(-max(n_f, 1) // self.chunk)
        n_groups = -(-n_chunks // G)
        level = (depth + 1) if depth is not None else None
        hv, hf, hp = [], [], []  # per-group unique candidates, host-side
        mult_np = np.zeros((self.K,), np.int64)
        saved = self._load_partials(ckdir, level, n_f) if ckdir else {}
        # host-paged segments transit through a one-entry cache: the chunk
        # walk is ascending, so when it enters segment sj+1 the device
        # copy of sj drops (the numpy copy in ``frontier`` stays)
        page = {"j": -1, "dev": None}

        def seg_dev(sj):
            s = frontier[sj]
            if not isinstance(s, _HostSeg):
                return s
            if page["j"] != sj:
                page["j"], page["dev"] = sj, self._seg_to_dev(s)
            return page["dev"]

        # async group window (engine/pipeline.py): group gi's padded
        # fetch starts with copy_to_host_async and completes — through
        # the LEDGERED device_get — only after group gi+1..gi+W have
        # been dispatched, so the device expands the next groups while
        # the previous ones stream over the host link
        # and the host tail (slice/append + partial save) runs.  All
        # dispatch stays on this (the main) thread; window 0 == the
        # serial fetch-after-dispatch chain, bit-identically.
        win = graft_pipeline.AsyncFetchWindow(
            self.pipeline_window if self.pipeline else 0
        )
        stop: dict = {}

        def consume(gi_, host):
            nonlocal mult_np
            n_u, ab, ovf_h, mult_g, gv_np, gf_np, gp_np = host
            if stop:
                # a prior group already aborted/overflowed: drop this
                # group's mult too — the serial chain never expands past
                # the aborting group, and the discard() path likewise
                # contributes nothing
                return
            mult_np += np.asarray(mult_g, np.int64)
            if int(ab) < n_f or bool(ovf_h):
                # abort (split-brain) or cap_x overflow: nothing reached
                # the store yet, so run() can report the trace / grow the
                # budget and redo the level cleanly.  Completed groups'
                # partials survive the redo — their candidate sets are
                # budget-independent (see _load_partials)
                stop["ab"], stop["ovf"] = int(ab), bool(ovf_h)
                return
            n_u = int(n_u)
            gv_c = np.asarray(gv_np[:n_u])
            gf_c = np.asarray(gf_np[:n_u])
            gp_c = np.asarray(gp_np[:n_u])
            hv.append(gv_c)
            hf.append(gf_c)
            hp.append(gp_c)
            if ckdir:
                self._save_partial(
                    ckdir, level, gi_, gv_c, gf_c, gp_c,
                    np.asarray(mult_g, np.int64), n_f,
                )

        for gi in range(n_groups):
            if stop:
                break
            if gi in saved:
                z = saved[gi]
                hv.append(z["hv"])
                hf.append(z["hf"])
                hp.append(z["hp"])
                mult_np += z["mult"]
                continue
            mult_acc = jnp.zeros((self.K,), I64)
            abort_at = BIG
            overflow = jnp.zeros((), bool)
            # a FULL group whose G chunks sit inside one segment runs as
            # one scanned span program (one dispatch instead of ~13*G);
            # the tail group and small chunks keep the per-chunk path
            g_lo, g_hi = gi * G * self.chunk, (gi + 1) * G * self.chunk
            span_ok = (
                self.chunk >= self.span_min_chunk
                and not self.orbit
                and (gi + 1) * G <= n_chunks
                and g_lo // seg_len == (g_hi - 1) // seg_len
            )
            fused = None
            if span_ok and self._mega_flag:
                # megakernel slice of the host-store path: span expand +
                # intra-group dedup in ONE program per group — the level
                # is then one dispatch + one fetch per group up to the
                # host-store probe
                sj, off = divmod(g_lo, seg_len)
                (n_u_dev, gv, gf, gp, mult_acc, abort_at,
                 overflow) = self._expand_group_fused(
                    seg_dev(sj), jnp.asarray(off, I64),
                    jnp.asarray(g_lo, I64), n_f_dev,
                )
                graft_sanitize.note_dispatch("host.group_fused")
                fused = True
            elif span_ok:
                sj, off = divmod(g_lo, seg_len)
                cvs_s, cfs_s, cps_s, mult_acc, abort_at, overflow = (
                    self._expand_span(
                        seg_dev(sj), jnp.asarray(off, I64),
                        jnp.asarray(g_lo, I64), n_f_dev,
                    )
                )
                graft_sanitize.note_dispatch("host.span")
                cat_v, cat_f, cat_p = (
                    cvs_s.reshape(-1), cfs_s.reshape(-1), cps_s.reshape(-1)
                )
            else:
                cvs, cfs, cps = [], [], []
                synced = 0
                for ci in range(gi * G, min((gi + 1) * G, n_chunks)):
                    sj, off = divmod(ci * self.chunk, seg_len)
                    part_f = jax.tree.map(
                        lambda x: jax.lax.dynamic_slice_in_dim(
                            x, off, self.chunk
                        ),
                        seg_dev(sj),
                    )
                    cv, cf, cp, mult_slots, ab_at, ovf = self._expand_chunk(
                        part_f, jnp.asarray(ci * self.chunk, I64), n_f_dev
                    )
                    graft_sanitize.note_dispatch("host.chunk")
                    cvs.append(cv)
                    cfs.append(cf)
                    cps.append(cp)
                    mult_acc = mult_acc + mult_slots
                    abort_at = jnp.minimum(abort_at, ab_at)
                    overflow = overflow | ovf
                    synced += 1
                    if synced >= self.sync_every:
                        jax.device_get(abort_at)
                        synced = 0
                while len(cvs) < G:  # pad the group to its fixed width
                    cvs.append(jnp.full((self.cap_x,), SENT, U64))
                    cfs.append(jnp.full((self.cap_x,), SENT, U64))
                    cps.append(jnp.full((self.cap_x,), -1, I64))
                cat_v = jnp.concatenate(cvs)
                cat_f = jnp.concatenate(cfs)
                cat_p = jnp.concatenate(cps)
            if fused is None:
                n_u_dev, gv, gf, gp = _group_unique(cat_v, cat_f, cat_p)
                graft_sanitize.note_dispatch("host.unique")
            # submit the FIXED-shape padded buffers to the fetch window
            # (host-side slicing: a device-side gv[:n_u] slice would
            # compile a fresh tiny program per distinct n_u — one compile
            # per group — for a bandwidth saving of ~6% of the fetch)
            win.submit(
                (n_u_dev, abort_at, overflow, mult_acc, gv, gf, gp),
                functools.partial(consume, gi),
            )
        # ---- window drain: the LEVEL BOUNDARY -------------------------
        # every group's candidates must be on the host before the level-
        # global representative choice and the store insert below — a
        # store insert with the window still open would let half a
        # level's candidates filter against the other half's inserts
        if not stop:
            win.drain()
        if stop:
            win.discard()  # complete in-flight fetches, ledger balanced
            return (0, None, None, stop["ab"], stop["ovf"], False, False,
                    mult_np)
        # ---- level-global representative choice + visited filter --------
        av = np.concatenate(hv) if hv else np.empty(0, np.uint64)
        af = np.concatenate(hf) if hf else np.empty(0, np.uint64)
        ap = np.concatenate(hp) if hp else np.empty(0, np.int64)
        order = np.lexsort((ap, af, av))
        sv, sp = av[order], ap[order]
        first = np.ones(len(sv), bool)
        first[1:] = sv[1:] != sv[:-1]
        uniq_v, uniq_p = sv[first], sp[first]
        t_probe = time.monotonic()
        is_new = self.host_store.insert(uniq_v)
        if getattr(self.host_store, "num_runs", 0):
            # the external store holds spilled (disk) runs: this
            # level's membership verdicts probed the warm/cold tiers —
            # publish the non-overlapped wait (the group candidates
            # themselves streamed through the async fetch window, so
            # the device expanded ahead of this probe)
            graft_obs.tier_probe(
                (depth + 1) if depth is not None else 0, len(uniq_v),
                int(len(uniq_v) - is_new.sum()),
                wait_s=time.monotonic() - t_probe,
            )
        new_fps = uniq_v[is_new]
        new_pay = uniq_p[is_new]
        # emit survivors in ASCENDING PAYLOAD order (payload = pidx*K+slot,
        # unique, so a plain argsort is deterministic): the delta record,
        # the trace spill and the frontier all share this order, and it is
        # what lets the segment-streamed materialize walk the parent
        # segments monotonically (the fps are no longer cv-sorted; nothing
        # downstream relied on that)
        o = np.argsort(new_pay)
        return (len(new_fps), np.ascontiguousarray(new_fps[o]),
                np.ascontiguousarray(new_pay[o]), int(BIG), False, False,
                False, mult_np)

    def _save_partial(self, ckdir, level, gi, hv, hf, hp, mult, n_f):
        resilience.commit_npz(
            ckdir,
            f"partial_{level:04d}_{gi:05d}.npz",
            dict(
                hv=hv, hf=hf, hp=hp, mult=mult,
                # meta[7]: fingerprint definition (0 = min-over-P,
                # 1 = orbit) — a partial's hv/hf are raw fingerprints and
                # must never be replayed into a run using the other
                # definition.  meta[8]: the async pipeline's in-flight
                # window at save time — INFORMATIONAL, never matched on
                # resume: partials commit in submission order, so a
                # crash mid-level loses at most this many trailing
                # groups (the recovery re-expands at most one window)
                meta=np.asarray(
                    [level, gi, self.chunk, self.cap_x, self.G, self.K,
                     n_f, int(self.orbit),
                     self.pipeline_window if self.pipeline else 0],
                    np.int64,
                ),
            ),
            kind="partial",
            depth=level,
            run_fp=self._run_fp,
        )

    def _load_partials(self, ckdir, level, n_f):
        """Completed-group partials for this level; stale ones are wiped.

        A partial is valid only if its meta matches the in-flight level
        exactly (a cap_x growth redo or a chunk-size change moves every
        group boundary).  Partials from other levels are leftovers of a
        crash between the delta save and the wipe — delete them.
        meta[8] (the async pipeline window, when present) is
        deliberately NOT matched: the window changes only how many
        trailing groups a crash can lose (consume order == submission
        order, so saved partials are always a clean prefix-with-holes
        of completed groups), never a completed group's contents —
        a resume may freely retune the window like chunk/cap_x."""
        import glob

        out = {}
        stale = []
        for f in sorted(glob.glob(os.path.join(ckdir, "partial_*.npz"))):
            try:
                z = np.load(f)
                meta = tuple(int(x) for x in z["meta"])
                # cap_x (meta[3]) deliberately does NOT participate in the
                # match: a saved group's candidate set is budget-
                # independent (its chunks passed the overflow check before
                # the save), so a cap_x-growth redo of the level keeps
                # every completed group instead of re-expanding it
                fp_def = meta[7] if len(meta) > 7 else 0
                want = (level, meta[1], self.chunk, self.G, self.K, n_f,
                        int(self.orbit))
                got = (meta[0], meta[1], meta[2], meta[4], meta[5], meta[6],
                       fp_def)
                if level is None or got != want:
                    stale.append(os.path.basename(f))
                    continue
                rec = dict(
                    hv=z["hv"], hf=z["hf"], hp=z["hp"],
                    mult=z["mult"].astype(np.int64),
                )
            except (OSError, ValueError, KeyError, EOFError,
                    zipfile.BadZipFile):
                # crash-truncated partial: the zip layer raises any of
                # these depending on where the write stopped
                stale.append(os.path.basename(f))
                continue
            out[meta[1]] = rec
        if stale:
            resilience.discard_artifacts(ckdir, stale)
        return out

    def _wipe_partials(self, ckdir):
        import glob

        resilience.discard_artifacts(
            ckdir,
            [os.path.basename(f)
             for f in glob.glob(os.path.join(ckdir, "partial_*.npz"))],
        )

    def run(
        self,
        max_depth: int | None = None,
        checkpoint_dir: str | None = None,
        checkpoint_every: int = 1,
        resume_from: str | None = None,
    ) -> CheckResult:
        self._audit_strikes = 0
        # the run's phases (init, levels, finish) and a level's host
        # plan or per-level path, as profiler spans: _run moves them on
        self._phase_spans = graft_obs.SpanStack()
        try:
            return self._run(
                max_depth=max_depth, checkpoint_dir=checkpoint_dir,
                checkpoint_every=checkpoint_every, resume_from=resume_from,
            )
        finally:
            if self._fpager is not None:
                # frontier segments are per-level transients: a finished
                # (or raised) run leaves none worth keeping — resume
                # rebuilds frontiers from the delta log
                try:
                    self._fpager.retire_all()
                except OSError:
                    pass  # a torn teardown is sweep_fsegs' problem
            if self.watchdog is not None:
                self.watchdog.disarm()
            if self._prewarmer is not None:
                # run over (done, raised, or preempted): give the almost-
                # finished tail a bounded grace to land in the persistent
                # compile cache, then drop the queued rest — nothing in
                # THIS process will use it, and a supervised relaunch
                # re-forecasts the same plan (a later run() on this
                # checker builds a fresh prewarmer via _submit_prewarm)
                self._prewarmer.join(10.0)
                self._prewarmer.shutdown()
            self._phase_spans.pop_to(0)

    def _run(
        self,
        max_depth: int | None = None,
        checkpoint_dir: str | None = None,
        checkpoint_every: int = 1,
        resume_from: str | None = None,
    ) -> CheckResult:
        cfg = self.cfg
        K = self.K
        t0 = time.monotonic()
        spans = self._phase_spans
        spans.pop_to(0)  # an audit rewind re-enters from the level loop
        spans.push("raft.check.init")

        if checkpoint_dir and checkpoint_every:
            import glob as _glob

            if resume_from is None and os.path.isdir(checkpoint_dir):
                # a killed earlier writer must not leak .tmp_* files
                # into a fresh run's directory (they waste disk and
                # shadow names; resume paths sweep via heal_log)
                resilience.sweep_tmp(checkpoint_dir)
            stale = _glob.glob(os.path.join(checkpoint_dir, "delta_*.npz"))
            has_base = os.path.exists(os.path.join(checkpoint_dir, "base.npz"))
            if resume_from is None and (stale or has_base):
                raise ValueError(
                    f"{checkpoint_dir} holds checkpoints from a previous "
                    "run; a fresh run would interleave two runs' logs into "
                    "one (silently wrong) replay chain — resume with "
                    "--recover or clear the directory"
                )
            if (
                resume_from is not None
                and os.path.isdir(resume_from)
                and os.path.abspath(resume_from) != os.path.abspath(checkpoint_dir)
                and (stale or has_base)
            ):
                raise ValueError(
                    f"resuming from {resume_from} but {checkpoint_dir} "
                    "already holds another run's checkpoints — the two "
                    "logs would interleave; clear it or checkpoint into "
                    "the resumed directory itself"
                )
            if (
                resume_from is not None
                and not os.path.isdir(resume_from)
                and os.path.abspath(resume_from)
                == os.path.abspath(os.path.join(checkpoint_dir, "base.npz"))
                and stale
            ):
                # resuming from the directory's own base monolith while it
                # already holds deltas would re-append a second chain on
                # top of the existing one (stale deeper deltas would then
                # replay with no gap error) — the directory itself is the
                # correct resume point
                raise ValueError(
                    f"{checkpoint_dir} holds delta checkpoints beyond its "
                    "base.npz; resume from the directory itself (delta "
                    "replay) instead of the base monolith, or clear the "
                    "deltas first"
                )
            if (
                resume_from is not None
                and not os.path.isdir(resume_from)
                and os.path.abspath(resume_from)
                != os.path.abspath(os.path.join(checkpoint_dir, "base.npz"))
            ):
                # resuming from a monolith while appending deltas: anchor
                # the delta chain by copying the monolith in as the base
                if stale:
                    raise ValueError(
                        f"{checkpoint_dir} already holds delta checkpoints; "
                        "resume from the directory itself instead of a "
                        "monolith file"
                    )
                import shutil

                os.makedirs(checkpoint_dir, exist_ok=True)
                shutil.copyfile(
                    resume_from, os.path.join(checkpoint_dir, "base.npz")
                )
                resilience.adopt_file(
                    checkpoint_dir, "base.npz", kind="base",
                    run_fp=self._run_fp,
                )
        # tiered visited store: the hot slab lives under a device-byte
        # budget; demotions spill whole generations to the checkpoint
        # directory (warm in host RAM, cold on disk — store/tiered.py)
        spill = (
            checkpoint_dir if (checkpoint_dir and checkpoint_every)
            else (resume_from if (
                resume_from and os.path.isdir(resume_from)
            ) else None)
        )
        if self.store_bytes and self.use_hashstore and (
            self.host_store is None
        ):
            self.tiered = graft_tiered.TieredVisitedStore(
                self.store_bytes, warm_bytes=self.warm_bytes,
                spill_dir=spill, run_fp=self._run_fp,
            )
            if spill:
                # stale generation files (a previous incarnation's, or
                # a crash mid-demotion) are noise: the delta log is the
                # source of truth and the resume rebuild re-commits a
                # fresh, disjoint set
                graft_tiered.sweep_gens(spill)
        if spill is not None:
            # spilled frontiers: host segments past TLA_RAFT_FSEG_BYTES
            # page through the warm tier (kind="fseg").  Orphans from a
            # crashed incarnation are per-level transients the delta
            # log supersedes — swept, never replayed
            graft_tiered.sweep_fsegs(spill)
            if self.fseg_host_bytes:
                self._fpager = graft_tiered.FrontierPager(
                    spill, run_fp=self._run_fp,
                )
        self._fseg_live = []
        self._fseg_retire = []
        if resume_from is not None:
            if os.path.isdir(resume_from):
                ck = self._resume_from_deltas(resume_from)
            else:
                ck = self._load_checkpoint(
                    resume_from,
                    device_visited=(
                        self.host_store is None and not self.use_hashstore
                    ),
                )
                self._check_fp_def(ck["fp_def"], resume_from)
                if self.host_store is not None:
                    # a monolith of a device-store run resumes onto the
                    # external tier: its visited array IS the fingerprint
                    # set, so it seeds the cleared store (same move as the
                    # base.npz path in _resume_from_deltas)
                    self.host_store.clear()
                    self._seed_host_store(ck.pop("visited"))
                    ck["visited"] = jnp.full((64,), SENT, U64)
                    ck["frontier"] = [ck["frontier"]]
                elif self.use_hashstore:
                    # a sorted-store monolith resumes onto the hash slab:
                    # its visited array is the fingerprint set — rebuild
                    # host-side (insert_np), one device_put of the slab.
                    # Under a tiered budget the monolith's set re-tiers:
                    # whatever exceeds the hot budget demotes up front.
                    vall = np.asarray(ck.pop("visited"))
                    vall = vall[vall != SENT]
                    if self._tier_on():
                        hot = self.tiered.rebuild(
                            [(ck["depth"], vall)],
                            hot_slots=self.tiered.hot_slot_budget(),
                        )
                        self.hstore = hashstore.DeviceHashStore.from_fps(
                            hot
                        )
                    else:
                        self.hstore = hashstore.DeviceHashStore.from_fps(
                            vall
                        )
                    ck["visited"] = jnp.full((64,), SENT, U64)
            frontier, visited = ck["frontier"], ck["visited"]
            n_f, distinct, generated = ck["n_f"], ck["distinct"], ck["generated"]
            depth, level_sizes, trace_levels = (
                ck["depth"], ck["level_sizes"], ck["trace_levels"],
            )
            mult_per_slot = ck["mult_per_slot"]
        else:
            st0 = init_batch(cfg, 1)
            n_f = 1
            fv, _ff = self._fp_states(st0)
            if self.host_store is not None:
                self.host_store.insert(np.asarray(fv.astype(U64)))
                visited = jnp.full((64,), SENT, U64)
            elif self.use_hashstore:
                self.hstore = hashstore.DeviceHashStore.from_fps(
                    np.asarray(jax.device_get(fv.astype(U64)))
                )
                visited = jnp.full((64,), SENT, U64)
            else:
                visited = jnp.sort(
                    jnp.concatenate([fv.astype(U64), jnp.full((63,), SENT, U64)])
                )
            distinct = 1
            generated = 0
            level_sizes = [1]
            depth = 0
            trace_levels = []
            mult_per_slot = np.zeros(K, np.int64)

            bad0 = int(
                jax.device_get(self._inv_scan(st0, jnp.asarray(1, I64)))
            )
            if bad0 >= 0:
                name0 = self._bad_invariant_name(st0, bad0)
                return CheckResult(
                    False, 1, 0, 0, (1,),
                    (
                        f"Invariant {name0} is violated",
                        self._trace(trace_levels, 0, 0),
                    ),
                )
            frontier, ovf0 = self._program(self._deflate)(st0)
            if bool(jax.device_get(ovf0.any())):
                raise RuntimeError(
                    f"initial state's message set exceeds cap_m={self.cap_m}"
                )
            if self.host_store is not None:
                frontier = [frontier]
        # frontier capacity must be a chunk multiple for dynamic slicing
        # (segment lists are chunk-aligned by construction)
        if (
            not isinstance(frontier, list)
            and frontier.voted_for.shape[0] % self.chunk
        ):
            cap0 = -(-frontier.voted_for.shape[0] // self.chunk) * self.chunk
            frontier = jax.tree.map(
                lambda x: _pad_axis0(x, cap0), frontier
            )
        elif isinstance(frontier, list) and (
            _seg_rows(frontier[0]) % self.chunk
        ):
            cap0 = -(-_seg_rows(frontier[0]) // self.chunk) * self.chunk
            frontier = [
                jax.tree.map(lambda x: _pad_axis0(x, cap0),
                             self._seg_to_dev(s))
                for s in frontier
            ]

        # a STOPPED superstep (uncommitted abort/violation/overflow
        # level) must route its level through the per-level machinery
        # exactly once before supersteps re-engage
        skip_superstep = False
        spans.pop_to(0)
        spans.push("raft.check.levels")
        while n_f > 0:
            # the previous level's per-level span ends here
            spans.pop_to(1)
            resilience.fault_fire("level.start")
            if resilience.preempt_requested():
                # every completed level's delta record is already
                # durable (written synchronously at level end), so
                # there is nothing left to flush — exit resumable
                raise resilience.Preempted(
                    checkpoint_dir if checkpoint_every else None, depth
                )
            if max_depth is not None and depth >= max_depth:
                break
            graft_obs.level_begin(depth + 1, n_f)
            self._hbm_note(frontier, level_sizes, max_depth, depth)
            if self.watchdog is not None:
                # armed BEFORE the device fault sites: an injected hang
                # at the dispatch site is exactly what it must convert
                # into a clean exit 75
                self.watchdog.arm(f"level {depth + 1} (single-device)")
            resilience.fault_fire("device.lost")
            resilience.fault_fire("device.hang")
            if self.presize and len(level_sizes) > PRESIZE_MIN_LEVELS:
                self._update_presize(level_sizes, distinct, max_depth,
                                     frontier)
                if self.host_store is None and self.use_hashstore:
                    # slab presize: one rehash up to the forecast entry
                    # count, so deep runs compile one probe shape per
                    # pow2 magnitude instead of overflow-redoing levels
                    ent = getattr(self, "_presize_entries", 0)
                    if ent:
                        try:
                            self._tier_reserve(int(ent * 1.1))
                        except Exception as e:  # graftlint: waive[GL003]
                            # a failed presize reserve degrades like any
                            # other grow failure (reserve() only grows)
                            visited = self._degrade_hashstore(e)
                elif (self.host_store is None
                        and self._presize_vcap > visited.shape[0]):
                    # SENT-pad the sorted store up front so its shape is
                    # pinned for the rest of the run (SENT sorts last, so
                    # appending keeps the array sorted)
                    visited = jnp.concatenate([
                        visited,
                        jnp.full(
                            (self._presize_vcap - visited.shape[0],),
                            SENT, U64,
                        ),
                    ])
            if self.prewarm and len(level_sizes) > PRESIZE_MIN_LEVELS:
                # forecast-driven AOT prewarm: the shape ladder the deep
                # levels will hit compiles in the background while the
                # cheap shallow levels run (re-submitted every level —
                # the Prewarmer dedupes keys, so only a SHARPER forecast
                # queues fresh programs)
                self._submit_prewarm(
                    level_sizes, distinct, max_depth, frontier, visited
                )
            # --- tiered drain: a slab left over-budget (transient
            # soft-seat, MIN_CAP floor) or whose next growth would bust
            # the budget demotes HERE, between levels, where no redo is
            # ever needed — and this is the superstep windows' only
            # drain site (their commit path adopts without the staged
            # between-level grow) ----------------------------------------
            self._tier_drain(depth, n_f)
            # consumed frontier segments' warm-tier artifacts retire at
            # the level top: the previous level is committed, nothing
            # can replay its parents
            self._fseg_retire_consumed()
            # adaptive sieve: per-level tick drives the stood-down
            # governor's re-arm probation (ops/sieve.py)
            self.sieve_governor.note_level(depth)
            # --- multi-level resident superstep: up to N fused levels
            # in ONE device program + ONE ledgered ring fetch
            # (engine/superstep.py).  A stopped level (abort /
            # violation / any overflow / ring high-water) falls
            # through to the per-level paths below, which re-enter the
            # existing grow-and-redo machinery against the slab as of
            # the committed prefix --------------------------------------
            if (not skip_superstep
                    and self._superstep_span_at(max_depth, depth) > 1
                    and self._mega_level_ok(frontier, n_f)):
                if self.watchdog is not None:
                    # the armed deadline scales with the declared level
                    # span (satellite: an N-level superstep must not
                    # trip the per-level hang budget)
                    self.watchdog.arm(
                        f"levels {depth + 1}..{depth + self._superstep_span_at(max_depth, depth)}"
                        " (superstep)",
                        span=self._superstep_span_at(max_depth, depth),
                    )
                sres = self._run_superstep(
                    frontier, n_f, max_depth, depth, level_sizes
                )
            else:
                sres = None
            skip_superstep = False
            if sres is not None and sres.get("degraded"):
                # hash store degraded while presizing for the span:
                # adopt the rebuilt sorted store and run staged
                frontier = sres["frontier"]
                visited = self._degraded_visited
                self._degraded_visited = None
                sres = None
                if self.watchdog is not None:
                    # the span-N window must not cover the staged
                    # single level below: its end-of-level disarm
                    # would divide one level's wall by N and deflate
                    # the adaptive budget right when the degraded
                    # (sorted-store) levels run slowest
                    self.watchdog.arm(f"level {depth + 1} (degraded)")
            if sres is not None:
                frontier = sres["frontier"]
                hit_fixpoint = False
                depth0 = depth  # window entry, for the dump cadence
                with graft_obs.span("raft.level.commit"):
                    for li, rec in enumerate(sres["recs"]):
                        if li:
                            # the per-level crash sites keep their once-
                            # per-level cadence (the while-loop top fired
                            # for the superstep's first level)
                            resilience.fault_fire("level.start")
                        level_mult = rec["mult"]
                        mult_per_slot = mult_per_slot + level_mult
                        generated += int(level_mult.sum())
                        if rec["n_new"] == 0:
                            # the terminal fixpoint level: generated counts
                            # (the staged loop breaks AFTER the mult add),
                            # distinct/depth do not
                            hit_fixpoint = True
                            break
                        n_new = rec["n_new"]
                        distinct += n_new
                        level_sizes.append(n_new)
                        depth += 1
                        trace_levels.append((rec["pidx"], rec["slot"]))
                        n_f = n_new
                        graft_obs.level_commit(
                            depth, n_new, distinct, generated,
                            slab_cap=(
                                self.hstore.cap
                                if self.use_hashstore and self.hstore
                                is not None else 0
                            ),
                        )
                        if self.progress is not None:
                            self.progress(
                                dict(
                                    level=depth,
                                    frontier=n_new,
                                    distinct=distinct,
                                    generated=generated,
                                    elapsed=time.monotonic() - t0,
                                )
                            )
                        if graft_sanitize.tracking():
                            sig = (
                                sres["cap_f"], self.hstore.cap,
                                sres["cap_f"], self.cap_x, self.cap_g,
                                self.cap_m, self._san_lanes,
                            )
                            if sig != getattr(self, "_san_sig", None):
                                graft_sanitize.note_shape_event(
                                    f"level shapes {sig}"
                                )
                                self._san_sig = sig
                            graft_sanitize.level_tick()
                        if checkpoint_dir and checkpoint_every:
                            self._save_delta(
                                checkpoint_dir, depth, rec["pidx"],
                                rec["slot"], rec["fps"], level_mult, n_new,
                            )
                    if sres["n_total"] or hit_fixpoint:
                        # adopt the committed prefix's slab in one step
                        self.hstore.adopt(sres["slab"], sres["n_total"])
                        # free conservation check: the driver counted the
                        # returned slab's live slots — they must equal the
                        # distinct set after the committed prefix (or, once
                        # generations exist, the HOT-tier count: the slab
                        # holds only the post-demotion residue, and every
                        # committed level was sieve-clean so its fresh
                        # count is insert-exact)
                        resilience.integrity.occupancy_check(
                            "device hash slab", sres["slab_live"],
                            self.hstore.count if self._tier_active()
                            else distinct,
                            level=depth,
                        )
                    if checkpoint_dir and checkpoint_every and sres["recs"]:
                        dump_every = hashstore.dump_interval(
                            self.hstore.cap * 8
                        ) if self.use_hashstore else 0
                        # floor-crossing, not ==: the window advanced depth
                        # by up to span levels, and any cadence point it
                        # crossed earns the (one, end-of-window) dump —
                        # keeping the per-level path's snapshot cadence
                        if (self.use_hashstore and dump_every
                                and (depth // dump_every)
                                > (depth0 // dump_every)):
                            self.hstore.dump(
                                os.path.join(checkpoint_dir, "hslab.npz"),
                                depth, int(self.orbit),
                                run_fp=self._run_fp,
                            )
                if hit_fixpoint:
                    if self.watchdog is not None:
                        self.watchdog.disarm(levels=len(sres["recs"]))
                    break
                # adaptive sieve: feed the window's outcome — whether
                # it stopped on in-kernel sieve hits — to the governor
                # (only FLAG_TIER stops count as sieve-dirty; overflow
                # and ring stops say nothing about revisit density)
                if self._sieve_ready():
                    self.sieve_governor.note_window(
                        sieve_stop=bool(
                            sres["reason"] == "stop"
                            and sres["flags"]
                            & graft_superstep.FLAG_TIER
                        ),
                        level=depth,
                    )
                if sres["reason"] == "stop" or (
                    sres["reason"] == "ring" and not sres["recs"]
                ):
                    # a zero-commit window (uncommitted stop level, or
                    # a ring too small for even one level) must make
                    # progress through the per-level path before the
                    # next superstep engages
                    skip_superstep = True
                if sres["reason"] == "stop":
                    # the control vector names the stopped level's
                    # overflow class — grow the budget NOW so the
                    # per-level replay lands on its first redo instead
                    # of re-discovering the overflow (a stopped level
                    # then costs one attempt + one redo, exactly the
                    # per-level path's price)
                    flags = sres["flags"]
                    if flags & graft_superstep.FLAG_OVF_X:
                        self.cap_x = _cap_steps(self.cap_x + 1)
                        self.cap_g = max(
                            self.cap_g, self.G * self.cap_x // 2
                        )
                        self._jit_expand_programs()
                        self._mega_stats["redo_x"] += 1
                        graft_obs.grow("cap_x", self.cap_x)
                    if flags & graft_superstep.FLAG_OVF_SLAB:
                        self._hs_pending = None
                        try:
                            how = self._slab_grow_or_demote(
                                depth + 1, expected=max(n_f, 1)
                            )
                        except Exception as e:  # graftlint: waive[GL003]
                            # grow failure degrades to the sort path
                            # like every other grow site
                            visited = self._degrade_hashstore(e)
                        else:
                            self._mega_stats["redo_slab"] += 1
                            if how == "demoted":
                                # FLAG_OVF_SLAB_TIER: the host
                                # reclassified the stop — the grow the
                                # device asked for would bust the tier
                                # budget, so it demoted instead and the
                                # stopped level replays per-level (the
                                # span stands down to 1 from here on)
                                flags |= (
                                    graft_superstep.FLAG_OVF_SLAB_TIER
                                )
                                self._ss_stats["tier_stops"] = (
                                    self._ss_stats.get("tier_stops", 0)
                                    + 1
                                )
                                self.tiered.stats["tier_redos"] += 1
                                graft_obs.redo("slab_tier")
                            else:
                                graft_obs.grow("slab", self.hstore.cap)
                    if (flags & graft_superstep.FLAG_OVF_M
                            and self.cap_m < self.kern.uni.M):
                        # mirror the per-level cap_m redo (widen + re-
                        # jit) so the replay's first attempt lands
                        # under the grown width; at the universe cap
                        # the replay raises through its own error path
                        self.cap_m = min(
                            self.cap_m + 32, self.kern.uni.M
                        )
                        print(
                            f"[engine] cap_m overflow: growing to "
                            f"{self.cap_m} and replaying the stopped "
                            "level per-level", file=sys.stderr,
                        )
                        frontier = self._widen_msg_ids(frontier)
                        self._jit_expand_programs()
                        self._mega_stats["redo_m"] += 1
                        graft_obs.grow("cap_m", self.cap_m)
                    if flags & graft_superstep.FLAG_TIER:
                        # in-kernel sieve hits: POSSIBLE spilled
                        # revisits in the stopped level.  Nothing to
                        # grow — the per-level replay's tier tail
                        # performs the exact generation probe (a false
                        # positive costs exactly this one replay; its
                        # tier_probe event reports zero revisits)
                        self._ss_stats["sieve_stops"] = (
                            self._ss_stats.get("sieve_stops", 0) + 1
                        )
                        graft_obs.sieve_stop(depth + 1, -1)
                if self.watchdog is not None:
                    # a stopped window's elapsed covered only the
                    # committed levels (+ the aborted attempt): keep
                    # the per-level history honest or the stopped
                    # level's own replay budget deflates
                    self.watchdog.disarm(levels=len(sres["recs"]))
                continue
            # --- whole-level megakernel: ONE fused program + ONE
            # ledgered fetch per level (engine/megakernel.py); every
            # overflow redoes inside, a mid-level hash-store
            # degradation falls through to the staged path below -----
            spans.push("raft.level.single")
            mres = None
            if self._mega_segs_ok(frontier, n_f):
                # spilled frontier: stream the parent through the fused
                # program one segment at a time (cutting an over-budget
                # device frontier first) — the level runs fused even
                # when its working set exceeds HBM
                if not isinstance(frontier, list):
                    frontier = self._cut_frontier(frontier, n_f, depth)
                mres = self._expand_level_mega_segs(
                    frontier, n_f, max_depth, level_sizes, depth
                )
                if mres is not None and mres.get("degraded"):
                    frontier = mres["parent"]
                    visited = self._degraded_visited
                    self._degraded_visited = None
                    mres = None
            elif self._mega_level_ok(frontier, n_f):
                mres = self._expand_level_mega(
                    frontier, n_f, max_depth, level_sizes
                )
                if mres is not None and mres.get("degraded"):
                    # hash store degraded mid-level: adopt the rebuilt
                    # sorted store, rebind the (donation pass-through)
                    # parent and redo the level staged
                    frontier = mres["parent"]
                    visited = self._degraded_visited
                    self._degraded_visited = None
                    mres = None
            if mres is not None:
                n_new = mres["n_new"]
                abort_at = mres["abort_at"]
                level_mult = mres["level_mult"]
                # under donation the parent came back as the aliased
                # pass-through output; rebind so redo/audit/trace all
                # see a live buffer
                frontier = mres["parent"]
                new_fps = new_payload = None
            # --- staged fallback: expand + compact-then-dedup (device),
            # fused level fetch ------------------------------------------
            while mres is None:
                if isinstance(frontier, list) and self.host_store is None:
                    # staged redo of a segment-streamed level (degrade,
                    # or megakernel turned off mid-run): page the parent
                    # segments back in and concat — the staged device
                    # path wants one frontier, and the degraded route is
                    # already off the fast path (correctness first)
                    for i, s in enumerate(frontier):
                        frontier[i] = self._seg_to_dev(s)
                    frontier = _concat_fields(frontier)
                (n_new, new_fps, new_payload, abort_at, overflow, overflow_g,
                 overflow_h, level_mult) = self._expand_level(
                    frontier, n_f, visited,
                    ckdir=checkpoint_dir if checkpoint_every else None,
                    depth=depth,
                )
                if not (overflow or overflow_g or overflow_h):
                    break
                # a lane budget overflowed: grow it and redo the level
                # (pure computation, rare).  cap_x is baked into the traced
                # chunk program, so re-jit; cap_g is a static jit arg and
                # retraces on its own.
                graft_obs.redo(
                    "cap_x" if overflow else
                    ("cap_g" if overflow_g else "slab")
                )
                if overflow_h:
                    # a probe window filled: rehash into a bigger slab and
                    # redo against the ORIGINAL slab (the pending update
                    # is discarded — the kernels are functional); under
                    # the tiered budget the grow becomes a generation
                    # demotion ("demote, then redo") instead
                    self._hs_pending = None
                    try:
                        how = self._slab_grow_or_demote(
                            depth + 1, expected=max(n_f, n_new)
                        )
                    except Exception as e:  # graftlint: waive[GL003]
                        # any grow failure (device OOM, injected fault)
                        # degrades to the sort path — never mid-run death
                        visited = self._degrade_hashstore(e)
                    else:
                        if how == "demoted":
                            self.tiered.stats["tier_redos"] += 1
                        else:
                            graft_obs.grow("slab", self.hstore.cap)
                if overflow:
                    # half-step growth ({2^k, 3*2^(k-1)}): a doubled cap_x
                    # inflates every downstream lane count (group filter,
                    # level sort) for the rest of the run, and the common
                    # overflow is a mid-depth level firing ~5 lanes/parent
                    # against a 4x-chunk budget — 1.5x absorbs it
                    self.cap_x = _cap_steps(self.cap_x + 1)
                    self.cap_g = max(self.cap_g, self.G * self.cap_x // 2)
                    self._jit_expand_programs()
                    graft_obs.grow("cap_x", self.cap_x)
                if overflow_g:
                    self.cap_g *= 2
                    graft_obs.grow("cap_g", self.cap_g)
            if abort_at < n_f:
                spans.pop_to(0)
                spans.push("raft.check.finish")
                # action_counts stays None on violations, like the oracle:
                # coverage of a partially-expanded level is ill-defined
                return CheckResult(
                    False, distinct, generated, depth, tuple(level_sizes),
                    (
                        'Assert "split brain" (Raft.tla:185)',
                        self._trace(trace_levels, depth, abort_at),
                    ),
                )
            mult_per_slot = mult_per_slot + level_mult
            generated += int(level_mult.sum())

            fps_host = None  # host-filtered level fps (delta-log record)
            pay_host = None  # host-side payloads (external-store path)
            if self.host_store is not None and n_new:
                # _expand_level_host already ran the store filter; its
                # outputs are host-side numpy in ASCENDING PAYLOAD order
                # (the load-bearing invariant of the segment-streamed
                # materialize and of delta-record/trace correspondence)
                fps_host, pay_host = new_fps, new_payload
                new_payload = _pad_axis0(
                    jnp.asarray(pay_host), max(_pow2(n_new), 4 * self.chunk)
                )
            if n_new == 0:
                # the empty level's partials (saved during its expansion)
                # have no delta record to supersede them — wipe here so a
                # completed run leaves a clean directory
                if self.host_store is not None and checkpoint_dir:
                    self._wipe_partials(checkpoint_dir)
                break

            tail = None
            if mres is not None:
                # the fused program already materialized the level and
                # fetched its trace/delta arrays in the one control get
                new_frontier = mres["new_frontier"]
                pidx_np = mres["pidx"]
                slot_np = mres["slot"]
                bad_idx = mres["bad_idx"]
            else:
                # --- materialize the survivors (device-resident) --------
                # slice width must not exceed the payload capacity (a
                # custom cap_x < 4*chunk shrinks the dedup output below
                # 4*chunk).  The new frontier comes back fully built at
                # its quantized capacity (donated in-place slice writes —
                # the parent, the slices AND the concat result never
                # coexist)
                new_frontier, bads, n_slices, sl, frontier = (
                    self._materialize_grow(
                        frontier, new_payload, n_new, pay_np=pay_host
                    )
                )
                # trace spill: the external-store path already holds the
                # payloads host-side — no device round-trip there.  The
                # device path submits its level-tail fetch (trace arrays +
                # the delta record's fps slice) to the async window instead
                # of blocking here, so the ~24 B/state tail crosses the host
                # link WHILE the store merge below runs on the device
                # (window 0 = the serial fetch-in-place chain).
                if pay_host is not None:
                    pidx_np = (pay_host // K).astype(np.int64)
                    slot_np = (pay_host % K).astype(np.int64)
                else:
                    pidx32 = (new_payload[: n_slices * sl] // K).astype(U32C)
                    # fetch width must match _save_delta's: a u16 cast here
                    # would wrap slots at K > 65535 before the widened save
                    # ever saw them
                    slot_jdt = jnp.uint16 if K <= 0xFFFF else jnp.uint32
                    slot16 = (
                        new_payload[: n_slices * sl] % K
                    ).astype(slot_jdt)
                    tree = [pidx32, slot16]
                    if (checkpoint_dir and checkpoint_every) or (
                        self._tier_active()
                    ):
                        # the delta record's fps (pow2-quantized device
                        # slice, host trim — see the checkpoint block);
                        # the tiered level tail needs them host-side
                        # regardless (the generation probe's input)
                        w_ck = min(new_fps.shape[0],
                                   max(_pow2(n_new), self.chunk))
                        tree.append(new_fps[:w_ck])
                    tail = graft_pipeline.DeferredFetch(
                        self.pipeline, tuple(tree)
                    )
                bad_idx = -1
                for si, b in enumerate(bads):
                    if b >= 0:
                        bad_idx = si * sl + int(b)
                        break
            # --- tiered level tail: probe the demoted generations -------
            # The fused/staged hot-slab probe can mistake a demoted
            # fingerprint's revisit for fresh; the generation probe
            # (sieve -> warm -> cold, store/tiered.py) finds exactly
            # those rows and ONE small compaction program drops them
            # from the materialized frontier — counts stay bit-identical
            # to the uncapped run.  The hit fps were re-inserted into
            # the hot slab by the very probe that admitted them: that
            # is the re-heat, so the next revisit resolves on device.
            n_new_store = n_new  # kernel-fresh (= hot-slab delta) count
            fps_np_lvl = None    # host-side POST-filter level fps
            tier_traced = False  # pidx/slot already host-filtered here
            # in-kernel sieve fast path: the fused level counted its
            # fresh lanes' sieve hits on device — ZERO hits provably
            # means no spilled revisits (blooms have no false
            # negatives), so the exact generation probe is skipped
            # outright (the common case once the working set moves past
            # the spilled prefix)
            if mres is not None and mres.get("tier_done"):
                # segment-streamed level: the sieve fast path / exact
                # generation probe already ran per segment inside
                # _expand_level_mega_segs — mres["fps"] is post-filter
                pass
            elif (self._tier_active() and n_new and mres is not None
                    and self._sieve_ready()
                    and mres.get("tier_hits", -1) == 0):
                self.tiered.stats["sieve_skips"] = (
                    self.tiered.stats.get("sieve_skips", 0) + 1
                )
            elif self._tier_active() and n_new:
                if mres is not None:
                    fps_pre = np.asarray(mres["fps"], np.uint64)
                else:
                    h = tail.get()
                    fps_pre = np.asarray(h[2])[:n_new].astype(np.uint64)
                n_keep, tier_keep, new_frontier = self._tier_filter_level(
                    depth, n_new, fps_pre,
                    new_frontier, new_frontier.voted_for.shape[0],
                )
                if tier_keep is None:
                    fps_np_lvl = fps_pre[:n_new]
                else:
                    fps_np_lvl = fps_pre[:n_new][tier_keep]
                    if mres is not None:
                        pidx_np = pidx_np[tier_keep]
                        slot_np = slot_np[tier_keep]
                        mres["fps"] = fps_np_lvl
                    else:
                        pidx_np = np.asarray(
                            h[0]
                        )[:n_new].astype(np.int64)[tier_keep]
                        slot_np = np.asarray(
                            h[1]
                        )[:n_new].astype(np.int64)[tier_keep]
                        tier_traced = True
                    if bad_idx >= 0:
                        # a violating row is truly new by construction
                        # (its FIRST visit is where the invariant scan
                        # sees it; generation members were scanned clean
                        # at theirs) — remap past the dropped revisits
                        assert tier_keep[bad_idx], (
                            "invariant violation attributed to an "
                            "already-visited (generation) row"
                        )
                        bad_idx = int(np.count_nonzero(tier_keep[:bad_idx]))
                    n_new = n_keep
                if n_new == 0:
                    # every fresh lane was a generation revisit: this IS
                    # the uncapped run's fixpoint level — adopt the slab
                    # (the re-heats stay hot; its count is the KERNEL
                    # fresh count) and stop exactly like the n_new == 0
                    # break above (mult already added, no delta record)
                    self.hstore.adopt(self._hs_pending, n_new_store)
                    self._hs_pending = None
                    n_f = 0
                    break
            # the audit re-expands sampled rows from their PARENTS, so
            # the pre-swap frontier must outlive the swap (audit runs
            # only; production keeps the old drop-at-swap lifetime)
            parent_prev = frontier if self.audit else None
            frontier = new_frontier
            if resilience.fault_flag("tensor.flip") and not isinstance(
                frontier, list
            ):
                # injected silent corruption: one bit of the first live
                # frontier row flips ON DEVICE after materialize — the
                # recorded fingerprints disagree with the slab from here
                # on, which is exactly what --audit must catch
                frontier = self._flip_frontier_row(frontier)

            # --- bookkeeping, store merge -------------------------------
            distinct += n_new
            level_sizes.append(n_new)
            depth += 1

            if self.host_store is None and self.use_hashstore:
                # the fused probe-and-insert already built the updated
                # slab — adopt the pending copy (no merge, no re-sort)
                # and grow BETWEEN levels when the next level's worst
                # case (~2x this one) would cross the 1/2 load line, so
                # mid-level overflow redos stay the rare backstop
                # adopt the KERNEL-fresh count: under the tiered store
                # the slab also re-heated this level's generation
                # revisits, so its occupancy delta is n_new_store, not
                # the post-filter n_new the distinct counter takes
                # (the segment-streamed path adopted per segment inside
                # — nothing pending there)
                if self._hs_pending is not None:
                    self.hstore.adopt(self._hs_pending, n_new_store)
                    self._hs_pending = None
                if mres is not None:
                    # free conservation check: the fused program counted
                    # the pending slab's live slots in its control
                    # vector — they must equal the distinct set exactly
                    # (or, once generations exist, the hot-tier count
                    # the engine tracks insert-exactly)
                    resilience.integrity.occupancy_check(
                        "device hash slab", mres["slab_live"],
                        self.hstore.count if self._tier_active()
                        else distinct,
                        level=depth,
                    )
                if self.hstore.need_grow(extra=2 * n_new) or (
                    self._tier_on() and self.hstore.count > 0
                    and not self.tiered.slab_fits(self.hstore.cap)
                ):
                    try:
                        # the between-level grow: under the tiered
                        # budget this is the COMMON demotion site (no
                        # redo needed — the level is already committed);
                        # it also DRAINS a soft over-budget slab left by
                        # a level whose fresh set alone exceeded the hot
                        # budget (seated transiently, demoted here)
                        how = self._slab_grow_or_demote(
                            depth, expected=2 * n_new
                        )
                    except Exception as e:  # graftlint: waive[GL003]
                        # grow failure degrades to the sort path (the
                        # adopted slab holds the full visited set)
                        visited = self._degrade_hashstore(e)
                    else:
                        if how == "grew":
                            graft_obs.grow("slab", self.hstore.cap)
            elif self.host_store is None:
                # merge, then trim the store to a pow4 capacity >= distinct;
                # new_fps is survivor-compacted, so slicing keeps every
                # real fingerprint and bounds the sort input.  The presize
                # floors pin both widths so deep runs reuse one compiled
                # merge instead of one per magnitude.
                w = max(_pow2(n_new), self.chunk)
                if self._presize_merge:
                    w = max(w, min(self._presize_merge, new_fps.shape[0]))
                graft_sanitize.note_dispatch("device.merge")
                visited = _merge_sorted(visited, new_fps[:w])[
                    : max(_cap4(distinct + 1), self._presize_vcap)
                ]
            if mres is None and pay_host is None and not tier_traced:
                # level tail boundary: everything after this needs the
                # trace arrays host-side (window 0 already fetched them
                # at submit, serially; the tiered correction above may
                # have consumed + filtered them already)
                h = tail.get()
                pidx_np = np.asarray(h[0])[:n_new].astype(np.int64)
                slot_np = np.asarray(h[1])[:n_new].astype(np.int64)
            trace_levels.append((pidx_np, slot_np))
            n_f = n_new

            graft_obs.level_commit(
                depth, n_new, distinct, generated,
                slab_cap=(
                    self.hstore.cap
                    if self.host_store is None and self.use_hashstore
                    and self.hstore is not None else 0
                ),
            )
            if self.progress is not None:
                self.progress(
                    dict(
                        level=depth,
                        frontier=n_new,
                        distinct=distinct,
                        generated=generated,
                        elapsed=time.monotonic() - t0,
                    )
                )
            if graft_sanitize.tracking():
                # per-level shape signature: a compile in a level whose
                # signature matches the previous level's is a SILENT
                # retrace (the regression class the sanitizer exists to
                # catch); any signature change is a declared shape event
                if isinstance(frontier, list):
                    fcap = tuple(_seg_rows(s) for s in frontier)
                else:
                    fcap = frontier.voted_for.shape[0]
                if self.host_store is not None:
                    vshape = 0
                elif self.use_hashstore:
                    vshape = self.hstore.cap
                else:
                    vshape = visited.shape[0]
                sig = (
                    fcap,
                    vshape,
                    mres["cap_out"] if mres is not None
                    else int(new_payload.shape[0]),
                    self.cap_x, self.cap_g, self.cap_m,
                    getattr(self, "_san_lanes", 0),
                )
                if sig != getattr(self, "_san_sig", None):
                    graft_sanitize.note_shape_event(f"level shapes {sig}")
                    self._san_sig = sig
                graft_sanitize.level_tick()
            if bad_idx >= 0:
                if isinstance(frontier, list):
                    L0 = _seg_rows(frontier[0])
                    bseg, boff = divmod(bad_idx, L0)
                    bsrc = frontier[bseg]
                    if isinstance(bsrc, _HostSeg):
                        bad_tree = Frontier(
                            **{f: jnp.asarray(v[boff : boff + 1])
                               for f, v in bsrc.fields.items()}
                        )
                    else:
                        bad_tree = jax.tree.map(
                            lambda x: x[boff : boff + 1], bsrc
                        )
                else:
                    bad_tree = jax.tree.map(
                        lambda x: x[bad_idx : bad_idx + 1], frontier
                    )
                spans.pop_to(0)
                spans.push("raft.check.finish")
                one = self._inflate(bad_tree)
                name = self._bad_invariant_name(one, 0)
                return CheckResult(
                    False, distinct, generated, depth, tuple(level_sizes),
                    (
                        f"Invariant {name} is violated",
                        self._trace(trace_levels, depth, bad_idx),
                    ),
                )
            # --- sampled recomputation audit (BEFORE the level's delta
            # record commits: a caught level never enters the log) -----
            if self.audit and n_new:
                if fps_np_lvl is not None:
                    level_fps_ref = fps_np_lvl
                elif mres is not None:
                    level_fps_ref = mres["fps"]
                elif fps_host is not None:
                    level_fps_ref = fps_host
                else:
                    level_fps_ref = new_fps
                problems = self._audit_level(
                    parent_prev, frontier, pidx_np, slot_np,
                    level_fps_ref,
                    n_new, depth,
                )
                graft_obs.audit(
                    depth, min(self.audit, n_new),
                    len(problems or []),
                )
                if problems:
                    return self._audit_rewind(
                        problems, depth, max_depth, checkpoint_dir,
                        checkpoint_every,
                    )
            # checkpoint only invariant-clean levels: a resumed run never
            # re-checks its loaded frontier, so saving before the check
            # could hide a violation behind a crash+resume.  Delta-log
            # format: every level appends its (parent, slot, fps) record
            # (the replay chain needs every level, so checkpoint_every
            # only gates whether checkpointing happens at all).
            if checkpoint_dir and checkpoint_every:
                # with a host store the device fps are pre-filter — the
                # log must hold exactly the level's NEW fingerprints.
                # Device slice at a POW2-quantized width, trim host-side:
                # a raw [:n_new] slice compiled one eager program per
                # level — latent under the sorted store (its per-level
                # capacity steps declared shape events that excused the
                # compile), surfaced by the hash slab's constant shape
                if fps_np_lvl is not None:
                    # the tiered correction already holds the exact
                    # post-filter level fps host-side
                    fps_np = fps_np_lvl
                elif mres is not None:
                    # the fused program's one control fetch carried them
                    fps_np = mres["fps"]
                elif fps_host is not None:
                    fps_np = fps_host.astype(np.uint64)
                else:
                    # prefetched through the level-tail window above
                    fps_np = np.asarray(
                        tail.get()[2]
                    )[:n_new].astype(np.uint64)
                self._save_delta(
                    checkpoint_dir, depth, pidx_np, slot_np, fps_np,
                    level_mult, n_new,
                )
                # slab snapshot next to the delta log (versioned; resume
                # loads it when it matches, else rebuilds from the
                # replayed fps — never the source of truth).  The dump
                # fetches + rewrites the WHOLE slab, so it runs on the
                # shared size-aware interval (hashstore.dump_interval /
                # TLA_RAFT_SLAB_DUMP; 0 = off).
                dump_every = (
                    hashstore.dump_interval(self.hstore.cap * 8)
                    if self.use_hashstore else 0
                )
                if (self.use_hashstore and dump_every
                        and depth % dump_every == 0):
                    # slab-occupancy conservation check at the dump
                    # cadence: the snapshot about to be trusted by a
                    # future resume must count exactly the distinct set
                    # (the hot-tier count once generations exist — a
                    # tiered resume rebuilds from the log regardless)
                    resilience.integrity.occupancy_check(
                        "device hash slab", self.hstore.occupancy(),
                        self.hstore.count if self._tier_active()
                        else distinct,
                        level=depth,
                    )
                    self.hstore.dump(
                        os.path.join(checkpoint_dir, "hslab.npz"),
                        depth, int(self.orbit), run_fp=self._run_fp,
                    )
                if self.host_store is not None:
                    # the level's per-group partials are superseded by its
                    # delta record (only the in-flight level ever has any)
                    self._wipe_partials(checkpoint_dir)
            if self.watchdog is not None:
                self.watchdog.disarm()

        spans.pop_to(0)
        spans.push("raft.check.finish")
        return CheckResult(
            True, distinct, generated, depth, tuple(level_sizes), None,
            self._action_counts(mult_per_slot),
        )
