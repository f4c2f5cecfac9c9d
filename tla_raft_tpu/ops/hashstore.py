"""Device-resident open-addressing fingerprint store: O(1) probe dedup.

docs/PERF.md shows the deep-sweep cost structure is dominated by
membership machinery, not expand: the per-level `searchsorted` against
a multi-million-row sorted visited table is 20+ rounds of random
gathers per query (the same "gather cliff" class the dense-expand
rewrite designed out in pass 1), and every level additionally pays a
full-lane 3-key lexsort for dedup plus a whole-store re-sort to merge
the survivors in.  TLC itself is a fingerprint-SET engine (a giant
open-addressed hash table, SURVEY.md §3.2); this module is that design
on device:

* one power-of-two **slab** of u64 fingerprint slots (``SENT`` = the
  repo-wide invalid marker = empty),
* a **splitmix64 probe hash** (``mix64``) and linear probing with a
  fixed probe depth — every *stored* fingerprint provably sits within
  ``depth`` slots of its home (inserts that would need more REPORT
  OVERFLOW instead of probing further, and the host grows/rehashes the
  slab), so a depth-bounded negative probe is an exact "not present",
* two fused jitted kernels:
    - ``probe(slab, fps) -> hit_mask`` — membership only (the visited
      filter / the exchange sieve),
    - ``probe_and_insert(slab, fps, keys, pays) ->
      (slab', fresh_mask, n_new, overflow)`` — batch insert with exact
      batch-internal dedup: lanes carrying the same fingerprint resolve
      to one slot, and the *representative* lane per newly-inserted
      fingerprint is chosen by a two-phase scatter-min reduce as the
      min-(key, payload) lane — exactly the min-(fp_full, payload)
      group-min lemma the lexsort path pins (the global min over
      candidates equals the min over slot-group mins), so counts stay
      bit-identical to the sort-based dedup.

The kernels are built from the repo's fixed-shape idioms — a
``while_loop`` whose trip count is data-bounded but whose shapes never
change, scatter-min as the batch claim/CAS, and ``mode='drop'``
trash-slot scatters — so the graftlint jaxpr audit pins ONE deliberate
gather per probe round and a handful of scatters, instead of the
O(log |visited|) gather storm of binary search.  Replacing the
O(N log N) sort + O(log V) probe with O(candidates) expected work is
the membership-side analog of the dense-expand rewrite.

Batch-insert semantics (the subtle part): distinct fingerprints that
race for the same empty slot are resolved by ``scatter-min`` — the
smallest contender claims the slot and the rest re-probe next round
(their path now walks past the winner), which terminates because every
round permanently resolves at least the minimum contender per slot.
A lane that finds its fingerprint already in the slab resolves as a
hit; whether that hit is *fresh* (inserted by this very call) is
tracked per slot, so duplicate-heavy batches still report exactly one
``fresh_mask`` lane per new fingerprint.

Host-side: ``DeviceHashStore`` wraps a slab with growth/rehash at a
quantized load factor (grow to keep live <= cap/2; capacities are
powers of two so the compile count stays logarithmic), slab
checkpoint dump/load (versioned npz, see SLAB_VERSION), and
``insert_np`` mirrors the kernel's layout in pure numpy for host-side
slab rebuilds (mesh resume paths must not dispatch device programs
from worker threads).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

# u64 fingerprints everywhere (same declaration as ops/fingerprint.py;
# jax.config is GL001-safe — no backend touch at import)
jax.config.update("jax_enable_x64", True)

U64 = jnp.uint64
I64 = jnp.int64
I32 = jnp.int32
# numpy scalars, not jnp: module scope must stay device-free (GL001)
SENT = np.uint64(0xFFFFFFFFFFFFFFFF)
BIGP = np.int64(1 << 62)

# fixed probe depth: every stored fp sits within this many slots of its
# home.  At the <=1/2 load factor the grower enforces, the expected
# longest probe chain in a 2^30-slot slab is ~30 (Knuth 6.4); 64 leaves
# margin so overflow-triggered rehashes are rare-to-never in practice
# while keeping the while_loop's worst-case trip count small.
PROBE_DEPTH = 64
# slots examined per probe round: one [W, N] gather of W consecutive
# slots per lane instead of W scalar rounds — the walk's while_loop
# runs at most PROBE_DEPTH/W trips, and the typical batch (expected
# chain ~1-2 at <=1/2 load) settles in ONE trip.  Consecutive slots
# are the cheapest gather class on the vector units (same row
# neighborhood), so the wider fetch costs far less than W round trips.
PROBE_WINDOW = 8
MIN_CAP = 1 << 10
SLAB_VERSION = 1

_C1 = 0xBF58476D1CE4E5B9
_C2 = 0x94D049BB133111EB


def mix64(x):
    """splitmix64 finalizer; identical semantics for np and jnp.

    The stored fingerprints are already pseudorandom, but they arrive
    owner-sharded (fp % D) on the mesh — the low bits are biased inside
    one shard, and a power-of-two slab masks exactly those bits.  The
    finalizer decorrelates the probe home from the routing key."""
    xp = jnp if isinstance(x, jnp.ndarray) else np
    u = xp.uint64
    x = x.astype(u)
    x = (x ^ (x >> u(30))) * u(_C1)
    x = (x ^ (x >> u(27))) * u(_C2)
    return x ^ (x >> u(31))


def enabled_by_env() -> bool:
    """The one TLA_RAFT_HASHSTORE default parse both engines share."""
    import os

    return bool(int(os.environ.get("TLA_RAFT_HASHSTORE", "1")))


def dump_interval(slab_bytes: int) -> int:
    """Slab-snapshot cadence (levels between dumps; 0 = off), shared by
    both engines: TLA_RAFT_SLAB_DUMP overrides; the default dumps every
    level while the fetch is cheap (<= 256 MB) and every 16th beyond —
    a per-level dump of a multi-GB slab would re-add exactly the
    O(|store|) level tail this store removes."""
    import os

    env = os.environ.get("TLA_RAFT_SLAB_DUMP")
    if env is not None:
        return int(env)
    return 1 if slab_bytes <= (1 << 28) else 16


def rebuild_np(per_shard, cap: int) -> np.ndarray:
    """[D, cap] hash-slab rows rebuilt host-side from per-shard
    contents (old slab rows OR raw fp arrays — SENT lanes skipped).
    The one rebuild loop every mesh resume/growth path shares, so the
    sizing/overflow/layout rules cannot drift between call sites."""
    out = np.full((len(per_shard), cap), SENT, np.uint64)
    for o, rows in enumerate(per_shard):
        rows = np.asarray(rows, np.uint64)
        live = rows[rows != SENT]
        if len(live):
            insert_np(out[o], live)
    return out


def slab_rows(expected: int, load: float = 0.5) -> int:
    """Power-of-two slab capacity holding ``expected`` entries at
    ``load`` (the quantized-load-factor sizing both engines use; the
    forecast layer feeds ``expected`` from per_device_forecast /
    horizon_forecast)."""
    need = max(MIN_CAP, int(expected / load) + 1)
    return 1 << (need - 1).bit_length()


def _probe_rounds(slab, fps, depth):
    """One depth-bounded probe walk for every lane of ``fps``.

    Returns (idx, found, settled): ``idx`` is the slot holding the
    lane's fp (found) or the first empty slot on its path (available);
    ``settled`` is False for SENT lanes and for lanes whose whole
    depth-window is full of other fingerprints (probe overflow).  The
    while_loop exits as soon as every lane settles — at the <=1/2 load
    the grower enforces, that is typically 2-3 rounds of ONE gather
    each, vs the ~log2(|visited|) gather rounds of searchsorted."""
    cap = slab.shape[0]
    live = fps != SENT
    h0 = (mix64(fps) & jnp.uint64(cap - 1)).astype(I32)
    W = PROBE_WINDOW
    # window-major [W, N]: the lanes sit on the minor axis.  Lane-major
    # [N, W] pads its minor dim of W up to a 128-wide TPU tile — 16x the
    # bytes at W = 8, which put a 2M-row superstep 11 GB over HBM
    woff = jnp.arange(W, dtype=I32)[:, None]

    def cond(c):
        d, _idx, _found, done = c
        return (d < depth) & ~done.all()

    def body(c):
        d, idx, found, done = c
        cur = (h0[None, :] + d + woff) & (cap - 1)  # [W, N]
        v = slab[cur]
        hitw = v == fps[None, :]
        stopw = hitw | (v == SENT)
        # first hit-or-empty slot in the window, selected gather-free
        # (one-hot contraction — the repo's standard idiom)
        one = (
            stopw
            & (jnp.cumsum(stopw.astype(I32), axis=0) == 1)
        )
        cand = (cur * one).sum(0, dtype=I32)
        is_hit = (hitw & one).any(0)
        stop = stopw.any(0)
        settle = ~done & stop
        idx = jnp.where(settle, cand, idx)
        found = found | (settle & is_hit)
        done = done | stop
        return d + W, idx, found, done

    init = (
        jnp.zeros((), I32),
        jnp.zeros(fps.shape, I32),
        jnp.zeros(fps.shape, bool),
        ~live,
    )
    _d, idx, found, done = jax.lax.while_loop(cond, body, init)
    return idx, found, done & live


def probe_impl(slab, fps):
    """Membership mask (un-jitted body, composable inside other jits).

    Exact: inserts never place a fingerprint beyond PROBE_DEPTH of its
    home (they overflow and the host rehashes instead), so a negative
    depth-bounded walk proves absence."""
    _idx, found, _settled = _probe_rounds(slab, fps, PROBE_DEPTH)
    return found


@jax.jit
def probe(slab, fps):
    """hit_mask bool[N]: fps[i] (!= SENT) is in the slab."""
    return probe_impl(slab, fps)


def _claim_loop(slab, fps):
    """The shared insert core: probe-and-claim every live lane.

    Returns (slab', slot i32[N] — the slot holding each live lane's fp,
    whether found or claimed — and overflow).  scatter-min is the batch
    CAS: the smallest contender per contested empty slot wins, the rest
    re-probe next round (their walk now passes the winner), which
    terminates because every round permanently resolves at least the
    minimum contender per slot."""
    cap = slab.shape[0]
    live = fps != SENT

    def cond(c):
        _slab, pending, _slot, _ovf = c
        return pending.any()

    def body(c):
        slab, pending, slot, ovf = c
        pf = jnp.where(pending, fps, SENT)
        idx, found, settled = _probe_rounds(slab, pf, PROBE_DEPTH)
        slot = jnp.where(pending & found, idx, slot)
        want = pending & ~found & settled
        tgt = jnp.where(want, idx, cap)  # cap = trash (mode='drop')
        slab = slab.at[tgt].min(jnp.where(want, fps, SENT), mode="drop")
        got = want & (slab[jnp.clip(idx, 0, cap - 1)] == fps)
        slot = jnp.where(got, idx, slot)
        dead = pending & ~found & ~settled  # probe-depth overflow
        return (
            slab,
            pending & ~found & ~got & ~dead,
            slot,
            ovf | dead.any(),
        )

    init = (
        slab,
        live,
        jnp.zeros(fps.shape, I32),
        jnp.zeros((), bool),
    )
    slab, _pending, slot, ovf = jax.lax.while_loop(cond, body, init)
    return slab, slot, ovf


def probe_and_insert_impl(slab, fps, keys, pays):
    """Batch probe-and-insert with exact in-batch dedup (un-jitted body).

    fps u64[N] (SENT = dead lane), keys u64[N] (fp_full — the
    representative tie-break key), pays i64[N] (unique payloads — the
    final tie-break).  Returns (slab', fresh bool[N], n_new i64,
    overflow bool): ``fresh`` marks exactly one lane per fingerprint
    NEWLY inserted by this call — the min-(key, payload) lane of its
    slot group (the deterministic refinement every engine of this
    project pins).  On ``overflow`` the caller must discard ``slab'``,
    grow/rehash the ORIGINAL slab and redo the batch (the same redo
    shape as the engines' cap_x growth).
    """
    cap = slab.shape[0]
    orig = slab  # pre-call contents: the "was it new" oracle below
    live = fps != SENT
    slab, slot, ovf = _claim_loop(slab, fps)
    slot_c = jnp.clip(slot, 0, cap - 1)
    # a lane's group is NEW iff its fp was absent from the PRE-CALL
    # slab: one extra lane-sized probe pass against the original input
    # (typically one window trip), instead of carrying a bool[cap]
    # claimed-slot mark through every while round — at the multi-GB
    # slabs this store targets, slab-sized loop state is the memory
    # budget, lane-sized state is noise
    _i, pre_found, _s = _probe_rounds(
        orig, jnp.where(live, fps, SENT), PROBE_DEPTH
    )
    grp_new = live & ~pre_found
    # two-phase min-reduce over slot groups: representative =
    # min-(key, payload) — phase 1 scatter-mins the key, phase 2 breaks
    # key ties (symmetry-images of one state) by the unique payload.
    # The two scatter targets are slab-sized, but their lifetimes are
    # disjoint (m1's last use feeds is1 before m2 exists), so the peak
    # transient is ONE extra slab-sized buffer — well under the sorted
    # path's whole-store merge re-sort.
    t1 = jnp.where(grp_new, slot, cap)
    m1 = jnp.full((cap,), SENT, U64).at[t1].min(
        jnp.where(grp_new, keys, SENT), mode="drop"
    )
    is1 = grp_new & (m1[slot_c] == keys)
    t2 = jnp.where(is1, slot, cap)
    m2 = jnp.full((cap,), BIGP, I64).at[t2].min(
        jnp.where(is1, pays, BIGP), mode="drop"
    )
    fresh = is1 & (m2[slot_c] == pays)
    return slab, fresh, fresh.sum().astype(I64), ovf


@jax.jit
def probe_and_insert(slab, fps, pays):
    """(slab', fresh, n_new, overflow) with keys defaulting to the
    fingerprints themselves (no secondary tie-break key)."""
    return probe_and_insert_impl(slab, fps, fps, pays)


def insert_only_impl(slab, fps):
    """Insert, skipping lanes that overflow their probe window.

    For subset-semantics caches (the exchange sieve) and rehash: no
    representative bookkeeping — just the claim loop, with n_inserted
    read off the live-count delta (two O(cap) reduces, no slab-sized
    scatter scratch and no extra probe pass — the sieve update runs
    per device per level, so the probe_and_insert extras would double
    its tail for outputs nobody reads).  A skipped (overflowed) insert
    only costs sieve effectiveness, never correctness.  Returns
    (slab', n_inserted i64, overflow bool) — overflow means some lane
    was skipped (or the load crossed 1/2) and the host should grow."""
    cap = slab.shape[0]
    before = (slab != SENT).sum()
    slab2, _slot, ovf = _claim_loop(slab, fps)
    after = (slab2 != SENT).sum()
    load_hi = after * 2 > cap
    return slab2, (after - before).astype(I64), ovf | load_hi


@jax.jit
def insert_only(slab, fps):
    return insert_only_impl(slab, fps)


@functools.partial(jax.jit, static_argnames=("n_out",))
def compact_fresh(fresh, fps, pays, n_out: int):
    """Survivor compaction: (new_fps u64[n_out], new_pays i64[n_out])
    with the fresh lanes packed to the prefix IN LANE ORDER (the
    engines' candidate lanes are payload-ascending, so the output is
    too — the load-bearing order of the segment-streamed materialize).
    cumsum + trash-slot scatter: one pass, no sort."""
    dest = jnp.cumsum(fresh) - 1
    tgt = jnp.where(fresh, dest, n_out)
    out_f = jnp.full((n_out,), SENT, U64).at[tgt].set(fps, mode="drop")
    out_p = jnp.full((n_out,), -1, I64).at[tgt].set(pays, mode="drop")
    return out_f, out_p


def make_slab(cap: int):
    assert cap & (cap - 1) == 0 and cap >= MIN_CAP, cap
    return jnp.full((cap,), SENT, U64)


# -- numpy mirror (host-side slab rebuilds; never dispatches) -------------

def insert_np(slab: np.ndarray, fps: np.ndarray) -> np.ndarray:
    """Pure-numpy ``insert_only`` with the identical slab layout.

    Vectorized round loop (np.minimum.at is the scatter-min CAS).  Used
    by resume paths that rebuild slabs on the host: worker threads and
    resume helpers must never dispatch device programs (GL007), and the
    layout must match the device kernels so a rebuilt slab and a
    checkpointed slab are interchangeable.  Lanes that overflow their
    probe window raise — the caller sized the slab from the exact entry
    count, so overflow means a sizing bug, not load."""
    cap = len(slab)
    fps = np.asarray(fps, np.uint64)
    fps = fps[fps != SENT]
    pending = np.unique(fps)
    h0 = (mix64(pending) & np.uint64(cap - 1)).astype(np.int64)
    while len(pending):
        # inner walk against the ROUND SNAPSHOT: every lane settles on
        # its hit or its first empty slot (the device's _probe_rounds)
        idx = np.full(len(pending), -1, np.int64)
        found = np.zeros(len(pending), bool)
        done = np.zeros(len(pending), bool)
        for d in range(PROBE_DEPTH):
            if done.all():
                break
            cur = (h0 + d) & (cap - 1)
            v = slab[cur]
            hit = v == pending
            empty = v == SENT
            settle = ~done & (hit | empty)
            idx[settle] = cur[settle]
            found |= ~done & hit
            done |= hit | empty
        if not done.all():
            raise ValueError(
                f"insert_np probe overflow (cap {cap}, "
                f"{int((~done).sum())} unresolved) — slab undersized"
            )
        # batch claim: scatter-min is the CAS, identical to the kernel
        want = done & ~found
        np.minimum.at(slab, idx[want], pending[want])
        got = want & (slab[np.clip(idx, 0, cap - 1)] == pending)
        keep = ~(found | got)
        pending, h0 = pending[keep], h0[keep]
    return slab


@jax.jit
def _live_count(slab):
    return (slab != jnp.uint64(SENT)).sum()


class DeviceHashStore:
    """Host-side wrapper: one device slab + growth/rehash + checkpoints.

    The slab itself is exposed (``.slab``) so engines can pass it into
    their own fused level programs; mutation is explicit via
    ``adopt()`` so overflow-redo loops can discard a failed level's
    slab and retry against the original (the kernels are functional).
    ``count`` is host-side bookkeeping fed by the engines' existing
    fused per-level control fetch — growth decisions never add a sync.
    """

    def __init__(self, cap: int = MIN_CAP, count: int = 0):
        cap = max(MIN_CAP, cap)
        assert cap & (cap - 1) == 0, cap
        self.cap = cap
        self.count = count
        self.slab = make_slab(cap)
        self._note_buffer()

    def _note_buffer(self) -> None:
        # live-HBM gauge (obs/telemetry.py): the slab is the run's
        # dominant long-lived device buffer — every capacity change
        # re-registers it (8 B per u64 slot)
        from ..obs import telemetry as _obs

        _obs.buffer("hslab", self.cap * 8)

    @classmethod
    def from_fps(cls, fps: np.ndarray, cap: int | None = None):
        """Build host-side from a fingerprint array (resume rebuilds)."""
        fps = np.asarray(fps, np.uint64)
        fps = fps[fps != SENT]
        n = len(np.unique(fps)) if len(fps) else 0
        st = cls.__new__(cls)
        st.cap = cap or slab_rows(n)
        st.count = n
        arr = np.full(st.cap, SENT, np.uint64)
        if n:
            insert_np(arr, fps)
        st.slab = jnp.asarray(arr)
        st._note_buffer()
        return st

    def need_grow(self, extra: int = 0) -> bool:
        return (self.count + extra) * 2 > self.cap

    def occupancy(self) -> int:
        """Live (non-SENT) slots, counted ON DEVICE — the integrity
        audit's slab-occupancy-vs-distinct conservation check.  One
        O(cap) reduce; callers run it at the slab-dump cadence, not
        per level."""
        return int(jax.device_get(_live_count(self.slab)))

    def adopt(self, slab, n_new: int):
        """Accept a level's updated slab (after the redo loop exits)."""
        self.slab = slab
        self.count += int(n_new)

    def grow(self, min_cap: int | None = None):
        """Rehash into a bigger slab (the old slab's live entries are
        unique, so one insert_only pass re-places them; on the rare
        probe overflow at the new size, double again).

        May raise (allocation failure on a full device, or an injected
        ``hashstore.grow`` fault): the engines catch and DEGRADE to the
        sort-based visited path instead of dying mid-run."""
        from ..resilience import faults

        faults.fire("hashstore.grow")
        want = max(self.cap * 2, min_cap or 0)
        want = 1 << (want - 1).bit_length()
        while True:
            slab2, _n, ovf = insert_only(make_slab(want), self.slab)
            if not bool(jax.device_get(ovf)):
                break
            want *= 2
        self.cap = want
        self.slab = slab2
        self._note_buffer()

    def reserve(self, expected: int):
        """Forecast presize: grow (never shrink) to hold ``expected``
        entries at the quantized <=1/2 load factor."""
        want = slab_rows(expected)
        if want > self.cap:
            self.grow(min_cap=want)

    # -- slab checkpoint (dump + load, versioned) ----------------------

    def dump(self, path: str, depth: int, fp_def: int = 0,
             run_fp: str | None = None):
        """Atomic slab snapshot next to the engine's delta records
        (digested + manifested via the shared atomic writer)."""
        import os

        from ..resilience import commit_npz

        commit_npz(
            os.path.dirname(path) or ".",
            os.path.basename(path),
            dict(
                slab=np.asarray(jax.device_get(self.slab)),
                meta=np.asarray(
                    [SLAB_VERSION, depth, self.count, self.cap, fp_def],
                    np.int64,
                ),
            ),
            kind="hslab",
            depth=depth,
            run_fp=run_fp,
        )

    @classmethod
    def load(cls, path: str, depth: int, count: int, fp_def: int = 0):
        """Load a dumped slab IF it matches the resume point exactly;
        returns None on any mismatch (the caller then rebuilds from the
        replayed fingerprints — the dump is an optimization, never the
        source of truth)."""
        import os

        import zipfile

        if not os.path.exists(path):
            return None
        try:
            z = np.load(path)
            ver, d, cnt, cap, fpd = (int(x) for x in z["meta"])
            if (
                ver != SLAB_VERSION or d != depth or cnt != count
                or fpd != fp_def or cap != len(z["slab"])
            ):
                return None
            st = cls.__new__(cls)
            st.cap = cap
            st.count = cnt
            st.slab = jnp.asarray(z["slab"])
            st._note_buffer()
            return st
        except (OSError, ValueError, KeyError, EOFError,
                zipfile.BadZipFile):
            # a torn/corrupt snapshot reads as "no snapshot": the
            # caller rebuilds from the replayed log (the dump is an
            # optimization, never the source of truth)
            return None
