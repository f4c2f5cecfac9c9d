"""Out-of-core visited store: HBM-hot / host-warm / disk-cold tiers.

The hashstore slab (PR 3) made membership O(1) on device, but it also
pinned the maximum ``|visited|`` to device memory — the one axis where
this reproduction still lost to TLC, whose disk-backed FPSet bounds the
state space by storage, not RAM (PAPER.md, SURVEY.md §3.2).  This
module is that tier structure for the device engine:

* **hot** — the open-addressing slab in HBM (``ops/hashstore.py``,
  unchanged layout).  Every candidate's membership-and-insert still
  runs as the fused on-device probe; the hot tier IS the sieve that
  keeps the lower tiers out of the common path (a hot hit is provably
  visited and never probes further down).
* **warm** — host-RAM **generations**: sorted, immutable fingerprint
  runs demoted from the hot slab when its quantized-load growth would
  exceed the device budget (``--dev-bytes`` / ``TLA_RAFT_STORE_BYTES``).
  Eviction is **by generation** — a full sorted run, never individual
  entries — so warm/cold probes stay ``searchsorted``-exact and the
  union of tiers is exactly the visited set.
* **cold** — generations whose host-RAM residency was evicted under the
  warm budget (``--warm-bytes`` / ``TLA_RAFT_WARM_BYTES``).  Every
  demotion commits its run through the ONE atomic checkpoint writer
  (``resilience.commit_npz``, kind ``gen`` — graftlint GL009 pins
  that), so a cold probe re-loads the committed file through a bounded
  LRU page cache; with no spill directory the generation simply stays
  warm.

**Probe protocol** (the level-tail correction both engine device paths
run): the fused device program probes-and-inserts against the hot slab
alone, so a level's "fresh" set may contain revisits of demoted
fingerprints.  The host probes exactly those fresh fingerprints —
sieve first (a bounded sorted cache of fingerprints already confirmed
spilled-visited), then warm runs, then cold runs — and the engine
drops the hit rows from the already-materialized frontier with one
small compaction program (:func:`drop_rows`).  On the engine device
paths the probe is a synchronous level-tail step whose blocking cost
is published per probe (``tier_probe``); on the external-store and
mesh paths the equivalent warm/cold membership rides the PR 5 async
fetch window / deferred tail, overlapping the next group's expand.  The hit fingerprints
were re-inserted into the hot slab by the very probe that mistook them
for fresh, which is the re-heat: the next revisit hits hot and never
reaches this code.  Counts stay bit-identical to an uncapped run
because dropping a visited row is exactly what the uncapped fused
probe would have done (representative choice is per-fingerprint-group
and unaffected; kept lanes preserve payload-ascending order).

**Crash/elastic contract**: the delta log remains the single source of
truth.  Generations are an optimization the resume REBUILDS from the
replayed per-level fingerprints (each generation then covers whole
levels, so the tier total is exactly ``distinct``); stale ``gen_*``
files from the crashed incarnation are discarded first.  Generations
carry the ``fp % D`` partition tag of their writer
(``(part_d, owner)``), and :func:`repartition` re-buckets a D-tagged
generation set onto D' owners with the same owner remap PR 8's elastic
resume applies to slabs — the mesh tiers (per-owner host stores +
their disk runs) rebuild through the same replay machinery.

Host-purity: probes and demotion bookkeeping are pure numpy and safe
from worker threads (GL007 — no device dispatch); the only device code
here is :func:`drop_rows_impl`, the row-compaction kernel the ENGINE
dispatches from its main thread (registered under the GL010
gather/scatter budget as ``store.tiered_compact``).
"""

from __future__ import annotations

import functools
import os
import time
import zipfile
from collections import OrderedDict

import numpy as np

from ..obs import telemetry as _obs

SENT = np.uint64(0xFFFFFFFFFFFFFFFF)

GEN_PREFIX = "gen_"
GEN_VERSION = 1

# default host-RAM budget for warm generations before the oldest ones
# drop to cold (disk-only): 1 GiB — big enough that CPU/test runs never
# touch the cold path unless asked to, small enough that a laptop-class
# host survives a multi-billion-state sweep's spill
DEFAULT_WARM_BYTES = 1 << 30

# sieve bound: fingerprints confirmed spilled-visited, kept sorted for
# the pre-generation probe.  8 MB of u64s; beyond it the oldest half is
# dropped (the sieve is a pure optimization cache — a miss only costs a
# generation probe, never correctness)
SIEVE_MAX = 1 << 20

# generation side-car suffix: the per-run bloom filter persisted beside
# each committed gen_*.npz (ops/sieve.py), probed before a cold disk
# load so the level tail touches disk only on likely hits
SIDECAR_SUFFIX = ".sieve.npz"

# LSM compaction fanout: once the COLD run count exceeds it, every cold
# generation merges into one sorted run (full-level compaction, the
# same policy the native host store applies to its run files at 16 —
# native/fpstore.cpp), bounding both the per-probe run walk and the
# open-file count of a billion-state sweep
DEFAULT_COMPACT_FANOUT = 8


# spilled-frontier segment files: one npz per demoted frontier segment
# (kind="fseg" through the atomic writer), committed by FrontierPager
# when a level's frontier working set outgrows the host budget
FSEG_PREFIX = "fseg_"


def compact_fanout_from_env() -> int:
    v = os.environ.get("TLA_RAFT_COMPACT_FANOUT")
    return max(1, int(v)) if v else DEFAULT_COMPACT_FANOUT


def fseg_bytes_from_env() -> int:
    """Host-RAM budget for paged-out frontier segments before they
    spill on to the warm tier (``TLA_RAFT_FSEG_BYTES``; 0 = disk spill
    off, host RAM is the only frontier overflow tier)."""
    v = os.environ.get("TLA_RAFT_FSEG_BYTES")
    return int(float(v)) if v else 0


def store_bytes_from_env() -> int:
    """The hot-tier device budget: ``TLA_RAFT_STORE_BYTES`` (bytes; 0 =
    unbounded = tiering off)."""
    v = os.environ.get("TLA_RAFT_STORE_BYTES")
    return int(float(v)) if v else 0


def warm_bytes_from_env() -> int:
    v = os.environ.get("TLA_RAFT_WARM_BYTES")
    return int(float(v)) if v else DEFAULT_WARM_BYTES


class Generation:
    """One demoted run: sorted unique u64 fingerprints.

    ``fps`` is the warm residency (None when cold — the committed file
    at ``path`` is then the only copy); ``lo``/``hi`` give the free
    range reject, ``(part_d, owner)`` the fp % D partition tag.
    ``sidecar`` is the run's bloom filter (ops/sieve.py SpillSieve,
    ~1.5 B/key), lazily loaded from ``sidecar_path`` and rebuilt from
    the generation itself when the persisted copy is torn or stale."""

    __slots__ = ("gid", "n", "lo", "hi", "fps", "path", "part_d",
                 "owner", "depth", "sidecar", "sidecar_path")

    def __init__(self, gid: int, fps: np.ndarray, *, path=None,
                 part_d: int = 1, owner: int = 0, depth: int = 0):
        fps = np.asarray(fps, np.uint64)
        self.gid = gid
        self.n = len(fps)
        self.lo = np.uint64(fps[0]) if self.n else SENT
        self.hi = np.uint64(fps[-1]) if self.n else np.uint64(0)
        self.fps = fps
        self.path = path
        self.part_d = part_d
        self.owner = owner
        self.depth = depth
        self.sidecar = None
        self.sidecar_path = None

    @property
    def nbytes(self) -> int:
        return self.n * 8

    @property
    def cold(self) -> bool:
        return self.fps is None


def _load_gen_fps(path: str) -> np.ndarray:
    """Re-load a cold generation's committed run (raises on a missing/
    torn file: cold data has no other copy, so silently returning an
    empty run would turn revisits into duplicate states)."""
    try:
        with np.load(path) as z:
            return np.asarray(z["fps"], np.uint64)
    except (OSError, ValueError, KeyError, EOFError,
            zipfile.BadZipFile) as e:
        raise IOError(
            f"cold generation {path} unreadable ({e}) — the visited "
            "set cannot be proven without it; restart from the delta "
            "log (--recover rebuilds every tier)"
        ) from e


class TieredVisitedStore:
    """Warm/cold generation bookkeeping + probes for one run.

    The HOT slab stays owned by the engine (``DeviceHashStore``); this
    object owns everything below it.  All methods are host-side numpy
    and safe to call from the external-store paths' worker threads
    (no device dispatch, GL007); the engine's level tail calls
    ``probe`` synchronously from the main thread — the measured
    ``probe_wait_s`` is that blocking cost, published per probe as a
    ``tier_probe`` event.
    """

    def __init__(self, dev_bytes: int, *, warm_bytes: int | None = None,
                 spill_dir: str | None = None, run_fp: str | None = None,
                 part_d: int = 1, owner: int = 0):
        self.dev_bytes = int(dev_bytes)
        self.warm_bytes = (
            warm_bytes_from_env() if warm_bytes is None else int(warm_bytes)
        )
        self.spill_dir = spill_dir
        self.run_fp = run_fp
        self.part_d = part_d
        self.owner = owner
        self.gens: list[Generation] = []
        self._next_gid = 0
        self.sieve = np.empty(0, np.uint64)
        # the device-resident spill sieve (ops/sieve.py): ONE blocked
        # bloom over EVERY demoted fingerprint, allocated at full size
        # on the first demotion (growing a bloom would re-hash every
        # spilled fp — cold reloads — so it trades graceful fp-rate
        # degradation past design load for never touching disk) and fed
        # at demote time.  The engine uploads ``spill_sieve.words`` and
        # refreshes on ``version`` bumps; its in-kernel probe is what
        # lets supersteps hold span N under spill.
        self.spill_sieve = None
        self.compact_fanout = compact_fanout_from_env()
        # cold page cache: gid -> fps, LRU-bounded by the warm budget
        # (a loaded cold run is warm residency like any other)
        self._cold_cache: OrderedDict[int, np.ndarray] = OrderedDict()
        self.stats = dict(
            demotions=0, spilled=0, cold_gens=0,
            probes=0, probe_lanes=0, probe_hits=0,
            sieve_hits=0, warm_hits=0, cold_hits=0,
            cold_loads=0, cold_load_s=0.0, probe_wait_s=0.0,
            reheats=0, tier_redos=0,
            compactions=0, compact_runs=0, compact_s=0.0,
            sidecar_skips=0, sidecar_rebuilds=0,
        )

    # -- policy -----------------------------------------------------------

    @property
    def active(self) -> bool:
        """True once at least one generation exists (probes required)."""
        return bool(self.gens)

    @property
    def max_hot_entries(self) -> int:
        """Entries the hot slab may hold inside the device budget at
        the enforced <= 1/2 load factor (0 = unbounded).  One under the
        half-slot mark: ``slab_rows(cap/2)`` rounds UP to the next
        power of two, so exactly cap/2 entries would demand a slab
        twice the budget."""
        if not self.dev_bytes:
            return 0
        return max(self.hot_slot_budget() // 2 - 1, 1)

    def hot_slot_budget(self) -> int:
        """Largest power-of-two slab (slots) that fits the device
        budget — the quantized form every sizing decision uses, so a
        pow2 rounding can never overshoot the budget."""
        if not self.dev_bytes:
            return 0
        slots = self.dev_bytes // 8
        return 1 << max(slots.bit_length() - 1, 0) if slots else 1

    def slab_fits(self, cap: int) -> bool:
        """May a slab of ``cap`` u64 slots live in the hot budget?"""
        return not self.dev_bytes or cap * 8 <= self.dev_bytes

    def spilled_distinct(self) -> int:
        """Total fingerprints across generations.  Exact ONLY when the
        generations are disjoint (the resume rebuild guarantees that —
        each generation covers whole levels); during a run, re-heated
        fingerprints may appear in several runs and this is an upper
        bound (membership is a union either way)."""
        return sum(g.n for g in self.gens)

    # -- demotion ---------------------------------------------------------

    def demote(self, fps: np.ndarray, *, depth: int = 0) -> Generation:
        """Seal one sorted run from the hot slab's live fingerprints.

        ``fps`` is the slab's live (non-SENT) content, host-side; the
        caller resets the device slab afterwards.  The run commits to
        the spill directory through the atomic writer (crash at any
        point leaves the delta log authoritative — a resumed run
        discards and rebuilds every generation), then the warm budget
        evicts the oldest warm residencies to cold and the LSM
        compaction bound merges the cold runs when they outgrow the
        fanout.  Every demoted fingerprint also lands in the global
        spill sieve (the device-resident filter) and the run's bloom
        side-car commits beside it."""
        t0 = time.monotonic()
        fps = np.asarray(fps, np.uint64)
        fps = np.unique(fps[fps != SENT])
        gen = Generation(
            self._next_gid, fps, part_d=self.part_d, owner=self.owner,
            depth=depth,
        )
        self._next_gid += 1
        if gen.n:
            if self.spill_sieve is None:
                from ..ops import sieve as sieve_mod

                self.spill_sieve = sieve_mod.SpillSieve(
                    sieve_mod.sieve_words_for(self.dev_bytes)
                )
            self.spill_sieve.add(fps)
        if self.spill_dir is not None and gen.n:
            from .. import resilience

            name = f"{GEN_PREFIX}{gen.gid:04d}.npz"
            gen.path = resilience.commit_npz(
                self.spill_dir, name,
                dict(
                    fps=fps,
                    meta=np.asarray(
                        [GEN_VERSION, gen.gid, gen.n, depth,
                         self.part_d, self.owner],
                        np.int64,
                    ),
                ),
                kind="gen", depth=depth, run_fp=self.run_fp,
            )
            self._commit_sidecar(gen, depth)
        if gen.n:
            self.gens.append(gen)
        self.stats["demotions"] += 1
        self.stats["spilled"] += gen.n
        self._enforce_warm()
        self._maybe_compact(depth)
        _obs.tier_demote(
            depth, gen.n, gen.gid, time.monotonic() - t0,
            cold=gen.cold,
        )
        return gen

    def _commit_sidecar(self, gen: Generation, depth: int) -> None:
        """Build the run's bloom side-car and commit it beside the run
        (kind ``sieve`` -> the ``sieve.tmp``/``sieve.commit`` fault
        sites).  Pure acceleration state: a torn/stale/lost side-car
        quarantines and rebuilds from the generation itself, never
        affecting membership."""
        from .. import resilience
        from ..ops import sieve as sieve_mod

        gen.sidecar = sieve_mod.SpillSieve.build(gen.fps)
        name = f"{GEN_PREFIX}{gen.gid:04d}{SIDECAR_SUFFIX}"
        gen.sidecar_path = resilience.commit_npz(
            self.spill_dir, name,
            dict(
                words=gen.sidecar.words,
                meta=np.asarray(
                    [sieve_mod.SIEVE_VERSION, gen.gid, gen.n,
                     len(gen.sidecar.words)],
                    np.int64,
                ),
            ),
            kind="sieve", depth=depth, run_fp=self.run_fp,
        )

    def _maybe_compact(self, depth: int = 0) -> None:
        """LSM merge: when the COLD run count exceeds the fanout, merge
        every cold generation into one sorted run (committed kind
        ``compact`` -> the ``compact.tmp``/``compact.commit`` fault
        sites) with a fresh bloom side-car, then discard the inputs.
        Commit-then-discard order makes a kill at any instruction safe:
        resume sweeps ALL ``gen_*`` files and rebuilds the tier layout
        from the delta log, so a crash can never double-count a
        generation; in-process, ``self.gens`` swaps only after the
        merged run is durable.  Full-level merge (not size-tiered):
        write amplification is bounded by the fanout trigger itself —
        each spilled fp is rewritten at most once per fanout's worth of
        new cold runs — and the probe walk shrinks to <= fanout runs
        plus the warm tail."""
        cold = [g for g in self.gens if g.cold]
        if len(cold) <= self.compact_fanout or self.spill_dir is None:
            return
        from .. import resilience
        from ..ops import sieve as sieve_mod

        t0 = time.monotonic()
        merged = np.unique(
            np.concatenate([self._gen_fps(g) for g in cold])
        )
        gen = Generation(
            self._next_gid, merged, part_d=self.part_d,
            owner=self.owner, depth=depth,
        )
        self._next_gid += 1
        name = f"{GEN_PREFIX}{gen.gid:04d}.npz"
        gen.path = resilience.commit_npz(
            self.spill_dir, name,
            dict(
                fps=merged,
                meta=np.asarray(
                    [GEN_VERSION, gen.gid, gen.n, depth,
                     self.part_d, self.owner],
                    np.int64,
                ),
            ),
            kind="compact", depth=depth, run_fp=self.run_fp,
        )
        gen.sidecar = sieve_mod.SpillSieve.build(merged)
        gen.sidecar_path = resilience.commit_npz(
            self.spill_dir, f"{GEN_PREFIX}{gen.gid:04d}{SIDECAR_SUFFIX}",
            dict(
                words=gen.sidecar.words,
                meta=np.asarray(
                    [sieve_mod.SIEVE_VERSION, gen.gid, gen.n,
                     len(gen.sidecar.words)],
                    np.int64,
                ),
            ),
            kind="sieve", depth=depth, run_fp=self.run_fp,
        )
        # the merged run is durable — NOW swap the in-memory view and
        # discard the inputs (their side-cars ride along)
        drop_names = []
        for g in cold:
            if g.path is not None:
                drop_names.append(os.path.basename(g.path))
            if g.sidecar_path is not None:
                drop_names.append(os.path.basename(g.sidecar_path))
            self._cold_cache.pop(g.gid, None)
        cold_ids = {g.gid for g in cold}
        self.gens = [gen] + [
            g for g in self.gens if g.gid not in cold_ids
        ]
        if drop_names:
            resilience.discard_artifacts(self.spill_dir, drop_names)
        self._enforce_warm()
        dt = time.monotonic() - t0
        self.stats["compactions"] += 1
        self.stats["compact_runs"] += len(cold)
        self.stats["compact_s"] += dt
        _obs.tier_compact(depth, len(cold), gen.n, dt)

    # -- side-cars --------------------------------------------------------

    def _gen_sidecar(self, g: Generation):
        """The run's bloom filter, or None when unavailable.

        Warm-held side-cars return instantly; a committed one lazily
        loads with full validation — manifest digest (catches torn and
        flipped bytes after commit), format version and (gid, n, words)
        meta (catches a stale side-car adopted across a crashed
        compaction).  ANY failure quarantines the file and REBUILDS the
        filter from the generation itself (one disk load — the same
        cost a missing side-car always had), so a bad side-car can
        never manufacture a false negative."""
        if g.sidecar is not None:
            return g.sidecar
        if g.sidecar_path is None:
            return None
        from ..ops import sieve as sieve_mod
        from ..resilience import manifest as _manifest

        name = os.path.basename(g.sidecar_path)
        try:
            state = _manifest.Manifest.load(
                os.path.dirname(g.sidecar_path)
            ).verify(name)
            if state != "ok":
                raise IOError(f"side-car {name}: manifest says {state}")
            with np.load(g.sidecar_path) as z:
                words = np.asarray(z["words"], np.uint64)
                meta = np.asarray(z["meta"], np.int64)
            if (
                meta[0] != sieve_mod.SIEVE_VERSION or meta[1] != g.gid
                or meta[2] != g.n or meta[3] != len(words)
                or len(words) == 0 or len(words) & (len(words) - 1)
            ):
                raise IOError(
                    f"side-car {name}: stale meta {meta.tolist()} for "
                    f"generation (gid={g.gid}, n={g.n})"
                )
            g.sidecar = sieve_mod.SpillSieve.from_words(
                words, n_added=int(meta[2])
            )
        except Exception as e:  # graftlint: waive[GL003] — a side-car
            # is acceleration state with a full fallback: quarantine
            # whatever failed (digest, zip, meta) and rebuild from the
            # generation run, which IS membership-authoritative
            import sys

            print(
                f"[tiered] side-car {name} quarantined ({e}); "
                "rebuilding from the generation run", file=sys.stderr,
            )
            from .. import resilience

            resilience.discard_artifacts(
                os.path.dirname(g.sidecar_path), [name]
            )
            g.sidecar_path = None
            g.sidecar = sieve_mod.SpillSieve.build(self._gen_fps(g))
            self.stats["sidecar_rebuilds"] += 1
        return g.sidecar

    def _enforce_warm(self) -> None:
        """Evict the oldest warm generations to cold (disk-only) until
        warm residency fits the budget.  Without a committed file the
        generation must stay warm — RAM is then the only copy."""
        def warm_bytes():
            return (
                sum(g.nbytes for g in self.gens if g.fps is not None)
                + sum(v.nbytes for v in self._cold_cache.values())
            )

        while self._cold_cache and warm_bytes() > self.warm_bytes:
            self._cold_cache.popitem(last=False)
        for g in self.gens:
            if warm_bytes() <= self.warm_bytes:
                break
            if g.fps is not None and g.path is not None:
                g.fps = None
                self.stats["cold_gens"] += 1

    # -- probes -----------------------------------------------------------

    def _gen_fps(self, g: Generation) -> np.ndarray:
        if g.fps is not None:
            return g.fps
        hit = self._cold_cache.get(g.gid)
        if hit is not None:
            self._cold_cache.move_to_end(g.gid)
            return hit
        t0 = time.monotonic()
        fps = _load_gen_fps(g.path)
        self.stats["cold_loads"] += 1
        self.stats["cold_load_s"] += time.monotonic() - t0
        self._cold_cache[g.gid] = fps
        self._enforce_warm()
        return fps

    def probe(self, fps: np.ndarray, *, level: int = 0) -> np.ndarray:
        """hit bool[N]: which fingerprints are in some generation.

        Probe order: sieve (confirmed spilled-visited cache) first,
        then warm generations oldest-first, then cold ones — each with
        the free [lo, hi] range reject.  Hits feed back into the sieve
        so repeat offenders (between their first hit and the hot
        re-heat landing) never reach the cold tier twice."""
        t0 = time.monotonic()
        fps = np.asarray(fps, np.uint64)
        hit = np.zeros(len(fps), bool)
        live = fps != SENT
        self.stats["probes"] += 1
        self.stats["probe_lanes"] += int(live.sum())
        sieve_this = 0
        if len(self.sieve):
            pos = np.searchsorted(self.sieve, fps)
            sh = live & (
                self.sieve[np.clip(pos, 0, len(self.sieve) - 1)] == fps
            )
            sieve_this = int(sh.sum())
            self.stats["sieve_hits"] += sieve_this
            hit |= sh
        pending = live & ~hit
        for g in self.gens:
            if not pending.any():
                break
            if not g.n:
                continue
            inr = pending & (fps >= g.lo) & (fps <= g.hi)
            if not inr.any():
                continue
            was_cold = g.fps is None and g.gid not in self._cold_cache
            if was_cold:
                # bloom side-car first: a definite miss for every
                # in-range lane means the disk run CANNOT hold any of
                # them (no false negatives) — skip the cold load
                # entirely; a filter hit (true or false positive) pays
                # the exact searchsorted probe below
                sc = self._gen_sidecar(g)
                if sc is not None and not sc.contains(fps[inr]).any():
                    self.stats["sidecar_skips"] += 1
                    continue
            run = self._gen_fps(g)
            pos = np.searchsorted(run, fps[inr])
            gh = run[np.clip(pos, 0, len(run) - 1)] == fps[inr]
            if gh.any():
                idx = np.nonzero(inr)[0][gh]
                hit[idx] = True
                pending[idx] = False
                key = "cold_hits" if was_cold else "warm_hits"
                self.stats[key] += int(gh.sum())
        n_hit = int(hit.sum())
        self.stats["probe_hits"] += n_hit
        if n_hit:
            self._sieve_add(fps[hit])
        wait = time.monotonic() - t0
        self.stats["probe_wait_s"] += wait
        _obs.tier_probe(
            level, int(live.sum()), n_hit, sieve=sieve_this,
            wait_s=wait,
        )
        return hit

    def _sieve_add(self, fps: np.ndarray) -> None:
        merged = np.union1d(self.sieve, fps)
        if len(merged) > SIEVE_MAX:
            # drop the LOW half: arbitrary but deterministic — the
            # sieve is a cache, correctness never depends on it
            merged = merged[len(merged) // 2:]
        self.sieve = merged

    def all_fps(self) -> np.ndarray:
        """Every spilled fingerprint (degradation/debug path: the
        sorted-store fallback must absorb the whole union)."""
        if not self.gens:
            return np.empty(0, np.uint64)
        return np.unique(
            np.concatenate([self._gen_fps(g) for g in self.gens])
        )

    # -- resume -----------------------------------------------------------

    def rebuild(self, level_fps, *, hot_slots: int) -> np.ndarray:
        """Re-tier a delta-log replay: feed per-level fingerprint
        arrays oldest-first; whole levels demote together whenever the
        accumulated hot set would no longer fit ``hot_slots`` at the
        <= 1/2 load factor.  Returns the fingerprints that stay hot.
        Generations built here cover whole levels, so they are DISJOINT
        and the tier total is exactly the replayed distinct count."""
        # one under the half-slot mark, like max_hot_entries: exactly
        # hot_slots/2 entries would make slab_rows round up to a slab
        # twice the budget
        budget = max(hot_slots // 2 - 1, 1)
        acc: list[np.ndarray] = []
        acc_n = 0
        last_depth = 0
        for depth, fps in level_fps:
            fps = np.asarray(fps, np.uint64)
            if acc_n and acc_n + len(fps) > budget:
                self.demote(np.concatenate(acc), depth=last_depth)
                acc, acc_n = [], 0
            while len(fps) > budget:
                # one level bigger than the whole hot tier (monolith
                # seeds, deep-level replays): split it across runs —
                # disjointness holds, membership is a union
                self.demote(fps[:budget], depth=depth)
                fps = fps[budget:]
            acc.append(fps)
            acc_n += len(fps)
            last_depth = depth
        return (
            np.concatenate(acc) if acc else np.empty(0, np.uint64)
        )


def sweep_gens(ckdir: str) -> int:
    """Discard every committed generation file AND bloom side-car in a
    checkpoint directory (the ``gen_*.npz`` glob matches
    ``gen_*.sieve.npz`` too).  Resume rebuilds the tier layout from the
    delta log, so stale runs from the crashed incarnation are noise —
    and sweeping them FIRST is what makes a kill mid-compaction safe:
    the commit-then-discard window can leave both the merged run and
    its inputs on disk, and only this sweep guarantees the overlapping
    sets never double-count (the resume re-demotes a fresh, disjoint
    generation sequence).  Orphaned ``.tmp_*`` files are the atomic
    writer's own sweep; this extends that hygiene to the committed-but-
    stale class."""
    import glob

    from .. import resilience

    names = [
        os.path.basename(f)
        for f in glob.glob(os.path.join(ckdir, f"{GEN_PREFIX}*.npz"))
    ]
    if names:
        resilience.discard_artifacts(ckdir, names)
    return len(names)


class FrontierPager:
    """Warm-tier paging for frontier segments (``kind="fseg"``).

    The visited tiers bound the SLAB's residency; this pager bounds the
    FRONTIER's.  A deep level's working set is (parent segments +
    sealed child segments); once the engine's host-RAM paging
    (`engine/bfs._HostSeg`) itself outgrows ``TLA_RAFT_FSEG_BYTES``,
    the overflow segments commit here through the same atomic
    ``commit_npz`` machinery the generations use — crash mid-write
    leaves only a ``.tmp_*`` the writer's own sweep removes, a
    committed-but-orphaned segment is swept on resume
    (:func:`sweep_fsegs`; the delta log rebuilds frontiers, so fseg
    files are NEVER a recovery input).  Spilled segments reload on
    demand when the next level's walk reaches them — the walks consume
    segments in ascending order, so residency is a moving window over
    the level, not the whole level.

    All methods are host-side numpy (no device dispatch, GL007).
    """

    def __init__(self, spill_dir: str, *, run_fp: str | None = None):
        self.spill_dir = spill_dir
        self.run_fp = run_fp
        self._next_tok = 0
        self._names: dict[int, str] = {}
        self.stats = dict(
            fseg_spills=0, fseg_loads=0, fseg_bytes=0,
            fseg_load_s=0.0, fseg_live_peak=0,
        )

    @property
    def live(self) -> int:
        return len(self._names)

    def spill(self, fields: dict, *, depth: int = -1) -> int:
        """Commit one frontier segment's field dict; returns a token."""
        from .. import resilience

        tok = self._next_tok
        self._next_tok += 1
        name = f"{FSEG_PREFIX}{tok:05d}.npz"
        resilience.commit_npz(
            self.spill_dir, name, dict(fields), kind="fseg",
            depth=depth, run_fp=self.run_fp,
        )
        self._names[tok] = name
        self.stats["fseg_spills"] += 1
        self.stats["fseg_bytes"] += sum(
            int(np.prod(v.shape)) * v.dtype.itemsize
            for v in fields.values()
        )
        self.stats["fseg_live_peak"] = max(
            self.stats["fseg_live_peak"], self.live
        )
        return tok

    def load(self, tok: int) -> dict:
        """Page one spilled segment back into host RAM."""
        t0 = time.monotonic()
        path = os.path.join(self.spill_dir, self._names[tok])
        with np.load(path) as z:
            fields = {k: z[k] for k in z.files}
        self.stats["fseg_loads"] += 1
        self.stats["fseg_load_s"] += time.monotonic() - t0
        _obs.fseg_page(tok, fields["voted_for"].shape[0],
                       time.monotonic() - t0)
        return fields

    def retire(self, toks) -> None:
        """Discard consumed segments' artifacts (one manifest commit)."""
        from .. import resilience

        names = [self._names.pop(t) for t in toks if t in self._names]
        if names:
            resilience.discard_artifacts(self.spill_dir, names)

    def retire_all(self) -> None:
        self.retire(list(self._names))


def sweep_fsegs(ckdir: str) -> int:
    """Discard every committed frontier-segment file in a checkpoint
    directory.  Frontier segments are per-level transients — resume
    rebuilds the frontier from the delta log, so fseg files from a
    crashed incarnation are pure noise (and, unswept, dead disk)."""
    import glob

    from .. import resilience

    names = [
        os.path.basename(f)
        for f in glob.glob(os.path.join(ckdir, f"{FSEG_PREFIX}*.npz"))
    ]
    if names:
        resilience.discard_artifacts(ckdir, names)
    return len(names)


def repartition(gens: list[np.ndarray], d_new: int) -> list[np.ndarray]:
    """Owner-remap a generation set onto ``d_new`` owners (fp % D').

    The same move PR 8's elastic resume applies to hash slabs, pointed
    at spilled runs: the input runs' union re-buckets into one sorted
    run per new owner.  Exact for any old partition — membership is a
    union, and re-sorting per bucket keeps every probe
    searchsorted-exact."""
    allf = (
        np.unique(np.concatenate([np.asarray(g, np.uint64) for g in gens]))
        if gens else np.empty(0, np.uint64)
    )
    return [
        np.ascontiguousarray(allf[(allf % np.uint64(d_new)) == o])
        for o in range(d_new)
    ]


# -- the row-compaction kernel (the one device program of this module) ----

def drop_rows_impl(tree, keep, n_keep):
    """Compact a materialized frontier's kept rows to the prefix.

    ``tree`` is any pytree of [cap, ...] arrays (the engine's Frontier),
    ``keep`` bool[cap] (True rows survive the generation probe),
    ``n_keep`` their count (traced).  Kept rows keep their relative
    order (stable argsort) — the payload-ascending order every engine
    pins — and dead rows zero out exactly like the staged path's padded
    frontier tail.  One data-indexed gather per field (the honest
    residue GL010 ledgers as ``store.tiered_compact``)."""
    import jax
    import jax.numpy as jnp

    cap = keep.shape[0]
    order = jnp.argsort(~keep, stable=True)
    lane = jnp.arange(cap)

    def one(x):
        live = (lane < n_keep).reshape((cap,) + (1,) * (x.ndim - 1))
        return jnp.where(live, x[order], jnp.zeros_like(x))

    return jax.tree.map(one, tree)


@functools.cache
def _drop_rows_jit():
    import jax

    return jax.jit(drop_rows_impl)


def drop_rows(tree, keep, n_keep):
    return _drop_rows_jit()(tree, keep, n_keep)


def ledger_trace(cfg=None):
    """Closed jaxpr of the compaction kernel at the audit's tiny
    reference shapes — the graftlint layer-2 registration (GL010): the
    budget pins one gather per frontier field, nothing data-indexed
    beyond that."""
    import jax
    import jax.numpy as jnp

    from ..config import RaftConfig
    from ..engine.bfs import JaxChecker
    from ..models.raft import init_batch

    if cfg is None:
        cfg = RaftConfig(
            n_servers=2, n_vals=1, max_election=1, max_restart=1,
        )
    eng = JaxChecker(cfg, chunk=64, use_hashstore=True)
    fr0, _ovf = eng._deflate(init_batch(cfg, 1))
    fr = eng._frontier_struct(fr0, 64)
    keep = jax.ShapeDtypeStruct((64,), jnp.bool_)
    n = jax.ShapeDtypeStruct((), jnp.int64)
    return jax.make_jaxpr(drop_rows_impl)(fr, keep, n)
