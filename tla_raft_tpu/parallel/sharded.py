"""Mesh-parallel BFS: the distributed-communication backend.

The reference's only parallelism is TLC's shared-memory worker pool
(``-workers 4``, /root/reference/myrun.sh:3); its distributed mode is
unused.  The TPU-native replacement shards the **frontier** over a 1-D
device mesh axis ``d``: each device expands its own states, candidate
fingerprints are exchanged for dedup, and (all_to_all mode) each NEW
state's full ~700 B crosses the interconnect exactly once — origin to
owner shard (``fp % D``) — so the next frontier is hash-balanced across
devices.  (Rounds 2-4 kept children on their parents' device; since
everything descends from the one init state, the whole frontier stayed
on device 0 and the mesh load-balanced nothing — the round-4 depth-13
chain records n_local = [N, 0, ..., 0] at every level.)  Two exchange
strategies:

* ``all_gather`` (small scale): each device locally pre-dedups its
  candidate fingerprints (lexsort + unique), an ``all_gather`` shares the
  compacted survivors, and every device runs the same global dedup
  against a **replicated** visited store, keeping the winners it
  originated.

* ``all_to_all`` (the scaling design, BASELINE.json north star): the
  visited store is **hash-sharded** — device ``o`` owns fingerprint
  ``fp`` iff ``fp % D == o``.  Each device routes its pre-deduped
  candidates to their owners with one ``lax.all_to_all``, owners dedup
  against their store shard (every copy of a fingerprint reaches the
  same owner, so dedup is exact), update the shard in place, and return
  one verdict bit per candidate with a reverse ``all_to_all``.  Nothing
  is replicated; per-level interconnect traffic is ~16 bytes per
  candidate fingerprint.

Determinism: representative choice is min (fp_view, fp_full, payload)
under a global total order, so results are identical for any device
count and to the single-device engine (engine/bfs.py) and the Python
oracle — the parity tests assert exactly that.

Invariant checking runs on each device over its freshly materialized
children; counterexample traces replay the (slot) chain from Init just
like the single-device engine.
"""

from __future__ import annotations

import functools
import os
import sys
import time
from types import SimpleNamespace
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import resilience
from ..analysis import sanitize as graft_sanitize
from ..obs import telemetry as graft_obs
from ..config import RaftConfig
from ..engine import pipeline as graft_pipeline
from ..engine.bfs import _compact_payloads
from ..engine.forecast import pow2ceil
from ..engine.invariants import resolve_invariant_kernel
from ..ops import hashstore, radix
from ..ops import tables as _tables
from ..ops.radix import stable_order, true_first
from ..models.raft import RaftState, init_batch, to_oracle
from ..ops.successor import get_kernel
from .exchange import (
    ExchangeMeter, pack_fp_deltas, packed_quantum, unpack_fp_deltas,
)

U64 = jnp.uint64
I64 = jnp.int64
I32 = jnp.int32
# numpy scalar, not jnp (device-free import; see engine/bfs.py)
SENT = np.uint64(0xFFFFFFFFFFFFFFFF)


def _shard_map(body, mesh, in_specs, out_specs):
    """``jax.shard_map`` with ``check_vma=False``: the scatter-in-switch
    inside materialize trips the varying-axis type checker, while the
    bodies are plain SPMD with explicit collectives."""
    return jax.shard_map(
        body, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )


def _owner_order(owner, D: int):
    """Lanes grouped by owner (values 0..D, D = dead lane), stable."""
    return stable_order((owner,), bits=(D.bit_length(),))


def _pmax(x, axis: str):
    """Exact max over a mesh axis.  XLA:TPU lowers a 64-bit all-reduce
    only as a sum, so the per-device values are all-gathered and reduced
    locally (they are scalars: the gather is a few bytes)."""
    return jax.lax.all_gather(x, axis).max(0)


def make_mesh(n_devices: int | None = None) -> Mesh:
    devs = jax.devices()
    if n_devices is not None:
        if len(devs) < n_devices:
            raise ValueError(
                f"requested a {n_devices}-device mesh but only "
                f"{len(devs)} device(s) are visible "
                f"({[str(d) for d in devs]}); for a virtual CPU mesh set "
                "JAX_PLATFORMS=cpu and "
                "XLA_FLAGS=--xla_force_host_platform_device_count=N"
            )
        devs = devs[:n_devices]
    return Mesh(np.array(devs), axis_names=("d",))


class Phase1Out(NamedTuple):
    """Host-store mode, phase 1: expand + local pre-dedup + owner routing.

    The visited filter moves OFF the device (per-owner external stores,
    native/fpstore.cpp) — phase 1 stops after the routing ``all_to_all``;
    the host filters each owner's level-unique candidates through its
    store shard; phase 2 carries the verdicts back and materializes."""

    cv: jnp.ndarray  # u64[cap_x] compacted local candidates (origin side)
    cf: jnp.ndarray  # u64[cap_x]
    cp: jnp.ndarray  # i64[cap_x]
    rv: jnp.ndarray  # u64[D, cap_r] owner-side recv (fp_view)
    rf: jnp.ndarray  # u64[D, cap_r]
    rp: jnp.ndarray  # i64[D, cap_r]
    mult_slots: jnp.ndarray  # i64[K] psum'd per-slot fired counts
    abort: jnp.ndarray  # bool[] any split-brain abort (psum'd)
    abort_at: jnp.ndarray  # i64[1]
    overflow_x: jnp.ndarray  # bool[] candidate/routing capacity exceeded
    cand_max: jnp.ndarray  # i64[] max per-device candidate count (pmax'd)


class Phase1DeepOut(NamedTuple):
    """Deep-sweep phase 1: expand one frontier segment + sieve + route.

    Like :class:`Phase1Out` but segment-relative (the frontier is a LIST
    of uniform 1/D-sharded segments) and sieved: candidates found in the
    device's sieve cache (fingerprints it routed in a PREVIOUS level —
    provably already in the external store) are dropped before the
    routing ``all_to_all``, which is what shrinks both collective and
    host-link traffic at deep levels where most candidates are
    re-generated duplicates (arXiv:1208.5542's sieve)."""

    cv: jnp.ndarray  # u64[cap_x] sieved compacted candidates (origin side)
    cf: jnp.ndarray  # u64[cap_x]
    cp: jnp.ndarray  # i64[cap_x] payloads — KEPT AT ORIGIN, never routed
    rv: jnp.ndarray  # u64[D, cap_r] owner-side recv (fp_view)
    rf: jnp.ndarray  # u64[D, cap_r] (fp_full — the representative key)
    mult_slots: jnp.ndarray  # i64[K] psum'd per-slot fired counts
    abort: jnp.ndarray  # bool[] any split-brain abort (psum'd)
    abort_at: jnp.ndarray  # i64[1] device-local frontier row or -1
    overflow_x: jnp.ndarray  # bool[] candidate capacity exceeded (psum'd)
    n_pre: jnp.ndarray  # i64[] candidates before the sieve (psum'd)
    n_post: jnp.ndarray  # i64[] candidates actually routed (psum'd)
    cand_max: jnp.ndarray  # i64[] max per-device pre-sieve count (pmax'd)


class DeepFinOut(NamedTuple):
    """Owner-side level finalize: exact dedup + delta-packed fp stream.

    The owner lexsorts EVERY routed candidate of the level (all segment
    rounds), picks the min-(fp_full, payload) representative per view
    fingerprint — the same global choice the host filter used to make,
    now on device — and emits only the sorted unique fingerprints,
    delta-packed (parallel/exchange.py), for the host store verdict."""

    stream: jnp.ndarray  # u8[cap_acc*8] packed delta bytes
    nib: jnp.ndarray  # u8[cap_acc//2] per-entry byte lengths (4-bit)
    n_u: jnp.ndarray  # i64[1] unique candidates this owner
    total: jnp.ndarray  # i64[1] live bytes of ``stream``
    n_recv_sum: jnp.ndarray  # i64[] psum: routed lanes received
    n_u_sum: jnp.ndarray  # i64[] psum: unique candidates mesh-wide


class Phase2Out(NamedTuple):
    children: RaftState
    child_msum: jnp.ndarray
    n_new_local: jnp.ndarray  # i64[1]
    n_new_total: jnp.ndarray  # i64[]
    gpidx: jnp.ndarray
    slots: jnp.ndarray
    inv_bad: jnp.ndarray
    inv_bad_at: jnp.ndarray  # i64[1]
    ovf_w: jnp.ndarray  # bool[] (origin, owner) shipping rows exceeded
    ovf_c: jnp.ndarray  # bool[] an owner's frontier block overflowed


class LevelOut(NamedTuple):
    """Per-device outputs of one distributed BFS level (shard_map body)."""

    children: RaftState  # [cap_c, ...] local new states (padded)
    child_msum: jnp.ndarray  # u32[cap_c, P, chan]
    visited: jnp.ndarray  # u64[vcap] updated store shard (all_to_all mode)
    n_new_local: jnp.ndarray  # i64[1] this device's new states
    n_new_total: jnp.ndarray  # i64[] psum over mesh
    generated: jnp.ndarray  # i64[] psum over mesh
    mult_slots: jnp.ndarray  # i64[K] psum'd per-slot fired counts
    gpidx: jnp.ndarray  # i64[cap_c] global parent index (dev*cap_f+i)
    slots: jnp.ndarray  # i64[cap_c] local slots (for traces)
    inv_bad: jnp.ndarray  # i32[] psum'd violation count this level
    inv_bad_at: jnp.ndarray  # i64[1] local index of first violation or -1
    abort: jnp.ndarray  # bool[] any split-brain abort (psum'd)
    abort_at: jnp.ndarray  # i64[1] local frontier index of first abort or -1
    overflow_x: jnp.ndarray  # bool[] candidate/routing capacity exceeded
    overflow_v: jnp.ndarray  # bool[] visited-shard capacity exceeded
    cand_max: jnp.ndarray  # i64[] max per-device candidate count (pmax'd)
    # cand_max feeds the presize forecast an OBSERVED candidates-per-new
    # ratio, replacing the hand-tuned margin that under-sized cap_x


class CheckResult(NamedTuple):
    ok: bool
    distinct: int
    generated: int
    depth: int
    level_sizes: tuple[int, ...]
    violation: tuple | None
    action_counts: dict | None = None


def _compact(mask, take_n, *arrays, fills):
    """Stable-compact ``arrays`` rows where ``mask`` into ``take_n`` lanes."""
    comp = true_first(mask)
    take = jnp.arange(take_n)
    src = comp[jnp.clip(take, 0, comp.shape[0] - 1)]
    lane = (take < mask.sum()) & (take < comp.shape[0])
    return tuple(
        jnp.where(lane, a[src], fill) for a, fill in zip(arrays, fills)
    ) + (lane,)


class ShardedChecker:
    """Distributed model checker over a 1-D device mesh.

    Parameters:
      cap_x: per-device compacted-candidate capacity per level (deep
             mode: per segment round).  Default: 4096; in deep mode
             8 x ``seg_rows``, so the deep programs compile once.
      vcap:  per-device visited-shard capacity (all_to_all mode; grows on
             demand by the host driver).
      exchange: "all_to_all" (sharded store) or "all_gather" (replicated).
      cap_x_max: ceiling for PREDICTIVE cap_x sizing only (run(presize=
             True)).  The growth forecast can overshoot ~2x early in a
             run, and at pow2 granularity that doubles the one big
             compile; an operator who has measured the real candidate
             peak (e.g. scripts/mesh_deep_parity.py) clamps the forecast
             here.  Reactive overflow growth ignores the ceiling — it is
             a sizing hint, never a correctness bound.
    """

    def __init__(
        self,
        cfg: RaftConfig,
        mesh: Mesh,
        cap_x: int | None = None,
        vcap: int = 1 << 16,
        exchange: str = "all_to_all",
        progress=None,
        canon: str = "late",
        host_store_dir: str | None = None,
        cap_x_max: int | None = None,
        deep: bool = False,
        seg_rows: int = 1 << 15,
        sieve: bool = True,
        compress: bool = True,
        scap: int | None = None,
        scap_max: int = 1 << 22,
        use_hashstore: bool | None = None,
        pipeline: bool | None = None,
        pipeline_window: int | None = None,
        use_mxu: bool | None = None,
        watchdog=None,
        warm_bytes: int | None = None,
    ):
        assert exchange in ("all_to_all", "all_gather")
        # async intra-level pipeline (engine/pipeline.py): the level's
        # big device->host fetches (routed candidates on the hosted
        # path, the repacked trace arrays in deep mode, gpidx/slots on
        # the resident path) go through a bounded AsyncFetchWindow —
        # copies start the moment their producer is dispatched and
        # complete through the LEDGERED get only after the remaining
        # level-tail device work has been dispatched.  Counts are
        # bit-identical either way; TLA_RAFT_PIPELINE=0 reverts to the
        # serial fetch-after-dispatch chain.
        if pipeline is None:
            pipeline = graft_pipeline.enabled_by_env()
        if pipeline_window is None:
            pipeline_window = graft_pipeline.window_from_env()
        self.pipeline_window = int(pipeline_window)
        self.pipeline = bool(pipeline) and self.pipeline_window >= 1
        # deep-sweep tier: the frontier itself is sharded 1/D — each
        # device holds its owner share (fp % D) as a list of uniform
        # ``seg_rows``-row segments, the level loop expands segment by
        # segment, owners dedup the whole level's candidates exactly on
        # device, and only sieved/compressed fingerprint streams cross
        # the host link.  Requires the owner-sharded external stores.
        if deep:
            if host_store_dir is None:
                raise ValueError(
                    "deep=True requires host_store_dir (the sharded "
                    "deep sweep filters through per-owner external "
                    "stores)"
                )
            if canon != "late":
                raise ValueError("deep=True requires canon='late'")
            if exchange != "all_to_all":
                raise ValueError("deep=True requires exchange='all_to_all'")
            if seg_rows % 2:
                raise ValueError("seg_rows must be even")
        self.deep = deep
        self.seg_rows = seg_rows
        # open-addressing fingerprint slabs (ops/hashstore.py) for the
        # two mesh-side membership structures keyed fp % D: the owner
        # visited shards of the plain all_to_all mode (replacing the
        # per-level lexsort + searchsorted + sorted merge) and the deep
        # mode's pre-routing sieve cache (the sieve becomes a plain
        # probe; updates become O(1) inserts instead of a sort-merge).
        # Default ON; TLA_RAFT_HASHSTORE=0 / --no-hashstore reverts.
        # all_gather keeps its replicated sorted store (its dedup IS a
        # global sort — there is no probe structure to replace).
        if use_hashstore is None:
            use_hashstore = hashstore.enabled_by_env()
        self.use_hashstore = bool(use_hashstore) and exchange == "all_to_all"
        self.sieve = sieve
        self.compress = compress
        if scap is None:
            # deep mode: the sieve slab is a phase-1 argument, so each
            # growth recompiles phase 1 (~140 s for a v5e at the default
            # seg_rows, PR 21).  Start it at 16 full rounds of
            # candidates (128 x seg_rows, 32 MB per device by default),
            # within scap_max.
            scap = 1 << 12
            if deep:
                scap = min(scap_max, max(scap, pow2ceil(128 * seg_rows)))
        self.scap = scap
        self.scap_max = scap_max
        self.meter = ExchangeMeter()
        self._dp: dict = {}  # deep-mode compiled programs (keyed by statics)
        self._cap_c_boost = 1  # deep phase-2 owner recv block growth
        # mesh x external store (VERDICT r3 missing #4 / next #6): the
        # visited set leaves the devices entirely — one HostFPStore per
        # owner shard (fp % D keying matches the all_to_all routing), the
        # host filters after the routing exchange.  North-star configs
        # exceed D*HBM on small meshes; this is TLC's states/ spill
        # composed with its worker pool (/root/reference/.gitignore:2).
        if host_store_dir is not None:
            if exchange != "all_to_all":
                raise ValueError(
                    "host_store_dir requires exchange='all_to_all' (the "
                    "store is owner-sharded by fp % D)"
                )
            if canon != "late":
                raise ValueError("host_store_dir requires canon='late'")
        self.host_store_dir = host_store_dir
        self.host_stores = None  # built lazily in run()
        # host-RAM budget for the WARM tier, split across the D
        # per-owner stores: each shard buffers warm_bytes/D in RAM and
        # spills sorted runs (the cold generations of the mesh paths,
        # partition-tagged by their shard directory = fp % D) to disk
        # past it — an elastic D -> D' resume rebuilds them from the
        # mdelta replay under the new owner map (store/tiered.py
        # repartition is the same move applied to raw runs)
        self.warm_bytes = warm_bytes
        # canon="late" (default): guards-only expand, then materialize +
        # full-state-fingerprint only the compacted candidates — no
        # P-sized per-lane intermediates and no per-state msum carried in
        # the frontier (see engine/bfs.py).  canon="expand": the round-2
        # per-lane incremental-hash formulation, kept as a reference.
        assert canon in ("late", "expand")
        self.canon = canon
        self.cfg = cfg
        self.mesh = mesh
        # MXU-native expand (ops/mxu_expand.py): both mesh paths route
        # their guards/materialize through the kernel, so the selection
        # happens here once; TLA_RAFT_MXU=0 / --no-mxu-expand reverts
        self.kern = get_kernel(cfg, mxu=use_mxu)
        self.use_mxu = self.kern.use_mxu
        self.fpr = self.kern.fpr
        self.K = self.kern.K
        self.D = mesh.devices.size
        # the mesh programs take the kernel's device tables as their
        # first operand (ops/tables.py), replicated on every device
        # graftlint: waive[GL006] — one-time upload at construction
        self.kernel_tables = jax.device_put(
            self.kern.device_tables, NamedSharding(mesh, P())
        )
        if cap_x is None:
            # deep mode: a segment round routes ~4 candidates per parent
            # (3.9 on the reference config, PR 21), so 8 x seg_rows
            # holds a full round twice over and no capacity rung
            # recompiles the deep programs
            cap_x = max(4096, pow2ceil(8 * seg_rows)) if deep else 4096
        self.cap_x = cap_x
        self.cap_x_max = cap_x_max
        self.vcap = vcap
        self.exchange = exchange
        # reactive (mid-level) growth events this run: each one is a
        # full level-program recompile the presize forecast should have
        # prevented — scripts surface it (docs/MESH_DEEP.json)
        self.reactive_grows = 0
        self.progress = progress
        self.inv_fns = [(n, resolve_invariant_kernel(n)) for n in cfg.invariants]
        # semantic run fingerprint for the checkpoint manifests: spec
        # constants + the modes the mdelta record meta pins (exchange,
        # canon) — NOT tunables (cap_x, seg_rows) and, since the
        # elastic-resume work, NOT the device count: a D-device log
        # resumes on D' devices by owner remap (resilience/elastic.py),
        # so D is per-record geometry now, never log identity
        self._run_fp = resilience.run_config_fingerprint(
            cfg, log="mdelta", exchange=exchange, canon=canon
        )
        # per-owner level skew metrics (resilience/integrity.py): new
        # rows per owner every level, plus per-owner store-insert
        # seconds on the deep path — the --json "straggler" block
        self.skew = resilience.integrity.SkewMeter(self.D)
        # per-level hang watchdog (resilience/elastic.py); None = off
        self.watchdog = watchdog

    def _mesh_program(self, body, in_specs, out_specs):
        """The method ``body`` as a jitted mesh program whose first
        operand is the replicated kernel tables (ops/tables.py)."""
        return _tables.method_program(
            body, self.kernel_tables,
            wrap=lambda fn: _shard_map(
                fn, self.mesh, (P(),) + tuple(in_specs), out_specs
            ),
        )

    def _store_budget_entries(self) -> int:
        """Per-owner in-RAM entry budget of the external stores (0 =
        the native default): --warm-bytes split across the D shards."""
        if not self.warm_bytes:
            return 0
        return max(int(self.warm_bytes) // 8 // self.D, 1)

    def _legacy_run_fps(self) -> tuple[str, ...]:
        """Pre-elastic run fingerprints of THIS semantic run: the old
        digest schema pinned the writing mesh's device count, which an
        elastic resume cannot know up front — accept the variant for
        every plausible width so an upgraded deployment's in-progress
        checkpoints stay resumable (heal_log migrates the manifest to
        the D-free form on first touch)."""
        return tuple(
            resilience.run_config_fingerprint(
                self.cfg, log="mdelta", D=d, exchange=self.exchange,
                canon=self.canon,
            )
            for d in range(1, 129)
        )

    # -- the per-device level body ----------------------------------------

    def _expand_local(self, frontier, msum, n_f):
        """Expand + local pre-dedup; returns compacted candidates."""
        K = self.K
        cap_f = frontier.voted_for.shape[0]
        dev = jax.lax.axis_index("d").astype(I64)

        if self.canon == "late":
            valid, mult, ab_state = self.kern.expand_guards(frontier)
        else:
            exp = self.kern.expand(frontier, msum)
            valid, mult, ab_state = exp.valid, exp.mult, exp.abort
        in_range = (jnp.arange(cap_f) < n_f[0])[:, None]
        valid = valid & in_range
        gparent = dev * cap_f + jnp.arange(cap_f, dtype=I64)
        payload = (gparent[:, None] * K + jnp.arange(K, dtype=I64)[None]).ravel()
        mult_slots = jax.lax.psum(
            jnp.where(valid, mult, 0).astype(I64).sum(0), "d"
        )
        abort_local = ab_state & in_range[:, 0]
        abort = jax.lax.psum(abort_local.any().astype(I32), "d") > 0
        abort_at = jnp.where(
            abort_local.any(), jnp.argmax(abort_local), -1
        ).astype(I64)

        if self.canon == "late":
            # compact the valid (parent, slot) lanes, materialize them
            # locally, and fingerprint the children from their full
            # states — the symmetry fold runs over cap_x candidates, not
            # cap_f*K fan-out lanes (see engine/bfs.py)
            cp_raw, lane, overflow = _compact_payloads(
                valid.ravel(), payload, self.cap_x
            )
            # graftlint: waive[GL005] — device-local row, < cap_f <= 2^31
            lidx = ((cp_raw // K) % cap_f).astype(I32)
            parents = jax.tree.map(lambda x: x[lidx], frontier)
            children = self.kern.materialize(parents, cp_raw % K)
            fv, ff, _msum = self.fpr.state_fingerprints(children)
            fpv = jnp.where(lane, fv.astype(U64), SENT)
            fpf = jnp.where(lane, ff.astype(U64), SENT)
            payload = jnp.where(lane, cp_raw, -1)
        else:
            fpv = jnp.where(valid, exp.fp_view, SENT).ravel()
            fpf = jnp.where(valid, exp.fp_full, SENT).ravel()

        # local pre-dedup: min (fp_full, payload) representative per view fp
        order = jnp.lexsort((payload, fpf, fpv))
        sv, sf, sp = fpv[order], fpf[order], payload[order]
        first = jnp.concatenate([jnp.ones((1,), bool), sv[1:] != sv[:-1]])
        keep = first & (sv != SENT)
        if self.canon != "late":
            overflow = keep.sum() > self.cap_x
        cv, cf, cp, _lane = _compact(
            keep, self.cap_x, sv, sf, sp, fills=(SENT, SENT, I64(-1))
        )
        return cv, cf, cp, mult_slots, abort, abort_at, overflow, dev, cap_f

    def _ship_winners_to_owners(self, frontier, cap_f, dev, oo, op,
                                win_sorted):
        """Materialize winning children at their ORIGIN (the parent's
        device) and route the full child states to their OWNER shard
        (fp % D) with one all_to_all per field.

        This is the load-balancing half the rounds 2-4 mesh never had:
        children used to stay with their parents, so the entire frontier
        cascaded from the init state's device and D-1 devices idled
        while device 0's candidate caps blew up (measured: the round-4
        depth-13 chain's n_local is [N, 0, ..., 0] at every level).
        Owner-claiming spreads the next frontier ~uniformly (fingerprints
        are pseudorandom), shrinking per-device expand load and cap_x by
        ~D.  Traffic: ~700 B/state origin->owner once per state lifetime
        — well inside ICI budgets, and the fp-only dedup exchanges are
        unchanged.

        Inputs are in owner-grouped candidate order (``oo`` = owner per
        lane, ``op`` = payload per lane, ``win_sorted`` = this origin's
        winners).  Returns (children, child_msum, gpidx, slots, lane,
        n_new_local, inv_bad, first_bad, ovf_w, ovf_c) — ``ovf_w``:
        some (origin, owner) pair exceeded the cap_w shipping rows
        (fix: grow cap_w); ``ovf_c``: an owner received more new states
        than its cap_x frontier block (fix: grow cap_x).
        """
        D, K = self.D, self.K
        cap_w = self.cap_w
        # winners are contiguous per owner group after a stable sort on
        # (not-winner, owner): group o's winners land at rows
        # wstarts[o] .. wstarts[o]+wcounts[o]
        wcounts = jnp.bincount(
            jnp.where(win_sorted, oo, D), length=D + 1
        )
        wstarts = jnp.cumsum(wcounts) - wcounts
        worder = jnp.argsort(jnp.where(win_sorted, oo, D), stable=True)
        idx = jnp.clip(
            wstarts[:D, None] + jnp.arange(cap_w, dtype=wstarts.dtype)[None, :],
            0, oo.shape[0] - 1,
        )
        lane_src = worder[idx]  # [D(owner), cap_w] winner lanes
        in_row = jnp.arange(cap_w)[None, :] < wcounts[:D, None]
        ovf_w = wcounts[:D].max() > cap_w
        spay = jnp.where(in_row, op[lane_src], 0)  # [D, cap_w]
        pidx = (spay // K) % cap_f
        slots = spay % K
        parents = jax.tree.map(
            lambda x: x[pidx.reshape(-1)], frontier
        )
        kids = self.kern.materialize(parents, slots.reshape(-1))
        gp_send = jnp.where(in_row, dev * cap_f + pidx, -1)

        def a2a(x):
            # senders pre-mask dead lanes (jnp.where above); the exchange
            # itself moves rows verbatim
            return jax.lax.all_to_all(
                x.reshape(D, cap_w, *x.shape[1:]), "d", 0, 0, tiled=True
            ).reshape(D * cap_w, *x.shape[1:])

        lane_r = a2a(in_row.astype(jnp.uint8).reshape(-1)).astype(bool)
        gp_r = a2a(gp_send.reshape(-1))
        sl_r = a2a(jnp.where(in_row, slots, 0).reshape(-1))
        kids_r = jax.tree.map(a2a, kids)
        # compact the received rows into this device's frontier block
        cap_c = self.cap_x
        comp = jnp.argsort(~lane_r, stable=True)
        take = jnp.clip(jnp.arange(cap_c), 0, comp.shape[0] - 1)
        src = comp[take]
        lane = (jnp.arange(cap_c) < lane_r.sum()) & (
            jnp.arange(cap_c) < comp.shape[0]
        )
        children = jax.tree.map(
            lambda x: jnp.where(
                lane.reshape((-1,) + (1,) * (x.ndim - 1)),
                x[src], jnp.zeros_like(x[src]),
            ),
            kids_r,
        )
        gpidx = jnp.where(lane, gp_r[src], -1)
        slots_c = jnp.where(lane, sl_r[src], -1)
        n_new_local = lane.sum().astype(I64)
        ovf_c = lane_r.sum() > cap_c
        child_msum = (
            self.fpr.msg_hash(children.msgs)
            if self.canon == "expand"
            else jnp.zeros((cap_c, 1, 1), jnp.uint32)
        )
        bad_local = jnp.zeros(cap_c, bool)
        for _name, fn in self.inv_fns:
            bad_local = bad_local | (
                ~fn(self.cfg, children, self.kern.tables) & lane
            )
        inv_bad = jax.lax.psum(bad_local.sum().astype(I32), "d")
        first_bad = jnp.where(
            bad_local.any(), jnp.argmax(bad_local), -1
        ).astype(I64)
        return (children, child_msum, gpidx, slots_c, lane, n_new_local,
                inv_bad, first_bad, ovf_w, ovf_c)

    @functools.cached_property
    def cap_w(self) -> int:
        # per-(origin, owner) shipping rows.  Steady state puts
        # n_new/D^2 winners on a pair; the healing case (a legacy
        # parent-local frontier concentrated on one device) puts
        # n_new/D on each of that origin's pairs — cap_x/2 covers both
        # with the reactive grow as backstop.  _cap_w_boost grows cap_w
        # alone (phase-2 retries must keep phase-1's cv/cp shapes).
        return max(256, self.cap_x // 2) * getattr(self, "_cap_w_boost", 1)

    def _children_from(self, frontier, cap_f, dev, wpay, wlane):
        """Materialize chosen (payload) slots locally + invariants."""
        K = self.K
        pidx = (wpay // K) % cap_f
        slots = wpay % K
        parents = jax.tree.map(lambda x: x[pidx], frontier)
        children = self.kern.materialize(parents, slots)
        # the per-state message-set hash partial is only carried between
        # levels by the canon="expand" incremental path; it is P-sized
        # per state, so the late path keeps a [cap, 1, 1] dummy instead
        child_msum = (
            self.fpr.msg_hash(children.msgs)
            if self.canon == "expand"
            else jnp.zeros((children.voted_for.shape[0], 1, 1), jnp.uint32)
        )
        children = jax.tree.map(
            lambda x: jnp.where(
                wlane.reshape((-1,) + (1,) * (x.ndim - 1)), x, jnp.zeros_like(x)
            ),
            children,
        )
        # invariants on the fresh level shard
        bad_local = jnp.zeros(children.voted_for.shape[0], bool)
        for _name, fn in self.inv_fns:
            bad_local = bad_local | (~fn(self.cfg, children, self.kern.tables) & wlane)
        inv_bad = jax.lax.psum(bad_local.sum().astype(I32), "d")
        has_bad = bad_local.any()
        first_bad = jnp.where(has_bad, jnp.argmax(bad_local), -1).astype(I64)
        gpidx = jnp.where(wlane, dev * cap_f + pidx, -1)
        return children, child_msum, gpidx, slots, inv_bad, first_bad

    def _body_all_gather(self, frontier, msum, n_f, visited):
        (cv, cf, cp, mult_slots, abort, abort_at, overflow, dev, cap_f) = (
            self._expand_local(frontier, msum, n_f)
        )
        n_cand = (cv != SENT).sum().astype(I64)  # pre-dedup: cap_x load
        pos = jnp.searchsorted(visited, cv)
        hit = visited[jnp.clip(pos, 0, visited.shape[0] - 1)] == cv
        cv = jnp.where(hit, SENT, cv)

        gv = jax.lax.all_gather(cv, "d").reshape(-1)
        gf = jax.lax.all_gather(cf, "d").reshape(-1)
        gp = jax.lax.all_gather(cp, "d").reshape(-1)
        gorder = jnp.lexsort((gp, gf, gv))
        gsv, gpay = gv[gorder], gp[gorder]
        gfirst = jnp.concatenate([jnp.ones((1,), bool), gsv[1:] != gsv[:-1]])
        gnew = gfirst & (gsv != SENT)
        n_new_total = gnew.sum().astype(I64)
        win = gnew & (gpay // (self.K * cap_f) == dev)
        n_new_local = win.sum().astype(I64)
        wpay, wlane = _compact(win, self.cap_x, gpay, fills=(I64(0),))
        children, child_msum, gpidx, slots, inv_bad, first_bad = self._children_from(
            frontier, cap_f, dev, wpay, wlane
        )
        # replicated store update (identical on every device)
        new_fps = jnp.where(gnew, gsv, SENT)
        visited = jnp.sort(jnp.concatenate([visited, new_fps]))[: visited.shape[0] + self.D * self.cap_x]
        return LevelOut(
            children, child_msum, visited,
            n_new_local[None], n_new_total,
            mult_slots.sum(), mult_slots,
            gpidx, jnp.where(wlane, slots, -1),
            inv_bad, first_bad[None], abort, abort_at[None],
            jax.lax.psum(overflow.astype(I32), "d") > 0,
            jnp.zeros((), bool),
            _pmax(n_cand, "d"),
        )

    def _body_all_to_all(self, frontier, msum, n_f, visited):
        """Owner-sharded dedup: fp % D owns; candidates route via all_to_all."""
        D, cap_x = self.D, self.cap_x
        cap_r = self.cap_r  # per-(src,dst) routing capacity
        (cv, cf, cp, mult_slots, abort, abort_at, overflow, dev, cap_f) = (
            self._expand_local(frontier, msum, n_f)
        )
        # --- route to owners ---------------------------------------------
        # sentinel lanes sort to a virtual group D past every real owner,
        # so they never land in a send row
        owner = jnp.where(cv == SENT, D, (cv % jnp.uint64(D)).astype(I64))
        oorder = jnp.argsort(owner, stable=True)  # candidates grouped by owner
        ov, of_, op, oo = cv[oorder], cf[oorder], cp[oorder], owner[oorder]
        counts = jnp.bincount(oo, length=D + 1)
        starts = jnp.cumsum(counts) - counts
        rank = jnp.arange(cap_x) - starts[oo]
        overflow_x = overflow | (counts[:D].max() > cap_r)
        rr = jnp.clip(rank, 0, cap_r - 1)
        ok_lane = (ov != SENT) & (rank < cap_r)
        # gather-based send-buffer build (no dynamic scatters on the mesh
        # path — XLA:TPU miscompiled this op class in the materialize pass,
        # docs/PERF.md): row o reads the owner-grouped lanes
        # starts[o] .. starts[o]+cap_r-1, masked to counts[o] entries
        idx = jnp.clip(
            starts[:D, None] + jnp.arange(cap_r, dtype=starts.dtype)[None, :],
            0,
            cap_x - 1,
        )
        in_row = jnp.arange(cap_r)[None, :] < counts[:D, None]
        sendv = jnp.where(in_row, ov[idx], SENT)
        sendf = jnp.where(in_row, of_[idx], SENT)
        sendp = jnp.where(in_row, op[idx], -1)
        rv = jax.lax.all_to_all(sendv, "d", 0, 0, tiled=True).reshape(D, cap_r)
        rf = jax.lax.all_to_all(sendf, "d", 0, 0, tiled=True).reshape(D, cap_r)
        rp = jax.lax.all_to_all(sendp, "d", 0, 0, tiled=True).reshape(D, cap_r)

        # --- owner-side dedup vs the store shard -------------------------
        qv, qf, qp = rv.reshape(-1), rf.reshape(-1), rp.reshape(-1)
        if self.use_hashstore:
            # one fused probe-and-insert: uniqueness, membership AND the
            # shard update — no lexsort over the recv lanes, no binary
            # search against the shard, no whole-shard re-sort.  The
            # min-(fp_full, payload) representative matches the lexsort
            # path's first-occurrence choice exactly (group-min lemma),
            # and verdicts come back already in recv-lane order (the
            # sorted path needs an inverse-permutation gather).  On
            # overflow the driver discards the level and grows the slab.
            upd, verdict, n_own_new, ovf_h = hashstore.probe_and_insert_impl(
                visited, qv, qf, qp
            )
            overflow_v = ovf_h | ((upd != SENT).sum() * 2 > visited.shape[0])
        else:
            qorder = jnp.lexsort((qp, qf, qv))
            qsv = qv[qorder]
            qfirst = jnp.concatenate(
                [jnp.ones((1,), bool), qsv[1:] != qsv[:-1]]
            )
            pos = jnp.searchsorted(visited, qsv)
            qhit = visited[jnp.clip(pos, 0, visited.shape[0] - 1)] == qsv
            qnew = qfirst & (qsv != SENT) & ~qhit
            n_own_new = qnew.sum()
            # update the shard (sorted merge, fixed capacity)
            vcount = (visited != SENT).sum()
            overflow_v = vcount + n_own_new > visited.shape[0]
            upd = jnp.sort(
                jnp.concatenate([visited, jnp.where(qnew, qsv, SENT)])
            )[: visited.shape[0]]
            # verdict bits back to origins, aligned to the recv layout
            # (inverse-permutation gather, not a scatter)
            verdict = qnew[jnp.argsort(qorder)]
        back = jax.lax.all_to_all(
            verdict.reshape(D, cap_r), "d", 0, 0, tiled=True
        ).reshape(D, cap_r)
        # my candidate i (owner-grouped order) sits at (oo[i], rank[i])
        win_sorted = back[jnp.clip(oo, 0, D - 1), rr] & ok_lane
        n_new_total = jax.lax.psum(n_own_new.astype(I64), "d")
        (children, child_msum, gpidx, slots, _lane, n_new_local,
         inv_bad, first_bad, ovf_w, ovf_c) = self._ship_winners_to_owners(
            frontier, cap_f, dev, oo, op, win_sorted
        )
        return LevelOut(
            children, child_msum, upd,
            n_new_local[None], n_new_total,
            mult_slots.sum(), mult_slots,
            gpidx, slots,
            inv_bad, first_bad[None], abort, abort_at[None],
            jax.lax.psum(
                (overflow_x | ovf_w | ovf_c).astype(I32), "d"
            ) > 0,
            jax.lax.psum(overflow_v.astype(I32), "d") > 0,
            _pmax(counts[:D].sum().astype(I64), "d"),
        )

    # -- host-store mode: the level split into two collective programs ----

    def _body_a2a_phase1(self, frontier, msum, n_f):
        """Expand + local pre-dedup + route to owners; no visited filter."""
        D, cap_x, cap_r = self.D, self.cap_x, self.cap_r
        (cv, cf, cp, mult_slots, abort, abort_at, overflow, _dev, _cap_f) = (
            self._expand_local(frontier, msum, n_f)
        )
        owner = jnp.where(cv == SENT, D, (cv % jnp.uint64(D)).astype(I64))
        oorder = jnp.argsort(owner, stable=True)
        ov, of_, op, oo = cv[oorder], cf[oorder], cp[oorder], owner[oorder]
        counts = jnp.bincount(oo, length=D + 1)
        starts = jnp.cumsum(counts) - counts
        overflow_x = overflow | (counts[:D].max() > cap_r)
        idx = jnp.clip(
            starts[:D, None] + jnp.arange(cap_r, dtype=starts.dtype)[None, :],
            0,
            cap_x - 1,
        )
        in_row = jnp.arange(cap_r)[None, :] < counts[:D, None]
        sendv = jnp.where(in_row, ov[idx], SENT)
        sendf = jnp.where(in_row, of_[idx], SENT)
        sendp = jnp.where(in_row, op[idx], -1)
        rv = jax.lax.all_to_all(sendv, "d", 0, 0, tiled=True).reshape(D, cap_r)
        rf = jax.lax.all_to_all(sendf, "d", 0, 0, tiled=True).reshape(D, cap_r)
        rp = jax.lax.all_to_all(sendp, "d", 0, 0, tiled=True).reshape(D, cap_r)
        return Phase1Out(
            cv, cf, cp, rv, rf, rp, mult_slots, abort, abort_at[None],
            jax.lax.psum(overflow_x.astype(I32), "d") > 0,
            _pmax(counts[:D].sum().astype(I64), "d"),
        )

    def _body_a2a_phase2(self, frontier, cv, cp, verdict_recv, n_f):
        """Verdicts back to origins; compact winners; materialize.

        The owner grouping is recomputed from ``cv`` — ``argsort`` over
        the same input is deterministic, so the lanes line up with the
        phase-1 send layout exactly."""
        D, cap_x, cap_r = self.D, self.cap_x, self.cap_r
        dev = jax.lax.axis_index("d").astype(I64)
        cap_f = frontier.voted_for.shape[0]
        owner = jnp.where(cv == SENT, D, (cv % jnp.uint64(D)).astype(I64))
        oorder = jnp.argsort(owner, stable=True)
        op, oo = cp[oorder], owner[oorder]
        counts = jnp.bincount(oo, length=D + 1)
        starts = jnp.cumsum(counts) - counts
        rank = jnp.arange(cap_x) - starts[oo]
        rr = jnp.clip(rank, 0, cap_r - 1)
        ok_lane = (cv[oorder] != SENT) & (rank < cap_r)
        back = jax.lax.all_to_all(
            verdict_recv, "d", 0, 0, tiled=True
        ).reshape(D, cap_r)
        win_sorted = back[jnp.clip(oo, 0, D - 1), rr] & ok_lane
        n_new_total = jax.lax.psum(win_sorted.sum().astype(I64), "d")
        (children, child_msum, gpidx, slots, _lane, n_new_local,
         inv_bad, first_bad, ovf_w, ovf_c) = self._ship_winners_to_owners(
            frontier, cap_f, dev, oo, op, win_sorted
        )
        return Phase2Out(
            children, child_msum, n_new_local[None], n_new_total,
            gpidx, slots, inv_bad, first_bad[None],
            jax.lax.psum(ovf_w.astype(I32), "d") > 0,
            jax.lax.psum(ovf_c.astype(I32), "d") > 0,
        )

    def _host_filter(self, rv, rf, rp):
        """Filter each owner's recv buffer through its external store.

        Mirrors the device dedup exactly: lexsort (payload, fp_full,
        fp_view), first-occurrence per fp_view is the representative
        (min (fp_full, payload) — the deterministic refinement every
        engine of this project pins), then the store's is-new verdict.
        Inputs arrive HOST-SIDE (the caller fetches them through the
        async window's ledgered path).  Returns (verdict [D, D, cap_r]
        aligned to the recv layout, n_new_total)."""
        D, cap_r = self.D, self.cap_r
        sent = np.uint64(0xFFFFFFFFFFFFFFFF)
        rv = np.asarray(rv).reshape(D, D * cap_r)
        rf = np.asarray(rf).reshape(D, D * cap_r)
        rp = np.asarray(rp).reshape(D, D * cap_r)
        # live-lane byte ledger, same convention as the deep path (so
        # bench can report the sieve+compress reduction against this,
        # the uncompressed exchange): 24 B routing + 1 B verdict per
        # routed candidate lane, host leg fetches all three u64 arrays.
        # Counting live lanes UNDERSTATES this path's true cost (the
        # actual fetch moves the full padded buffers), which keeps any
        # reduction the deep path reports conservative.
        n_routed = int((rv != sent).sum())
        off_diag = (D - 1) / D
        self.meter.begin_level(len(self.meter.levels) + 1)
        self.meter.add(
            n_candidates=n_routed, n_unique=n_routed,
            a2a_bytes=int(n_routed * 25 * off_diag),
            raw_a2a_bytes=int(n_routed * 25 * off_diag),
            host_bytes=n_routed * 25,
            raw_host_bytes=n_routed * 25,
        )
        self.meter.end_level()
        verdict = np.zeros((D, D * cap_r), bool)
        n_new = 0
        n_uniq = 0
        t_probe = time.monotonic()
        for o in range(D):
            order = np.lexsort((rp[o], rf[o], rv[o]))
            sv = rv[o][order]
            first = np.concatenate([[True], sv[1:] != sv[:-1]]) & (sv != sent)
            uniq = sv[first]
            if len(uniq):
                is_new = self.host_stores[o].insert(uniq)
            else:
                is_new = np.zeros(0, bool)
            vs = np.zeros(D * cap_r, bool)
            vs[first] = is_new
            verdict[o][order] = vs
            n_new += int(is_new.sum())
            n_uniq += len(uniq)
        if any(s.num_runs for s in self.host_stores):
            # the per-owner stores hold spilled (disk) runs: publish
            # the warm/cold probe wait of this level's verdicts
            graft_obs.tier_probe(
                len(self.meter.levels), n_uniq, n_uniq - n_new,
                wait_s=time.monotonic() - t_probe,
            )
        return verdict.reshape(D, D, cap_r), n_new

    @functools.cached_property
    def level_phase1(self):
        spec_state = jax.tree.map(lambda _: P("d"), init_batch(self.cfg, 1))
        return self._mesh_program(
            self._body_a2a_phase1,
            (spec_state, P("d"), P("d")),
            Phase1Out(
                P("d"), P("d"), P("d"), P("d"), P("d"), P("d"),
                P(), P(), P("d"), P(), P(),
            ),
        )

    @functools.cached_property
    def level_phase2(self):
        spec_state = jax.tree.map(lambda _: P("d"), init_batch(self.cfg, 1))
        return self._mesh_program(
            self._body_a2a_phase2,
            (spec_state, P("d"), P("d"), P("d"), P("d")),
            Phase2Out(
                jax.tree.map(lambda _: P("d"), init_batch(self.cfg, 1)),
                P("d"), P("d"), P(), P("d"), P("d"), P(), P("d"),
                P(), P(),
            ),
        )

    def _hosted_level(self, frontier, msum, n_f):
        """One BFS level in host-store mode: phase 1 (expand + route),
        host filter through the per-owner external stores, phase 2
        (verdicts back + materialize).  Returns a LevelOut-shaped
        namespace for the shared driver loop."""
        grows = 0
        while True:
            p1 = self.level_phase1(frontier, msum, n_f)
            if not bool(jax.device_get(p1.overflow_x)):
                break
            if grows >= 8:
                raise RuntimeError(
                    f"capacity overflow (cap_x={self.cap_x}, "
                    f"cap_r={self.cap_r})"
                )
            grows += 1
            self.reactive_grows += 1
            self.cap_x *= 2
            for k in ("level_phase1", "level_phase2", "cap_r", "cap_w"):
                self.__dict__.pop(k, None)
        generated = p1.mult_slots.sum()
        common = dict(
            mult_slots=p1.mult_slots, generated=generated, visited=None,
            abort=p1.abort, abort_at=p1.abort_at, cand_max=p1.cand_max,
            overflow_x=jnp.zeros((), bool), overflow_v=jnp.zeros((), bool),
        )
        # the level's big fetch (three D*D*cap_r routed-candidate
        # buffers) enters the async window NOW, so the copies stream
        # over the host link while the abort control sync below waits
        # for the phase-1 programs — and complete through the LEDGERED
        # get path (the implicit np.asarray conversions this fetch used
        # to make would trip the sanitizer's transfer guard)
        routed = graft_pipeline.DeferredFetch(
            self.pipeline, (p1.rv, p1.rf, p1.rp)
        )
        if bool(jax.device_get(p1.abort)):
            routed.discard()  # ledger stays balanced on the abort path
            return SimpleNamespace(
                n_new_total=jnp.asarray(0, I64), children=None,
                child_msum=None, n_new_local=None, gpidx=None, slots=None,
                inv_bad=jnp.asarray(0, I32), inv_bad_at=None, **common,
            )
        verdict, n_new = self._host_filter(*routed.get())
        vr = jax.device_put(
            jnp.asarray(verdict.reshape(self.D * self.D, self.cap_r)),
            NamedSharding(self.mesh, P("d")),
        )
        boosted = False
        while True:
            p2 = self.level_phase2(frontier, p1.cv, p1.cp, vr, n_f)
            ovf_w, ovf_c = jax.device_get((p2.ovf_w, p2.ovf_c))
            if not (bool(ovf_w) or bool(ovf_c)):
                break
            if grows >= 8:
                raise RuntimeError(
                    f"shipping overflow (cap_w={self.cap_w}, "
                    f"cap_x={self.cap_x})"
                )
            grows += 1
            self.reactive_grows += 1
            if bool(ovf_c):
                # an owner received more new states than its cap_x
                # frontier block: growing cap_w cannot help — grow cap_x
                # and redo the WHOLE level (phase-1 shapes change)
                self.cap_x *= 2
                for k in ("level_phase1", "level_phase2", "cap_r",
                          "cap_w"):
                    self.__dict__.pop(k, None)
                return self._hosted_level(frontier, msum, n_f)
            # cap_w rows overflowed (healing a concentrated legacy
            # frontier): grow cap_w ALONE and redo phase 2 — phase-1's
            # cv/cp shapes must stay valid, so cap_x is not touched
            boosted = True
            self._cap_w_boost = getattr(self, "_cap_w_boost", 1) * 2
            for k in ("level_phase2", "cap_w"):
                self.__dict__.pop(k, None)
        if boosted:
            # the boost exists to absorb a one-time concentrated layout;
            # after this level the frontier is owner-balanced, so drop it
            # (one recompile next level beats shipping D x boosted rows
            # of full states every level for the rest of the run)
            self._cap_w_boost = 1
            for k in ("level_phase2", "cap_w"):
                self.__dict__.pop(k, None)
        n2 = int(jax.device_get(p2.n_new_total))
        resilience.integrity.reconcile(
            "host-store verdict map", n_new, n2
        )
        return SimpleNamespace(
            children=p2.children, child_msum=p2.child_msum,
            n_new_local=p2.n_new_local, n_new_total=p2.n_new_total,
            gpidx=p2.gpidx, slots=p2.slots,
            inv_bad=p2.inv_bad, inv_bad_at=p2.inv_bad_at, **common,
        )

    # -- deep-sweep mode: 1/D frontier segments + sieve-and-compress ------
    #
    # The level-29 wall of the single-device external-store sweep is one
    # frontier (~15 GB) resident on one device (docs/PERF.md).  Deep mode
    # shards the frontier itself: device d owns exactly the states whose
    # fingerprint hashes to it (fp % D — same keying as the external
    # store shards and the all_to_all routing), held as a list of uniform
    # ``seg_rows``-row segments, so per-device frontier memory, expand
    # work and dedup sort all drop ~D-fold and the ceiling moves to
    # ~D x 15 GB.  The fingerprint exchange is sieve-then-compress
    # (arXiv:1208.5542): candidates a device routed in ANY previous
    # level are provably already in the store and are dropped before the
    # routing all_to_all (the sieve cache); owners dedup the level
    # exactly ON DEVICE (the host lexsort of the plain host-store mode
    # moves into the finalize program) and ship only sorted fp deltas in
    # a variable-width packed stream over the host link, answered by one
    # is-new bit per fingerprint.  The host-side level tail is double-
    # buffered: per-owner fetch+insert run in a small thread pool (the
    # ctypes store releases the GIL) and checkpoint writes are deferred
    # to a background writer that overlaps the next level's expand.
    #
    # Parity discipline: the owner-side lexsort picks the same global
    # min-(fp_full, payload) representative per view fingerprint the
    # host filter picked, every sieve drop is provably-visited, and the
    # per-level distinct/generated counts are asserted bit-identical to
    # the single-device engine and oracle by the tier-1 parity tests.

    @property
    def cap_c_deep(self) -> int:
        # phase-2 owner recv block (winners shipped to one owner in one
        # segment round); grows alone on ovf_c so phase-1 shapes hold
        return self.cap_x * self._cap_c_boost

    def _expand_local_seg(self, seg, n_f, base, capf):
        """Expand ONE frontier segment + local pre-dedup (canon late).

        ``base``/``capf`` are device i64 scalars: the segment's first row
        within the device's frontier block and the block's total row
        capacity — dynamic so segment count never recompiles this (the
        largest) program.  Global parent index = dev*capf + base + i."""
        K = self.K
        rows = seg.voted_for.shape[0]
        dev = jax.lax.axis_index("d").astype(I64)
        valid, mult, ab_state = self.kern.expand_guards(seg)
        gidx = base + jnp.arange(rows, dtype=I64)
        in_range = (gidx < n_f[0])[:, None]
        valid = valid & in_range
        gparent = dev * capf + gidx
        payload = (gparent[:, None] * K + jnp.arange(K, dtype=I64)[None]).ravel()
        mult_slots = jax.lax.psum(
            jnp.where(valid, mult, 0).astype(I64).sum(0), "d"
        )
        abort_local = ab_state & in_range[:, 0]
        abort = jax.lax.psum(abort_local.any().astype(I32), "d") > 0
        abort_at = jnp.where(
            abort_local.any(), base + jnp.argmax(abort_local), -1
        ).astype(I64)
        cp_raw, lane, overflow = _compact_payloads(
            valid.ravel(), payload, self.cap_x
        )
        # graftlint: waive[GL005] — clipped segment-relative row, < seg_rows
        lidx = jnp.clip(
            (cp_raw // K) - dev * capf - base, 0, rows - 1
        ).astype(I32)
        parents = jax.tree.map(lambda x: x[lidx], seg)
        children = self.kern.materialize(parents, cp_raw % K)
        fv, ff, _msum = self.fpr.state_fingerprints(children)
        fpv = jnp.where(lane, fv.astype(U64), SENT)
        fpf = jnp.where(lane, ff.astype(U64), SENT)
        payload = jnp.where(lane, cp_raw, -1)
        # live lanes arrive payload-ascending (_compact_payloads), so a
        # stable order on (fp_view, fp_full) is lexsort((payload, fpf,
        # fpv))
        order = stable_order((fpv, fpf))
        sv, sf, sp = fpv[order], fpf[order], payload[order]
        first = jnp.concatenate([jnp.ones((1,), bool), sv[1:] != sv[:-1]])
        keep = first & (sv != SENT)
        cv, cf, cp, _lane = _compact(
            keep, self.cap_x, sv, sf, sp, fills=(SENT, SENT, I64(-1))
        )
        return cv, cf, cp, mult_slots, abort, abort_at, overflow

    def _deep_phase1_body(self, seg, n_f, base, capf, sieve):
        """Expand segment + sieve + route candidates to owners.

        Only (fp_view, fp_full) cross the mesh — 16 B/lane, not the
        plain exchange's 24.  Payloads stay at their origin: the owner
        needs fp_full to pick the representative (min fp_full per view
        fingerprint — the canonical-state choice the engines share) and
        breaks fp_full TIES by deterministic recv order, which is
        count-exact because equal canonical full-state fingerprints are
        symmetry-images of one state (identical successor fingerprints
        either way)."""
        D, cap_x, cap_r = self.D, self.cap_x, self.cap_r
        (cv, cf, cp, mult_slots, abort, abort_at, overflow) = (
            self._expand_local_seg(seg, n_f, base, capf)
        )
        n_pre = (cv != SENT).sum().astype(I64)
        if self.sieve:
            # drop candidates this device routed in a PREVIOUS level:
            # every routed fingerprint was inserted into the store by
            # that level's filter, so the drop is provably-visited-only.
            # Hash mode: a depth-bounded O(1) probe instead of the
            # ~log2(scap) gather rounds of binary search per candidate.
            if self.use_hashstore:
                hit = hashstore.probe_impl(sieve, cv)
            else:
                pos = jnp.searchsorted(sieve, cv)
                hit = sieve[jnp.clip(pos, 0, sieve.shape[0] - 1)] == cv
            cv = jnp.where(hit, SENT, cv)
            cf = jnp.where(hit, SENT, cf)
            cp = jnp.where(hit, I64(-1), cp)
        n_post = (cv != SENT).sum().astype(I64)
        owner = jnp.where(cv == SENT, D, (cv % jnp.uint64(D)).astype(I64))
        oorder = _owner_order(owner, D)
        ov, of_, oo = cv[oorder], cf[oorder], owner[oorder]
        counts = jnp.bincount(oo, length=D + 1)
        starts = jnp.cumsum(counts) - counts
        overflow_x = overflow | (counts[:D].max() > cap_r)
        idx = jnp.clip(
            starts[:D, None] + jnp.arange(cap_r, dtype=starts.dtype)[None, :],
            0,
            cap_x - 1,
        )
        in_row = jnp.arange(cap_r)[None, :] < counts[:D, None]
        sendv = jnp.where(in_row, ov[idx], SENT)
        sendf = jnp.where(in_row, of_[idx], SENT)
        rv = jax.lax.all_to_all(sendv, "d", 0, 0, tiled=True).reshape(D, cap_r)
        rf = jax.lax.all_to_all(sendf, "d", 0, 0, tiled=True).reshape(D, cap_r)
        return Phase1DeepOut(
            cv, cf, cp, rv, rf, mult_slots, abort, abort_at[None],
            jax.lax.psum(overflow_x.astype(I32), "d") > 0,
            jax.lax.psum(n_pre, "d"), jax.lax.psum(n_post, "d"),
            _pmax(n_pre, "d"),
        )

    def _deep_finalize_body(self, rv3, rf3):
        """Owner-side exact level dedup + delta-packed unique stream.

        Inputs are the stacked segment rounds' recv buffers [Rq, D,
        cap_r] (padded rounds are all-SENT).  One lexsort over every
        candidate the owner received this level picks the min-fp_full
        representative per view fingerprint (the canonical-state choice
        every engine of this project pins), with fp_full ties broken by
        recv-lane order — deterministic, and count-exact because tied
        canonical fingerprints are symmetry-images of one state.  The
        surviving unique fingerprints leave sorted ascending, which is
        exactly what the delta encoder needs."""
        q = rv3.reshape(-1)
        qf = rf3.reshape(-1)
        order = stable_order((q, qf))  # ties in recv order
        qsv = q[order]
        first = jnp.concatenate([jnp.ones((1,), bool), qsv[1:] != qsv[:-1]])
        keep = first & (qsv != SENT)
        n_u = keep.sum().astype(I64)
        comp = true_first(keep)
        pref = jnp.arange(qsv.shape[0]) < n_u
        uq = jnp.where(pref, qsv[comp], SENT)
        stream, nib, total = pack_fp_deltas(uq, n_u)
        n_recv = (q != SENT).sum().astype(I64)
        return DeepFinOut(
            stream, nib, n_u[None], total[None],
            jax.lax.psum(n_recv, "d"), jax.lax.psum(n_u, "d"),
        ), uq

    def _deep_verdict_body(self, rv3, rf3, vb):
        """Map per-unique-fp is-new bits back to per-lane win flags.

        Recomputes the finalize ordering (argsort over identical input
        is deterministic) and returns win flags in the recv layout
        [Rq, D, cap_r] so each round's phase 2 can slice its own page
        and route verdicts back with the standard reverse all_to_all."""
        Rq, D, cap_r = rv3.shape
        q = rv3.reshape(-1)
        qf = rf3.reshape(-1)
        order = stable_order((q, qf))  # ties in recv order
        qsv = q[order]
        first = jnp.concatenate([jnp.ones((1,), bool), qsv[1:] != qsv[:-1]])
        keep = first & (qsv != SENT)
        # graftlint: waive[GL005] — rank < q.shape[0] < 2^31 lanes
        rank = radix.cumsum(keep.astype(I32)) - 1
        need = q.shape[0] // 8 + 1
        if vb.shape[0] < need:
            vb = jnp.concatenate(
                [vb, jnp.zeros((need - vb.shape[0],), jnp.uint8)]
            )
        rr = jnp.clip(rank, 0, q.shape[0] - 1)
        bit = (vb[rr >> 3] >> (rr & 7).astype(jnp.uint8)) & 1
        win_sorted = keep & (bit == 1)
        # back to recv layout: scatter through the permutation
        win = jnp.zeros_like(win_sorted).at[order].set(win_sorted)
        return win.reshape(Rq, D, cap_r)

    def _ship_winners_deep(self, seg, base, capf, dev, oo, op, win_sorted):
        """_ship_winners_to_owners with segment-relative parent rows.

        Parents of this round's winners live in ``seg`` (rows base..
        base+rows of this device's frontier block); global parent index
        (dev*capf + row) rides in the payloads, so gpidx stays global
        for the trace walk.  Recv compaction uses cap_c_deep."""
        D, K = self.D, self.K
        cap_w = self.cap_w
        rows = seg.voted_for.shape[0]
        wcounts = jnp.bincount(jnp.where(win_sorted, oo, D), length=D + 1)
        wstarts = jnp.cumsum(wcounts) - wcounts
        worder = _owner_order(jnp.where(win_sorted, oo, D), D)
        idx = jnp.clip(
            wstarts[:D, None] + jnp.arange(cap_w, dtype=wstarts.dtype)[None, :],
            0, oo.shape[0] - 1,
        )
        lane_src = worder[idx]
        in_row = jnp.arange(cap_w)[None, :] < wcounts[:D, None]
        ovf_w = wcounts[:D].max() > cap_w
        spay = jnp.where(in_row, op[lane_src], 0)
        pg = spay // K  # global parent index
        pidx = jnp.clip(pg - dev * capf - base, 0, rows - 1)
        slots = spay % K
        parents = jax.tree.map(lambda x: x[pidx.reshape(-1)], seg)
        kids = self.kern.materialize(parents, slots.reshape(-1))
        gp_send = jnp.where(in_row, pg, -1)

        def a2a(x):
            return jax.lax.all_to_all(
                x.reshape(D, cap_w, *x.shape[1:]), "d", 0, 0, tiled=True
            ).reshape(D * cap_w, *x.shape[1:])

        lane_r = a2a(in_row.astype(jnp.uint8).reshape(-1)).astype(bool)
        gp_r = a2a(gp_send.reshape(-1))
        sl_r = a2a(jnp.where(in_row, slots, 0).reshape(-1))
        kids_r = jax.tree.map(a2a, kids)
        cap_c = self.cap_c_deep
        comp = true_first(lane_r)
        take = jnp.clip(jnp.arange(cap_c), 0, comp.shape[0] - 1)
        src = comp[take]
        lane = (jnp.arange(cap_c) < lane_r.sum()) & (
            jnp.arange(cap_c) < comp.shape[0]
        )
        children = jax.tree.map(
            lambda x: jnp.where(
                lane.reshape((-1,) + (1,) * (x.ndim - 1)),
                x[src], jnp.zeros_like(x[src]),
            ),
            kids_r,
        )
        gpidx = jnp.where(lane, gp_r[src], -1)
        slots_c = jnp.where(lane, sl_r[src], -1)
        n_new_local = lane.sum().astype(I64)
        ovf_c = lane_r.sum() > cap_c
        child_msum = jnp.zeros((cap_c, 1, 1), jnp.uint32)
        bad_local = jnp.zeros(cap_c, bool)
        for _name, fn in self.inv_fns:
            bad_local = bad_local | (
                ~fn(self.cfg, children, self.kern.tables) & lane
            )
        inv_bad = jax.lax.psum(bad_local.sum().astype(I32), "d")
        first_bad = jnp.where(
            bad_local.any(), jnp.argmax(bad_local), -1
        ).astype(I64)
        return (children, child_msum, gpidx, slots_c, lane, n_new_local,
                inv_bad, first_bad, ovf_w, ovf_c)

    def _deep_phase2_body(self, seg, cv, cp, ver, r, base, capf):
        """Verdicts of round ``r`` back to origins; materialize + ship."""
        D, cap_x, cap_r = self.D, self.cap_x, self.cap_r
        dev = jax.lax.axis_index("d").astype(I64)
        verdict_recv = jax.lax.dynamic_index_in_dim(ver, r, 0, keepdims=False)
        owner = jnp.where(cv == SENT, D, (cv % jnp.uint64(D)).astype(I64))
        oorder = _owner_order(owner, D)  # as phase 1
        op, oo = cp[oorder], owner[oorder]
        counts = jnp.bincount(oo, length=D + 1)
        starts = jnp.cumsum(counts) - counts
        rank = jnp.arange(cap_x) - starts[oo]
        rr = jnp.clip(rank, 0, cap_r - 1)
        ok_lane = (cv[oorder] != SENT) & (rank < cap_r)
        back = jax.lax.all_to_all(
            verdict_recv, "d", 0, 0, tiled=True
        ).reshape(D, cap_r)
        win_sorted = back[jnp.clip(oo, 0, D - 1), rr] & ok_lane
        n_new_total = jax.lax.psum(win_sorted.sum().astype(I64), "d")
        (children, child_msum, gpidx, slots, _lane, n_new_local,
         inv_bad, first_bad, ovf_w, ovf_c) = self._ship_winners_deep(
            seg, base, capf, dev, oo, op, win_sorted
        )
        return Phase2Out(
            children, child_msum, n_new_local[None], n_new_total,
            gpidx, slots, inv_bad, first_bad[None],
            jax.lax.psum(ovf_w.astype(I32), "d") > 0,
            jax.lax.psum(ovf_c.astype(I32), "d") > 0,
        )

    def _deep_repack_body(self, n_out, ch_stack, gp_stack, sl_stack):
        """Merge the rounds' shipped children into uniform segments.

        Per device: compact the valid child lanes of all Rq round blocks
        (stable, round-major — deterministic) into a prefix, then cut it
        into ``n_out`` uniform seg_rows segments.  Also returns the
        repacked gpidx/slots (the trace/mdelta record must describe the
        frontier layout the next level actually expands)."""
        Rq, cap_c = gp_stack.shape
        seg = self.seg_rows
        gp = gp_stack.reshape(-1)
        sl = sl_stack.reshape(-1)
        validl = gp >= 0
        comp = true_first(validl)
        ntot = n_out * seg
        take = jnp.clip(jnp.arange(ntot), 0, comp.shape[0] - 1)
        src = comp[take]
        lane = (jnp.arange(ntot) < validl.sum()) & (
            jnp.arange(ntot) < comp.shape[0]
        )
        flat = jax.tree.map(
            lambda x: x.reshape(Rq * cap_c, *x.shape[2:]), ch_stack
        )
        out = jax.tree.map(
            lambda x: jnp.where(
                lane.reshape((-1,) + (1,) * (x.ndim - 1)),
                x[src], jnp.zeros_like(x[src]),
            ),
            flat,
        )
        gpo = jnp.where(lane, gp[src], -1)
        slo = jnp.where(lane, sl[src], -1)
        n_loc = validl.sum().astype(I64)
        segs = tuple(
            jax.tree.map(lambda x: x[s * seg:(s + 1) * seg], out)
            for s in range(n_out)
        )
        return segs, gpo, slo, n_loc[None]

    def _deep_sieve_merge_body(self, sieve, cv):
        """Fold one round's routed candidates into the sieve cache.

        Sorted merge + dedup at fixed capacity; on overflow the LARGEST
        fingerprints fall off the end — the cache stays an exact subset
        of the store (a sieve miss is never wrong, only less effective)
        and the driver grows scap for the next level."""
        scap = sieve.shape[0]
        merged = jnp.sort(jnp.concatenate([sieve, cv]))
        first = jnp.concatenate(
            [jnp.ones((1,), bool), merged[1:] != merged[:-1]]
        ) & (merged != SENT)
        n_u = first.sum()
        comp = jnp.argsort(~first, stable=True)
        pref = jnp.arange(merged.shape[0]) < n_u
        out = jnp.where(pref, merged[comp], SENT)[:scap]
        overflow = jax.lax.psum((n_u > scap).astype(I32), "d") > 0
        return out, overflow

    def _deep_sieve_insert_body(self, sieve, cv):
        """Hash-slab sieve update: O(candidates) probe-and-insert
        instead of the sort-merge of ``_deep_sieve_merge_body``.  Lanes
        whose probe window is full are SKIPPED (subset semantics — a
        sieve miss is never wrong) and the psum'd overflow flag makes
        the driver grow/rehash scap for the next level."""
        sieve2, _n, ovf = hashstore.insert_only_impl(sieve, cv)
        overflow = jax.lax.psum(ovf.astype(I32), "d") > 0
        return sieve2, overflow

    # -- deep-mode program cache ------------------------------------------

    def _dprog(self, key, build):
        # all deep-mode program fetch/build goes through here, so this
        # one assert is the always-on choke point keeping device
        # dispatch off the _io_pool/_ck_pool worker threads
        graft_sanitize.assert_device_dispatch_ok(
            f"deep program dispatch ({key!r})"
        )
        prog = self._dp.get(key)
        if prog is None:
            graft_sanitize.note_shape_event(f"deep program build {key!r}")
            prog = self._dp[key] = build()
        return prog

    def _deep_p1(self):
        def build():
            spec_state = jax.tree.map(
                lambda _: P("d"), init_batch(self.cfg, 1)
            )
            return self._mesh_program(
                self._deep_phase1_body,
                (spec_state, P("d"), P(), P(), P("d")),
                Phase1DeepOut(
                    P("d"), P("d"), P("d"), P("d"), P("d"),
                    P(), P(), P("d"), P(), P(), P(), P(),
                ),
            )

        return self._dprog("p1", build)

    def _deep_fin(self, Rq):
        def build():
            return jax.jit(
                _shard_map(
                    self._deep_finalize_body,
                    self.mesh,
                    (P(None, "d"), P(None, "d")),
                    (
                        DeepFinOut(
                            P("d"), P("d"), P("d"), P("d"), P(), P(),
                        ),
                        P("d"),
                    ),
                )
            )

        return self._dprog(("fin", Rq, self.cap_r), build)

    def _deep_vpad(self, vq, Rq):
        """Pad (or trim the zero tail of) each owner's ``vq`` verdict
        bytes to the verdict program's fixed width, on device: a trivial
        program per ``vq``, so the verdict program itself (two 64-bit
        sort keys, minutes to compile on TPU) compiles once per
        (Rq, cap_r) instead of once per level."""
        need = Rq * self.D * self.cap_r // 8 + 1

        def build():
            return jax.jit(
                _shard_map(
                    lambda v: jnp.pad(v, (0, max(0, need - vq)))[:need],
                    self.mesh, (P("d"),), P("d"),
                )
            )

        return self._dprog(("vpad", vq, need), build)

    def _deep_ver(self, Rq):
        def build():
            return jax.jit(
                _shard_map(
                    self._deep_verdict_body,
                    self.mesh,
                    (P(None, "d"), P(None, "d"), P("d")),
                    P(None, "d"),
                )
            )

        return self._dprog(("ver", Rq, self.cap_r), build)

    def _deep_p2(self):
        def build():
            spec_state = jax.tree.map(
                lambda _: P("d"), init_batch(self.cfg, 1)
            )
            return self._mesh_program(
                self._deep_phase2_body,
                (spec_state, P("d"), P("d"), P(None, "d"), P(),
                 P(), P()),
                Phase2Out(
                    jax.tree.map(
                        lambda _: P("d"), init_batch(self.cfg, 1)
                    ),
                    P("d"), P("d"), P(), P("d"), P("d"), P(), P("d"),
                    P(), P(),
                ),
            )

        return self._dprog("p2", build)

    def _deep_rp(self, Rq, n_out):
        def build():
            spec_state = jax.tree.map(
                lambda _: P(None, "d"), init_batch(self.cfg, 1)
            )
            seg_spec = jax.tree.map(
                lambda _: P("d"), init_batch(self.cfg, 1)
            )
            return jax.jit(
                _shard_map(
                    functools.partial(self._deep_repack_body, n_out),
                    self.mesh,
                    (spec_state, P(None, "d"), P(None, "d")),
                    (
                        tuple(seg_spec for _ in range(n_out)),
                        P("d"), P("d"), P("d"),
                    ),
                )
            )

        return self._dprog(("rp", Rq, n_out, self.cap_c_deep), build)

    def _deep_sv(self):
        body = (
            self._deep_sieve_insert_body
            if self.use_hashstore
            else self._deep_sieve_merge_body
        )

        def build():
            return jax.jit(
                _shard_map(
                    body,
                    self.mesh,
                    (P("d"), P("d")),
                    (P("d"), P()),
                )
            )

        return self._dprog(("sv", self.scap, self.cap_x), build)

    def _deep_prefix(self, width, q):
        """Quantized-prefix fetch program: every device's first ``q``
        elements of its shard, in ONE collective-free dispatch.

        Cached per (width, q) so the program set stays O(log) over a run
        — the fetch is the host-link cost, and fetching fixed whole
        buffers would forfeit the bytes the compressed stream saved.  The slice
        is shard-LOCAL (shard_map, P('d') in and out): a global
        dynamic_slice over the sharded array would lower to an
        all-gather, and concurrently dispatched collectives from fetch
        worker threads interleave differently across the virtual
        devices and deadlock the CPU rendezvous (measured: two RunIds
        stuck at one AllGather at D=8)."""

        def build():
            return jax.jit(
                _shard_map(
                    lambda x: x[:q], self.mesh, (P("d"),), P("d")
                )
            )

        return self._dprog(("prefix", width, q), build)

    @functools.cached_property
    def _io_pool(self):
        from concurrent.futures import ThreadPoolExecutor

        # per-owner store-insert workers: the ctypes insert releases the
        # GIL for the C++ sort/merge/spill, so the D shard inserts — the
        # single-CPU serial level tail of the resident design — run
        # concurrently on a multi-core host.  Workers never touch jax:
        # concurrently dispatched device programs interleave their
        # collectives differently across devices and deadlock the CPU
        # rendezvous (the reason the prefix fetch is one main-thread
        # dispatch, see _deep_prefix).  The initializer marks each worker
        # no-dispatch so any future code path that DOES reach a device
        # program from a worker fails loudly instead of deadlocking
        # (graftlint GL007's runtime twin; always on, one thread-local
        # write per worker).
        return ThreadPoolExecutor(
            max_workers=max(2, min(self.D, os.cpu_count() or 2)),
            initializer=graft_sanitize.forbid_device_dispatch_in_thread,
        )

    @functools.cached_property
    def _ck_pool(self):
        from concurrent.futures import ThreadPoolExecutor

        return ThreadPoolExecutor(  # deferred tail writes
            max_workers=1,
            initializer=graft_sanitize.forbid_device_dispatch_in_thread,
        )

    def _grow_deep(self, what):
        """Reactive capacity growth for the deep path (recompiles)."""
        self.reactive_grows += 1
        if what == "cap_x":
            self.cap_x *= 2
        elif what == "cap_c":
            self._cap_c_boost *= 2
        elif what == "cap_w":
            self._cap_w_boost = getattr(self, "_cap_w_boost", 1) * 2
        self._dp.clear()
        for k in ("cap_r", "cap_w"):
            self.__dict__.pop(k, None)

    def _grow_sieve(self, new_scap):
        new_scap = min(new_scap, self.scap_max)
        if new_scap <= self.scap:
            return
        arr = np.asarray(
            jax.device_get(self._sieve_cache)
        ).reshape(self.D, self.scap)
        try:
            resilience.fault_fire("hashstore.grow")
            if self.use_hashstore:
                # hash slabs rehash on growth (slot homes move with the
                # capacity mask — padding would orphan every cached
                # entry)
                new = hashstore.rebuild_np(arr, new_scap)
            else:
                pad = np.full((self.D, new_scap - self.scap), SENT)
                new = np.concatenate([arr, pad], axis=1)
        except Exception as e:  # graftlint: waive[GL003]
            # the sieve is a pure optimization cache: a failed growth
            # (host OOM, injected fault) costs effectiveness, never
            # correctness — keep the current capacity and move on
            print(
                f"[resilience] sieve grow to {new_scap} failed ({e}); "
                "keeping the current sieve capacity",
                file=sys.stderr,
            )
            return
        self.scap = new_scap
        self._sieve_cache = jax.device_put(
            jnp.asarray(new).reshape(-1),
            NamedSharding(self.mesh, P("d")),
        )
        self._dp.clear()

    def _load_sieve_slab(self, ckdir, depth, shard):
        """Adopt a checkpointed sieve slab if (version, depth, D, mode)
        all match; silently keep the empty sieve otherwise."""
        path = os.path.join(ckdir, "sieve_slab.npz")
        if not os.path.exists(path):
            return
        try:
            z = np.load(path)
            ver, d, Dz, rows, hs = (int(x) for x in z["meta"])
            slab = np.asarray(z["slab"], np.uint64)
        except (OSError, ValueError, KeyError):
            return
        if (
            ver != hashstore.SLAB_VERSION or d != depth
            or hs != int(self.use_hashstore)
            or slab.shape[0] != Dz * rows
        ):
            return
        if Dz != self.D:
            # elastic resume: the snapshot was cut for a Dz-device mesh.
            # The sieve is origin-keyed (device d holds what d routed),
            # so no fp-based slice reproduces the old locality — instead
            # REPLICATE the union of all shards into every new shard
            # when it fits (every entry is provably in the store, so any
            # superset-per-shard is still an exact sieve), else start
            # empty and re-learn.
            live = slab[slab != SENT]
            union = np.unique(live)
            if len(union) == 0:
                return
            if self.use_hashstore:
                rows_new = hashstore.slab_rows(len(union))
            else:
                rows_new = 1 << (max(1, 2 * len(union)) - 1).bit_length()
            if rows_new > self.scap_max:
                return
            if self.use_hashstore:
                new = hashstore.rebuild_np(
                    [union] * self.D, rows_new
                )
            else:
                new = np.full((self.D, rows_new), SENT)
                new[:, : len(union)] = np.sort(union)[None, :]
            print(
                f"[elastic] sieve slab repartitioned {Dz} -> {self.D} "
                f"shards ({len(union)} entries replicated, "
                f"{rows_new} rows/shard)",
                file=sys.stderr,
            )
            self.scap = rows_new
            # graftlint: waive[GL006] — one-time elastic-resume upload
            self._sieve_cache = jax.device_put(
                jnp.asarray(new).reshape(-1), shard
            )
            self._dp.clear()
            return
        self.scap = rows
        self._sieve_cache = jax.device_put(jnp.asarray(slab), shard)
        self._dp.clear()

    def _deep_level(self, segments, n_f_np, depth):
        """One BFS level of the sharded deep sweep.

        Sequence: per-segment phase 1 (expand + sieve + route; dispatched
        without intermediate host syncs so the device pipelines rounds),
        owner-side finalize (exact level dedup + delta pack), ONE
        quantized-prefix host fetch + concurrent per-owner store inserts
        (the double-buffered level tail), verdict mapping, per-round
        phase 2 (materialize winners at origins + ship to owners),
        repack into uniform segments.  Returns a dict; on abort or
        violation only the locating fields."""
        D, seg = self.D, self.seg_rows
        shard = NamedSharding(self.mesh, P("d"))
        R = len(segments)
        capf = R * seg
        n_f_dev = jax.device_put(jnp.asarray(n_f_np, I64), shard)
        meter = self.meter
        meter.begin_level(depth + 1)

        grows = 0
        while True:
            p1 = self._deep_p1()
            p1s = [
                p1(
                    segments[r], n_f_dev, jnp.asarray(r * seg, I64),
                    jnp.asarray(capf, I64), self._sieve_cache,
                )
                for r in range(R)
            ]
            ovfs = jax.device_get([p.overflow_x for p in p1s])
            if not any(bool(o) for o in ovfs):
                break
            if grows >= 8:
                raise RuntimeError(
                    f"deep candidate overflow (cap_x={self.cap_x})"
                )
            grows += 1
            print(
                f"[mesh-deep] REACTIVE cap_x grow at level {depth + 1} "
                f"({self.cap_x} -> {self.cap_x * 2})", file=sys.stderr,
            )
            self._grow_deep("cap_x")
        aborts = jax.device_get([p.abort for p in p1s])
        mult_np = np.zeros((self.K,), np.int64)
        for m in jax.device_get([p.mult_slots for p in p1s]):
            mult_np += np.asarray(m, np.int64)
        if any(bool(a) for a in aborts):
            for r, p in enumerate(p1s):
                aa = np.asarray(jax.device_get(p.abort_at)).reshape(D)
                devs = np.nonzero(aa >= 0)[0]
                if len(devs):
                    return dict(
                        abort_gidx=int(devs[0]) * capf + int(aa[devs[0]]),
                        mult_slots=mult_np,
                    )

        # --- owner-side finalize + packed host exchange ------------------
        cap_r = self.cap_r
        Rq = 1 << max(0, R - 1).bit_length()
        pads_v = []
        if Rq > R:
            pad_v = self._dprog(
                ("padv", cap_r),
                lambda: jax.device_put(
                    jnp.full((D * D, cap_r), SENT, U64), shard
                ),
            )
            pads_v = [pad_v] * (Rq - R)
        rv3 = jnp.stack([p.rv.reshape(D * D, cap_r) for p in p1s] + pads_v)
        rf3 = jnp.stack([p.rf.reshape(D * D, cap_r) for p in p1s] + pads_v)
        fin, uq = self._deep_fin(Rq)(rv3, rf3)
        (n_us, totals, n_recv, n_uniq, n_pres, n_posts) = jax.device_get((
            fin.n_u, fin.total, fin.n_recv_sum, fin.n_u_sum,
            [p.n_pre for p in p1s], [p.n_post for p in p1s],
        ))
        n_us = np.asarray(n_us).reshape(D)
        totals = np.asarray(totals).reshape(D)
        n_pre = int(sum(int(x) for x in n_pres))
        n_post = int(sum(int(x) for x in n_posts))
        cap_acc = Rq * D * cap_r
        cap8, capnib = cap_acc * 8, cap_acc // 2
        # live-lane byte ledger (capacity padding excluded on both sides;
        # quantized-prefix fetches ARE counted with their padding — that
        # is what actually moves).  Deep routing tiles are 16 B/lane
        # (fp_view + fp_full; payloads never leave their origin) plus
        # the 1 B/lane verdict return; the uncompressed exchange's are
        # 24+1 B/lane.  Off-diagonal share crosses a link.
        off_diag = (D - 1) / D
        meter.add(
            n_candidates=n_pre, n_sieved=n_pre - n_post,
            n_unique=int(n_uniq),
            a2a_bytes=int(n_post * 17 * off_diag),
            raw_a2a_bytes=int(n_pre * 25 * off_diag),
            raw_host_bytes=n_pre * 25,
        )

        max_nu = int(n_us.max()) if len(n_us) else 0
        vq = packed_quantum(max(1, (max_nu + 7) // 8))
        bits_np = np.zeros((D, vq), np.uint8)
        # ONE collective-free prefix fetch for all owners (quantized to
        # the largest owner's live bytes), dispatched from the main
        # thread; then the D store inserts — the serial single-CPU
        # level tail of the resident design — run concurrently in the
        # pool (the ctypes insert releases the GIL).
        #
        # Packing fallback: the delta/varint form wins only once levels
        # carry enough fingerprints to amortize its per-owner quanta —
        # at tiny levels the packed stream + nibble header is BIGGER
        # than the raw u64 prefix (BENCH_r06 per_level reduction
        # 0.21-0.56 on levels 1-2), so compare the two quantized fetch
        # sizes and ship whichever is smaller, recording packed=False
        # in the ledger when the raw form goes out.
        qf = min(packed_quantum(max(max_nu, 1)), cap_acc)
        qb = min(packed_quantum(max(int(totals.max()), 1)), cap8)
        qn = min(packed_quantum(max((max_nu + 1) // 2, 1)), capnib)
        packed_ok = self.compress and (qb + qn) < qf * 8

        def fetch_prefixes():
            """The quantized-prefix host fetch, as one IDEMPOTENT unit:
            re-fetching an already-computed device array has no side
            effects, so transient link failures retry with backoff
            (resilience.with_retry) instead of killing a multi-hour
            sweep.  The fault site makes the retry path testable."""
            resilience.fault_fire("exchange.fetch")
            if packed_ok:
                st_dev = self._deep_prefix(cap8, qb)(fin.stream)
                nb_dev = self._deep_prefix(capnib, qn)(fin.nib)
                if self.pipeline:
                    # both prefix programs are dispatched; start both
                    # copies so the streams overlap instead of fetching
                    # strictly one after the other
                    graft_pipeline.async_start((st_dev, nb_dev))
                st = np.asarray(jax.device_get(st_dev)).reshape(D, qb)
                nb = np.asarray(jax.device_get(nb_dev)).reshape(D, qn)
                return st, nb, None, D * (qb + qn)
            uqh = np.asarray(jax.device_get(
                self._deep_prefix(cap_acc, qf)(uq)
            )).reshape(D, qf)
            return None, None, uqh, D * qf * 8

        st_all, nb_all, uq_all, fetch_bytes = resilience.with_retry(
            fetch_prefixes, "deep exchange prefix fetch"
        )
        inserted = np.zeros(D, np.int64)
        insert_secs = np.zeros(D, np.float64)

        def insert_one(o):
            t_o = time.monotonic()
            n_o = int(n_us[o])
            if n_o == 0:
                return
            if packed_ok:
                # verify=True: the decoded stream must be strictly
                # ascending (integrity check on the host leg)
                fps = unpack_fp_deltas(
                    st_all[o], nb_all[o], n_o, verify=True
                )
            else:
                fps = uq_all[o][:n_o]
            is_new = self.host_stores[o].insert(fps)
            inserted[o] = int(is_new.sum())
            pb = np.packbits(is_new, bitorder="little")
            bits_np[o, : len(pb)] = pb[:vq]
            # per-owner insert wall time: the straggler-skew signal of
            # the double-buffered level tail (one slow store shard =
            # one degraded host/disk path)
            insert_secs[o] = time.monotonic() - t_o

        t_probe = time.monotonic()
        list(self._io_pool.map(insert_one, range(D)))
        if any(s.num_runs for s in self.host_stores):
            # spilled membership: the per-owner stores hold disk runs,
            # so this level's insert verdicts probed the warm/cold
            # tiers — publish the wall elapsed around the concurrent
            # map (NOT the per-owner sum, which overstates a parallel
            # stall by up to D), the spill-overlap acceptance metric
            graft_obs.tier_probe(
                depth + 1, int(n_us.sum()),
                int(n_us.sum()) - int(inserted.sum()),
                wait_s=time.monotonic() - t_probe,
            )
        meter.note_packed(packed_ok)
        meter.add(host_bytes=fetch_bytes + D * vq + 16 * D)
        vb = jax.device_put(jnp.asarray(bits_np.reshape(-1)), shard)
        vb = self._deep_vpad(vq, Rq)(vb)
        ver = self._deep_ver(Rq)(rv3, rf3, vb)

        # --- verdicts back; materialize + ship winners per round ---------
        grows = 0
        while True:
            p2 = self._deep_p2()
            p2s = [
                p2(
                    segments[r], p1s[r].cv, p1s[r].cp, ver,
                    jnp.asarray(r, I32),
                    jnp.asarray(r * seg, I64), jnp.asarray(capf, I64),
                )
                for r in range(R)
            ]
            flags = jax.device_get([(p.ovf_w, p.ovf_c) for p in p2s])
            if not any(bool(w) or bool(c) for w, c in flags):
                break
            if grows >= 8:
                raise RuntimeError(
                    f"deep shipping overflow (cap_w={self.cap_w}, "
                    f"cap_c={self.cap_c_deep})"
                )
            grows += 1
            self._grow_deep(
                "cap_c" if any(bool(c) for _w, c in flags) else "cap_w"
            )
        n2s, invs, nls = jax.device_get(
            ([p.n_new_total for p in p2s], [p.inv_bad for p in p2s],
             [p.n_new_local for p in p2s])
        )
        n2 = sum(int(x) for x in n2s)
        n_new = int(inserted.sum())
        # per-owner count reconciliation across the exchange: what the
        # owner stores admitted must equal what the origins materialized
        resilience.integrity.reconcile(
            "deep owner exchange", n_new, n2, level=depth + 1
        )
        self.skew.note(depth + 1, rows=inserted, seconds=insert_secs)
        inv_total = sum(int(x) for x in invs)
        inv = None
        if inv_total > 0:
            for p in p2s:
                ba = np.asarray(jax.device_get(p.inv_bad_at)).reshape(D)
                devs = np.nonzero(ba >= 0)[0]
                if len(devs):
                    cap_c = self.cap_c_deep
                    gidx = int(devs[0]) * cap_c + int(ba[devs[0]])
                    inv = (
                        np.asarray(jax.device_get(p.gpidx), np.int64),
                        np.asarray(jax.device_get(p.slots), np.int64),
                        gidx,
                    )
                    break

        # --- repack shipped children into uniform 1/D segments ----------
        nl = np.zeros(D, np.int64)
        for x in nls:
            nl += np.asarray(x, np.int64).reshape(D)
        n_out = max(1, -(-int(nl.max()) // seg))
        cap_c = self.cap_c_deep
        pads_k, pads_n = [], []
        if Rq > R:
            zero_kids = self._dprog(
                ("padk", cap_c),
                lambda: jax.device_put(
                    jax.tree.map(jnp.zeros_like, p2s[0].children), shard
                ),
            )
            neg = self._dprog(
                ("padn", cap_c),
                lambda: jax.device_put(
                    jnp.full((D * cap_c,), -1, I64), shard
                ),
            )
            pads_k = [zero_kids] * (Rq - R)
            pads_n = [neg] * (Rq - R)
        ch_stack = jax.tree.map(
            lambda *xs: jnp.stack(xs),
            *([p.children for p in p2s] + pads_k),
        )
        gp_stack = jnp.stack([p.gpidx for p in p2s] + pads_n)
        sl_stack = jnp.stack([p.slots for p in p2s] + pads_n)
        segs_new, gpo, slo, _nloc = self._deep_rp(Rq, n_out)(
            ch_stack, gp_stack, sl_stack
        )
        # the level's trace arrays (its two largest host-bound fetches)
        # enter the async window here, then the sieve update and the
        # candidate-peak control fetch below dispatch/run WHILE they
        # stream — the window drains before the arrays are consumed,
        # still inside the level
        tail = graft_pipeline.DeferredFetch(self.pipeline, (gpo, slo))

        # --- sieve cache update (level end: the level's own candidates
        # must never sieve each other — exact representative choice) ----
        if self.sieve and self.scap:
            sv = self._deep_sv()
            ovf_s = False
            for p in p1s:
                self._sieve_cache, ovf = sv(self._sieve_cache, p.cv)
                ovf_s = ovf_s or bool(jax.device_get(ovf))
            if ovf_s and self.scap < self.scap_max:
                print(
                    f"[mesh-deep] sieve cache full at level {depth + 1}: "
                    f"scap {self.scap} -> {self.scap * 4}",
                    file=sys.stderr,
                )
                self._grow_sieve(self.scap * 4)
        stats = meter.end_level()
        self._cand_hist.append(
            max(int(np.asarray(c)) for c in jax.device_get(
                [p.cand_max for p in p1s]
            ))
        )
        gpo_np, slo_np = tail.get()
        gpidx_np = np.asarray(gpo_np, np.int64)
        slots_np = np.asarray(slo_np, np.int64)
        return dict(
            n_new=n_new, segments=list(segs_new), n_f=nl,
            gpidx=gpidx_np, slots=slots_np, mult_slots=mult_np,
            inv=inv, capf=capf, stats=stats,
        )

    def run_deep(
        self,
        max_depth: int | None = None,
        checkpoint_dir: str | None = None,
        checkpoint_every: int = 1,
        resume_from: str | None = None,
        presize: bool = True,
    ) -> CheckResult:
        """The sharded deep-sweep driver (frontier 1/D across devices)."""
        cfg, D, seg = self.cfg, self.D, self.seg_rows
        shard = NamedSharding(self.mesh, P("d"))
        repl = NamedSharding(self.mesh, P())
        t0 = time.monotonic()
        if self.host_stores is None:
            from ..native import HostFPStore

            self.host_stores = [
                HostFPStore(
                    os.path.join(self.host_store_dir, f"shard_{o:02d}"),
                    mem_budget_entries=self._store_budget_entries(),
                )
                for o in range(D)
            ]
            if resume_from is None:
                for s in self.host_stores:
                    s.clear()
        if checkpoint_dir and checkpoint_every:
            import glob as _glob

            if resume_from is None and os.path.isdir(checkpoint_dir):
                # a killed earlier writer must not leak .tmp_* files
                # into a fresh run's directory
                resilience.sweep_tmp(checkpoint_dir)
            has_log = _glob.glob(
                os.path.join(checkpoint_dir, "mdelta_*.npz")
            )
            if resume_from is None and has_log:
                raise ValueError(
                    f"{checkpoint_dir} holds checkpoints from a previous "
                    "run; resume with --recover or clear the directory"
                )
        self._sieve_cache = jax.device_put(
            jnp.full((D * self.scap,), SENT, U64), shard
        )
        self._cand_hist = []
        # per-device peak frontier rows (segments are uniform slabs, so
        # rows x per-row state bytes IS the per-device frontier memory —
        # the ~1/D claim the parity tests and bench record measure)
        self.peak_dev_rows = 0
        ck_fut = None

        ck = None
        if resume_from is not None:
            if not os.path.isdir(resume_from):
                raise ValueError(
                    "deep mode resumes from an mdelta directory only"
                )
            ck = self._resume_from_mdeltas(resume_from, shard, repl)
            if ck is None:
                # healing left nothing replayable: restart from Init
                # with clean stores (they may hold pre-crash inserts)
                for s in self.host_stores:
                    s.clear()
        if ck is not None:
            fr = ck["frontier"]
            rows = fr.voted_for.shape[0] // D
            R = max(1, -(-rows // seg))
            fr_np = {}
            for f in RaftState._fields:
                # intended one-time resume sync (ledgered explicit get:
                # the rebuilt frontier re-splits into uniform segments)
                v = np.asarray(jax.device_get(getattr(fr, f)))
                fr_np[f] = v.reshape((D, rows) + v.shape[1:])
            segments = []
            for r in range(R):
                segd = {}
                for f, v in fr_np.items():
                    blk = v[:, r * seg:(r + 1) * seg]
                    if blk.shape[1] < seg:
                        pad = np.zeros(
                            (D, seg - blk.shape[1]) + blk.shape[2:],
                            blk.dtype,
                        )
                        blk = np.concatenate([blk, pad], axis=1)
                    segd[f] = jax.device_put(
                        jnp.asarray(
                            blk.reshape((D * seg,) + blk.shape[2:])
                        ),
                        shard,
                    )
                segments.append(RaftState(**segd))
            n_f_np = np.asarray(
                jax.device_get(ck["n_f"]), np.int64
            ).reshape(D)
            distinct, generated, depth = (
                ck["distinct"], ck["generated"], ck["depth"],
            )
            level_sizes = ck["level_sizes"]
            trace_levels = ck["trace_levels"]
            mult_slots_total = np.asarray(ck["mult_slots"], np.int64)
            # restore the serialized sieve-cache slab when it matches
            # the resume point (pure optimization — an empty sieve is
            # always correct, just less effective for a few levels)
            self._load_sieve_slab(resume_from, depth, shard)
        else:
            segments = [jax.device_put(init_batch(cfg, D * seg), shard)]
            n_f_np = np.array([1] + [0] * (D - 1), np.int64)
            fv0, _ff0, _ms0 = self.fpr.state_fingerprints(
                init_batch(cfg, 1)
            )
            fp0 = np.asarray(jax.device_get(fv0.astype(U64)))[0]
            self.host_stores[int(fp0 % D)].insert(
                np.asarray([fp0], np.uint64)
            )
            distinct, generated, depth = 1, 0, 0
            level_sizes = [1]
            trace_levels = []
            mult_slots_total = np.zeros(self.K, np.int64)
            from ..engine.bfs import JaxChecker

            chk0 = JaxChecker(cfg)
            init1 = jax.device_put(init_batch(cfg, 1), repl)
            bad0 = int(jax.device_get(
                chk0._inv_scan(init1, jnp.asarray(1, I64))
            ))
            if bad0 >= 0:
                name0 = chk0._bad_invariant_name(init1, bad0)
                return CheckResult(
                    False, 1, 0, 0, (1,),
                    (f"Invariant {name0} is violated",
                     self._trace([], 0, 0)), {},
                )

        from ..engine.forecast import MIN_LEVELS, per_device_forecast

        def join_ck():
            nonlocal ck_fut
            if ck_fut is not None:
                ck_fut.result()
                ck_fut = None
                self._ck_fut = None

        while True:
            resilience.fault_fire("level.start")
            if resilience.preempt_requested():
                # the deferred tail writer may still hold the last
                # level's record — join it so the log is complete, then
                # exit resumable
                join_ck()
                raise resilience.Preempted(
                    checkpoint_dir if checkpoint_every else None, depth
                )
            if max_depth is not None and depth >= max_depth:
                break
            if self.watchdog is not None:
                self.watchdog.arm(f"mesh-deep level {depth + 1}")
            resilience.fault_fire("device.lost")
            resilience.fault_fire("device.hang")
            if presize and len(level_sizes) > MIN_LEVELS:
                fc = per_device_forecast(
                    level_sizes, distinct, max_depth, D
                )
                if fc is not None:
                    if self._cand_hist:
                        # measured per-round candidate peak, floored by
                        # the forecast: a round's parents are bounded by
                        # min(seg_rows, forecast per-device rows), at
                        # ~4 candidate lanes per parent
                        want_x = pow2ceil(max(
                            int(1.35 * max(self._cand_hist[-3:])),
                            4 * min(fc["peak_rows"], seg),
                        ) + 1)
                        if self.cap_x_max is not None:
                            want_x = min(want_x, self.cap_x_max)
                        want_x = min(
                            want_x, 1 << 22,
                            pow2ceil(fc["budget"] // (48 * D)) // 2,
                        )
                        if want_x > self.cap_x:
                            print(
                                f"[mesh-deep] presize: cap_x {self.cap_x}"
                                f" -> {want_x}", file=sys.stderr,
                            )
                            self.cap_x = want_x
                            self._dp.clear()
                            for k in ("cap_r", "cap_w"):
                                self.__dict__.pop(k, None)
                    want_s = min(
                        pow2ceil(int(2.2 * fc["final_rows"]) + 1),
                        pow2ceil(fc["budget"] // 8),
                        self.scap_max,
                    )
                    # growing an unused sieve would only recompile p1
                    if self.sieve and want_s > self.scap:
                        print(
                            f"[mesh-deep] presize: scap {self.scap} -> "
                            f"{want_s}", file=sys.stderr,
                        )
                        self._grow_sieve(want_s)
            out = self._deep_level(segments, n_f_np, depth)
            if "abort_gidx" in out:
                join_ck()
                return CheckResult(
                    False, distinct, generated, depth, tuple(level_sizes),
                    (
                        'Assert "split brain" (Raft.tla:185)',
                        self._trace(trace_levels, depth, out["abort_gidx"]),
                    ),
                )
            mult_slots_total += out["mult_slots"]
            generated += int(out["mult_slots"].sum())
            n_new = out["n_new"]
            if n_new == 0:
                break
            capf_prev = out["capf"]
            segments, n_f_np = out["segments"], out["n_f"]
            self.peak_dev_rows = max(
                self.peak_dev_rows, len(segments) * seg
            )
            distinct += n_new
            # store-occupancy conservation: the per-owner external
            # stores must jointly hold exactly the distinct set (a
            # lost/duplicated insert would silently skew every later
            # sieve drop and verdict)
            resilience.integrity.occupancy_check(
                "deep per-owner stores",
                sum(len(s) for s in self.host_stores), distinct,
                level=depth + 1,
            )
            level_sizes.append(n_new)
            depth += 1
            trace_levels.append((out["gpidx"], out["slots"]))
            graft_obs.level_commit(depth, n_new, distinct, generated)
            if self.progress is not None:
                st = out["stats"]
                self.progress(
                    dict(
                        level=depth, frontier=n_new, distinct=distinct,
                        generated=generated,
                        elapsed=time.monotonic() - t0,
                        exchange_bytes=st["exchanged_bytes"],
                        exchange_raw_bytes=st["raw_bytes"],
                        exchange_reduction=st["reduction"],
                    )
                )
            if graft_sanitize.CURRENT is not None:
                sig = (
                    len(segments), self.seg_rows, self.cap_x,
                    self.scap, self.cap_c_deep, self.cap_w,
                )
                if sig != getattr(self, "_san_sig", None):
                    graft_sanitize.note_shape_event(f"deep level {sig}")
                    self._san_sig = sig
                graft_sanitize.level_tick()
            if out["inv"] is not None:
                gp_r, sl_r, gidx = out["inv"]
                trace = self._trace(
                    trace_levels[:-1] + [(gp_r, sl_r)], depth, gidx
                )
                from ..oracle.explicit import resolve_invariant

                name = next(
                    (
                        n for n in cfg.invariants
                        if not resolve_invariant(n)(cfg, trace[-1][1])
                    ),
                    cfg.invariants[0],
                )
                join_ck()
                return CheckResult(
                    False, distinct, generated, depth, tuple(level_sizes),
                    (f"Invariant {name} is violated", trace),
                )
            if checkpoint_dir and checkpoint_every:
                # deferred tail write: the mdelta record of level L lands
                # on disk while the device expands level L+1 (the chain
                # is still strictly ordered — one writer, joined before
                # the next submit and before any return)
                join_ck()
                ns = SimpleNamespace(
                    gpidx=out["gpidx"], slots=out["slots"],
                    n_new_local=n_f_np.copy(),
                    mult_slots=out["mult_slots"],
                )
                sieve_np = None
                # shared size-aware snapshot cadence (the dump is a
                # resume optimization, not the source of truth)
                dump_every = hashstore.dump_interval(self.D * self.scap * 8)
                if (self.sieve and self.scap and dump_every
                        and depth % dump_every == 0):
                    # intended slab snapshot (the fetch is O(D*scap)):
                    # fetched on the MAIN thread (workers never
                    # dispatch), written by the deferred tail writer
                    # with the mdelta record
                    sieve_np = np.asarray(
                        jax.device_get(self._sieve_cache)
                    )
                ck_fut = self._ck_fut = self._ck_pool.submit(
                    self._save_mdelta, checkpoint_dir, depth, ns,
                    capf_prev, sieve_np,
                )
            if self.watchdog is not None:
                self.watchdog.disarm()
        join_ck()
        return CheckResult(
            True, distinct, generated, depth, tuple(level_sizes), None,
            self._action_counts(mult_slots_total),
        )

    @functools.cached_property
    def cap_r(self) -> int:
        # routing capacity per (src, dst) pair.  Duplicate fan-out lanes
        # CONCENTRATE on their child's owner (same fp -> same shard), so
        # uniform-hashing slack under-provisions skewed levels (measured:
        # reactive cap_x doublings at levels 9-10 of the reference config
        # were routing overflows).  cap_r = cap_x is worst-case exact —
        # per-owner count can never exceed the device's candidate total —
        # and the D*cap_r all_to_all buffers stay MB-scale.
        return self.cap_x

    @functools.cached_property
    def level_step(self):
        body = (
            self._body_all_to_all
            if self.exchange == "all_to_all"
            else self._body_all_gather
        )
        spec_state = jax.tree.map(lambda _: P("d"), init_batch(self.cfg, 1))
        vspec = P("d") if self.exchange == "all_to_all" else P()
        return self._mesh_program(
            body,
            (spec_state, P("d"), P("d"), vspec),
            LevelOut(
                jax.tree.map(lambda _: P("d"), init_batch(self.cfg, 1)),
                P("d"), vspec, P("d"), P(), P(), P(),
                P("d"), P("d"), P(), P("d"), P(), P("d"), P(), P(),
                P(),
            ),
        )

    # -- trace replay (slot chains are device-agnostic) --------------------

    def _trace(self, trace_levels, level, gidx):
        chain = []
        d, j = level, gidx
        while d > 0:
            gpidx, slots = trace_levels[d - 1]
            chain.append(int(slots[j]))
            j = int(gpidx[j])
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

    def _action_counts(self, mult_slots: np.ndarray) -> dict:
        out: dict[str, int] = {}
        fam = self.kern.slot_family
        for fi, (name, _fn, _c) in enumerate(self.kern.families):
            out[name] = out.get(name, 0) + int(mult_slots[fam == fi].sum())
        return {k: v for k, v in out.items() if v}

    # -- checkpoint / resume (TLC's states/ + -recover, mesh edition) ------
    #
    # Two formats, mirroring the single-device engine (engine/bfs.py):
    #
    # * **delta log** (``mdelta_####.npz``, the default): each level
    #   appends only its compact (parent-layout-index, slot) pairs plus
    #   per-device winner counts — resume REPLAYS the materialize pass
    #   from Init and recomputes fingerprints, so nothing store-sized is
    #   ever written.  Records are device-layout-relative and pinned to
    #   (D, exchange, canon) in their meta.
    #
    # * **monolith** (``latest.npz``, back-compat): full frontier + store
    #   in one file.

    def _save_mdelta(self, ckdir, depth, out, cap_f, sieve_np=None):
        """Append one level's delta record (compact layout prefixes).

        ``sieve_np`` (deep mode): the level-end sieve-cache slab,
        serialized VERSIONED alongside the segment-quantized frontier
        records so a resumed deep run keeps its sieve effectiveness
        instead of re-learning the visited set from zero.  The slab is
        an optimization cache — resume validates (version, depth, D,
        mode) and silently starts empty on any mismatch."""
        os.makedirs(ckdir, exist_ok=True)
        if sieve_np is not None:
            rows = sieve_np.shape[0] // self.D
            resilience.commit_npz(
                ckdir,
                "sieve_slab.npz",
                dict(
                    slab=sieve_np,
                    meta=np.asarray(
                        [hashstore.SLAB_VERSION, depth, self.D, rows,
                         int(self.use_hashstore)],
                        np.int64,
                    ),
                ),
                kind="sieve",
                depth=depth,
                run_fp=self._run_fp,
            )
        gpidx = np.asarray(out.gpidx).astype(np.int64)
        slots = np.asarray(out.slots).astype(np.int64)
        n_local = np.asarray(out.n_new_local).astype(np.int64).reshape(-1)
        valid = gpidx >= 0
        cap_c = gpidx.shape[0] // self.D
        # winners are compacted to each device block's prefix (_compact),
        # so the valid mask must equal the per-device prefix counts
        assert valid.reshape(self.D, cap_c).sum(1).tolist() == n_local.tolist()
        slot_dt = np.uint16 if self.K <= 0xFFFF else np.uint32
        # deep-sweep global parent indices (dev * capf + row) can pass
        # 2^32 at the frontier scales that tier targets — widen the
        # record rather than silently truncating (the loader reads
        # either width via .astype(int64))
        pidx_dt = (
            np.uint32
            if valid.sum() == 0 or gpidx[valid].max() <= 0xFFFFFFFF
            else np.uint64
        )
        resilience.commit_npz(
            ckdir,
            f"mdelta_{depth:04d}.npz",
            dict(
                pidx=gpidx[valid].astype(pidx_dt),
                slot=slots[valid].astype(slot_dt),
                n_local=n_local,
                mult=np.asarray(out.mult_slots, np.int64),
                meta=np.asarray(
                    [depth, int(valid.sum()), self.D, cap_f, cap_c,
                     1 if self.exchange == "all_to_all" else 0,
                     1 if self.canon == "late" else 0],
                    np.int64,
                ),
            ),
            kind="mdelta",
            depth=depth,
            run_fp=self._run_fp,
        )

    def _resume_from_mdeltas(self, ckdir, shard, repl):
        """Rebuild the mesh run state by replaying the delta log from Init.

        The replay materializes each level's (parent, slot) record with
        the shared successor kernel and recomputes canonical fingerprints
        — minutes of compute instead of a store-sized monolith read, and
        the rebuilt store holds exactly what an uninterrupted run's would
        (fp %% D shards for all_to_all, a sorted replicated array for
        all_gather)."""
        # -- self-healing pass: sweep orphaned tmp files, digest-verify
        # every record, quarantine corrupt/torn/unmanifested ones and
        # truncate to the last good contiguous prefix (a TAIL gap is a
        # healed crash; only an interior hole — which the ordered
        # writer cannot produce — stays fatal).  A bad sieve slab is
        # quarantined here and the resume silently starts with an
        # empty sieve (it is a pure optimization cache).
        files = resilience.heal_log(
            ckdir, "mdelta", run_fp=self._run_fp,
            slabs=("sieve_slab.npz",),
            legacy_run_fps=self._legacy_run_fps(),
        )
        if not files:
            if resilience.Manifest.load(ckdir).exists:
                # everything was quarantined: restart from Init (the
                # worst-case but still hands-free recovery)
                return None
            raise ValueError(f"no mdelta_*.npz checkpoints under {ckdir}")
        cfg, K, D = self.cfg, self.K, self.D
        # -- elastic replay: every record carries its OWN geometry -----
        # A record's pidx index its PARENT level's layout (Dz device
        # blocks of cap_f rows: gpidx = dev*cap_f + row) and its own
        # rows land in a (len(n_local), cap_c) layout.  The replay
        # tracks that per-record geometry instead of assuming the
        # current mesh width, which is what lets a D-device log resume
        # on D' != D devices: after the replay, ONE owner remap
        # (resilience/elastic.py) re-shards the final frontier by
        # fp % D' and the stores/slabs rebuild into the new partition
        # from the replayed fingerprints.
        z0 = np.load(files[0])
        par_D = int(z0["meta"][2])  # the log's initial mesh width
        frontier = init_batch(cfg, par_D)  # layout [par_D, cap_f=1]
        fv0, _ff0, _ms0 = self.fpr.state_fingerprints(
            jax.tree.map(lambda x: x[:1], frontier)
        )
        fps_all = [np.asarray(jax.device_get(fv0.astype(U64)))]
        trace_levels, level_sizes = [], [1]
        mult_slots_total = np.zeros(K, np.int64)
        depth = 0
        n_local = np.array([1] + [0] * (par_D - 1), np.int64)
        for f in files:
            z = np.load(f)
            meta = [int(x) for x in z["meta"]]
            d, n_new, Dz, cap_f, cap_c, a2a, late = meta
            nl = z["n_local"].astype(np.int64)
            D_own = len(nl)  # the record's own device-block count
            if d != depth + 1:
                raise ValueError(
                    f"mdelta log gap: expected level {depth + 1}, found "
                    f"level {d} ({f})"
                )
            if Dz != par_D:
                raise ValueError(
                    f"mdelta geometry break at level {d}: record "
                    f"expects a {Dz}-device parent layout, replay "
                    f"built {par_D} ({f})"
                )
            if a2a != (1 if self.exchange == "all_to_all" else 0):
                raise ValueError(
                    "checkpoint exchange mode differs from this run"
                )
            if late != (1 if self.canon == "late" else 0):
                raise ValueError(
                    "checkpoint canonicalization mode differs from this "
                    "run (pass the matching --canon)"
                )
            built = int(frontier.voted_for.shape[0]) // par_D
            if cap_f < built:
                raise ValueError(
                    f"mdelta level {d} expects a {cap_f}-wide frontier, "
                    f"replay built {built}"
                )
            if cap_f > built:
                # deep-sweep records describe segment-quantized frontier
                # blocks (cap_f = n_segments * seg_rows); pad each
                # DEVICE BLOCK so the record's global parent indices
                # (dev*cap_f + row) land on the replayed rows
                def _padblk(x, _c=cap_f, _b=built, _d=par_D):
                    blk = x.reshape((_d, _b) + x.shape[1:])
                    pad = jnp.zeros(
                        (_d, _c - _b) + x.shape[1:], x.dtype
                    )
                    return jnp.concatenate([blk, pad], axis=1).reshape(
                        (_d * _c,) + x.shape[1:]
                    )

                frontier = jax.tree.map(_padblk, frontier)
            # rebuild the padded device layout from the compact prefixes
            gpidx = np.full(D_own * cap_c, -1, np.int64)
            slots = np.zeros(D_own * cap_c, np.int64)
            off = 0
            for dev in range(D_own):
                c = int(nl[dev])
                gpidx[dev * cap_c : dev * cap_c + c] = z["pidx"][off : off + c]
                slots[dev * cap_c : dev * cap_c + c] = z["slot"][off : off + c]
                off += c
            valid = gpidx >= 0
            parents = jax.tree.map(
                lambda x: x[jnp.asarray(np.clip(gpidx, 0, None))], frontier
            )
            children = self.kern.materialize(parents, jnp.asarray(slots, I64))
            vmask = jnp.asarray(valid)
            children = jax.tree.map(
                lambda x: jnp.where(
                    vmask.reshape((-1,) + (1,) * (x.ndim - 1)),
                    x, jnp.zeros_like(x),
                ),
                children,
            )
            fv, _ff, _ms = self.fpr.state_fingerprints(children)
            fps_all.append(np.asarray(jax.device_get(fv.astype(U64)))[valid])
            trace_levels.append((gpidx, slots))
            level_sizes.append(n_new)
            mult_slots_total = mult_slots_total + z["mult"].astype(np.int64)
            frontier = children
            n_local = nl
            par_D = D_own  # this record's layout is the next's parent
            depth = d
        if par_D != D and trace_levels:
            print(
                f"[elastic] resuming a {par_D}-device log on a "
                f"{D}-device mesh: owner remap re-shards the frontier "
                f"by fp % {D} and the visited structures rehash into "
                "the new partition",
                file=sys.stderr,
            )
        distinct = int(sum(level_sizes))
        fps = np.unique(np.concatenate(fps_all))
        if len(fps) != distinct:
            raise ValueError(
                f"mdelta replay rebuilt {len(fps)} distinct fingerprints "
                f"for {distinct} recorded states — corrupt or mixed log"
            )
        # Rebalance the resumed frontier by OWNER (fp % D) onto the
        # CURRENT mesh.  Three layouts need this: chains written before
        # the owner-shipping exchange (rounds 2-4: the whole frontier on
        # device 0), any same-D resume whose layout drifted, and — the
        # elastic case — a log written on a different device count,
        # whose rows must redistribute by fp % D' before the first
        # resumed level.  The remap permutes rows host-side
        # (resilience/elastic.owner_rebalance), growing the per-device
        # block when the new partition needs it, and permutes the LAST
        # trace record identically so slot-chain replay stays exact
        # (earlier records reference their own levels' layouts, which
        # are untouched).
        if trace_levels and (D > 1 or par_D != D):
            cap_cr = frontier.voted_for.shape[0] // par_D
            fvh = np.asarray(jax.device_get(fv.astype(U64)))
            validh = np.asarray(valid)
            perm, counts_o, cap_new = resilience.elastic.owner_rebalance(
                fvh, validh, D,
                min_cap=cap_cr if par_D == D else 1,
            )
            lane = perm >= 0
            safe = np.clip(perm, 0, None)
            lane_dev = jnp.asarray(lane)
            safe_dev = jnp.asarray(safe)

            def _remap(x):
                g = x[safe_dev]
                return jnp.where(
                    lane_dev.reshape((-1,) + (1,) * (x.ndim - 1)),
                    g, jnp.zeros_like(g),
                )

            frontier = jax.tree.map(_remap, frontier)
            gpidx_l, slots_l = trace_levels[-1]
            gpidx_n = np.where(lane, gpidx_l[safe], -1)
            slots_n = np.where(lane, slots_l[safe], 0)
            trace_levels[-1] = (gpidx_n, slots_n)
            n_local = counts_o.astype(np.int64)
            # Persist the normalized layout: records appended after this
            # resume reference the REBALANCED level-d row positions, so
            # the on-disk level-d record must describe them or the next
            # full replay gathers wrong parents and dies as "corrupt or
            # mixed log".  Row order, n_local and (elastic case) the
            # own-layout geometry (cap_c + device-block count) change;
            # the record's pidx values AND its parent geometry (Dz,
            # cap_f — what the indices point into) are untouched.
            z_last = np.load(files[-1])
            meta_n = [int(x) for x in z_last["meta"]]
            meta_n[4] = int(cap_new)
            validn = gpidx_n >= 0
            slot_dt = z_last["slot"].dtype
            pidx_dt = (
                np.uint32
                if validn.sum() == 0
                or gpidx_n[validn].max() <= 0xFFFFFFFF
                else np.uint64
            )
            resilience.commit_npz(
                ckdir,
                os.path.basename(files[-1]),
                dict(
                    pidx=gpidx_n[validn].astype(pidx_dt),
                    slot=slots_n[validn].astype(slot_dt),
                    n_local=n_local,
                    mult=z_last["mult"],
                    meta=np.asarray(meta_n, np.int64),
                ),
                kind="mdelta",
                depth=depth,
                run_fp=self._run_fp,
            )
        if self.host_stores is not None:
            # the replay rebuilds the EXTERNAL stores: clear first (they
            # may hold pre-crash inserts, including a partially-completed
            # level that never reached the log — those would silently mark
            # reachable states as visited), then insert each owner's fps
            # (concurrently — the ctypes insert releases the GIL)
            from ..native import insert_sharded

            for s in self.host_stores:
                s.clear()
            insert_sharded(self.host_stores, fps)
            visited = None
        elif self.exchange == "all_to_all":
            per_shard = [np.sort(fps[fps % np.uint64(D) == o]) for o in range(D)]
            need = max(len(s) for s in per_shard)
            vcap = max(self.vcap, 1 << (2 * need - 1).bit_length())
            if self.use_hashstore:
                vis = hashstore.rebuild_np(per_shard, vcap)
            else:
                vis = np.full((D, vcap), np.uint64(0xFFFFFFFFFFFFFFFF))
                for o, s in enumerate(per_shard):
                    vis[o, : len(s)] = s
                vis = np.sort(vis, axis=1)
            self.vcap = vcap
            visited = jax.device_put(jnp.asarray(vis).reshape(-1), shard)
        else:
            vcap = max(self.vcap, 1 << (2 * len(fps) - 1).bit_length())
            vis = np.full(vcap, np.uint64(0xFFFFFFFFFFFFFFFF))
            vis[: len(fps)] = fps
            self.vcap = vcap
            visited = jax.device_put(jnp.asarray(np.sort(vis)), repl)
        msum = (
            self.fpr.msg_hash(frontier.msgs)
            if self.canon == "expand"
            else jnp.zeros((frontier.voted_for.shape[0], 1, 1), jnp.uint32)
        )
        return dict(
            frontier=jax.device_put(frontier, shard),
            msum=jax.device_put(msum, shard),
            n_f=jax.device_put(jnp.asarray(n_local, I64), shard),
            visited=visited,
            distinct=distinct,
            generated=int(mult_slots_total.sum()),
            depth=depth,
            level_sizes=level_sizes,
            trace_levels=trace_levels,
            mult_slots=mult_slots_total,
        )

    def _load_checkpoint(self, path, shard, repl):
        """Read a legacy ``latest.npz`` monolith (writer removed — the
        delta log replaced it; kept so old checkpoints stay resumable)."""
        z = np.load(path)
        meta = [int(x) for x in z["meta"]]
        D, distinct, generated, depth, a2a = meta[:5]
        if D != self.D:
            raise ValueError(
                f"checkpoint was taken on a {D}-device mesh, this run has "
                f"{self.D} (fingerprint ownership is D-dependent)"
            )
        if a2a != (1 if self.exchange == "all_to_all" else 0):
            raise ValueError("checkpoint exchange mode differs from this run")
        # the canon="late" frontier carries a dummy msum that the
        # canon="expand" incremental hash would silently consume as zeros
        late = meta[5] if len(meta) > 5 else 0
        if late != (1 if self.canon == "late" else 0):
            raise ValueError(
                "checkpoint canonicalization mode differs from this run "
                "(pass the matching --canon)"
            )
        frontier = RaftState(
            **{
                k[3:]: jax.device_put(jnp.asarray(z[k]), shard)
                for k in z.files
                if k.startswith("st_")
            }
        )
        vis_np = z["visited"]
        if self.use_hashstore and self.exchange == "all_to_all":
            # legacy monoliths hold sorted shards; rebuild the hash
            # slabs host-side at the same per-shard capacity (growing
            # if the sorted shard ran hotter than the 1/2 load line)
            arr = np.asarray(vis_np).reshape(D, -1)
            need = int(max((arr[o] != SENT).sum() for o in range(D)))
            vcap = max(arr.shape[1], hashstore.slab_rows(need))
            vis_np = hashstore.rebuild_np(arr, vcap).reshape(-1)
        visited = jax.device_put(
            jnp.asarray(vis_np),
            shard if self.exchange == "all_to_all" else repl,
        )
        if self.exchange == "all_to_all":
            self.vcap = vis_np.shape[0] // D
        else:
            self.vcap = vis_np.shape[0]
        trace_levels = [
            (z[f"trace_p{i}"], z[f"trace_s{i}"])
            for i in range(int(z["n_trace"][0]))
        ]
        return dict(
            frontier=frontier,
            msum=jax.device_put(jnp.asarray(z["msum"]), shard),
            n_f=jax.device_put(jnp.asarray(z["n_f"]), shard),
            visited=visited,
            distinct=distinct,
            generated=generated,
            depth=depth,
            level_sizes=list(int(x) for x in z["level_sizes"]),
            trace_levels=trace_levels,
            mult_slots=np.asarray(z["mult_slots"]),
        )

    # -- the distributed run ----------------------------------------------

    def run(
        self,
        max_depth: int | None = None,
        checkpoint_dir: str | None = None,
        checkpoint_every: int = 1,
        resume_from: str | None = None,
        presize: bool = True,
    ) -> CheckResult:
        try:
            return self._run_impl(
                max_depth=max_depth, checkpoint_dir=checkpoint_dir,
                checkpoint_every=checkpoint_every,
                resume_from=resume_from, presize=presize,
            )
        except BaseException as e:  # graftlint: waive[GL003] —
            # crash-path bookkeeping only: the tail write joins, device
            # loss gets a note, and the exception ALWAYS re-raises
            # a crash (device loss included) must not lose the deep
            # path's deferred tail write: join it so everything the
            # level loop committed stays on disk, then let the CLI map
            # device loss to exit 75 — --supervise relaunches and the
            # elastic resume re-shards onto the surviving mesh
            fut, self._ck_fut = getattr(self, "_ck_fut", None), None
            if fut is not None:
                try:
                    fut.result()
                except Exception:  # graftlint: waive[GL003] — the
                    # original crash must propagate, not the tail
                    # writer's secondary failure
                    pass
            if resilience.elastic.is_device_loss(e):
                print(
                    "[elastic] device failure mid-run — committed "
                    "levels are durable"
                    + (f" in {checkpoint_dir}" if checkpoint_dir else "")
                    + "; a relaunch resumes over the surviving mesh",
                    file=sys.stderr,
                )
            raise
        finally:
            if self.watchdog is not None:
                self.watchdog.disarm()

    def _run_impl(
        self,
        max_depth: int | None = None,
        checkpoint_dir: str | None = None,
        checkpoint_every: int = 1,
        resume_from: str | None = None,
        presize: bool = True,
    ) -> CheckResult:
        self._ck_fut = None
        if self.deep:
            return self.run_deep(
                max_depth=max_depth, checkpoint_dir=checkpoint_dir,
                checkpoint_every=checkpoint_every,
                resume_from=resume_from, presize=presize,
            )
        cfg, D = self.cfg, self.D
        mesh = self.mesh
        shard = NamedSharding(mesh, P("d"))
        repl = NamedSharding(mesh, P())
        t0 = time.monotonic()

        if self.host_store_dir is not None and self.host_stores is None:
            from ..native import HostFPStore

            self.host_stores = [
                HostFPStore(
                    os.path.join(self.host_store_dir, f"shard_{o:02d}"),
                    mem_budget_entries=self._store_budget_entries(),
                )
                for o in range(D)
            ]
            if resume_from is None:
                for s in self.host_stores:
                    s.clear()  # orphaned run files from a crashed process

        if checkpoint_dir and checkpoint_every:
            import glob as _glob

            if resume_from is None and os.path.isdir(checkpoint_dir):
                # sweep a killed earlier writer's orphaned tmp files
                resilience.sweep_tmp(checkpoint_dir)
            has_log = _glob.glob(os.path.join(checkpoint_dir, "mdelta_*.npz"))
            if resume_from is None and has_log:
                raise ValueError(
                    f"{checkpoint_dir} holds checkpoints from a previous "
                    "run; a fresh run would interleave two runs' logs — "
                    "resume with --recover or clear the directory"
                )
            if resume_from is not None and not os.path.isdir(resume_from):
                # a monolith resumes at depth d > 0; appending mdelta
                # records from level d+1 would leave a gapped (or, if the
                # directory already holds another run's records,
                # interleaved) chain that replay correctly rejects later —
                # refuse up front
                raise ValueError(
                    "cannot append mdelta checkpoints while resuming from "
                    "a monolith snapshot (the replay chain would start at "
                    f"level {1}+gap); resume from the delta directory, or "
                    "drop --checkpoint-dir for this run"
                )
        ck = None
        if resume_from is not None:
            if os.path.isdir(resume_from):
                ck = self._resume_from_mdeltas(resume_from, shard, repl)
                if ck is None and self.host_stores is not None:
                    # healing left nothing replayable: restart from
                    # Init with clean stores
                    for s in self.host_stores:
                        s.clear()
            else:
                ck = self._load_checkpoint(resume_from, shard, repl)
        if ck is not None:
            frontier, msum, n_f = ck["frontier"], ck["msum"], ck["n_f"]
            visited = ck["visited"]
            distinct, generated, depth = (
                ck["distinct"], ck["generated"], ck["depth"],
            )
            level_sizes, trace_levels = ck["level_sizes"], ck["trace_levels"]
            mult_slots_total = ck["mult_slots"]
        else:
            frontier = jax.device_put(init_batch(cfg, D), shard)
            fv, _ff, msum0 = self.fpr.state_fingerprints(frontier)
            if self.canon == "late":
                msum0 = jnp.zeros((D, 1, 1), jnp.uint32)
            msum = jax.device_put(msum0, shard)
            n_f = jax.device_put(jnp.asarray([1] + [0] * (D - 1), I64), shard)
            fp0 = np.asarray(jax.device_get(fv.astype(U64)))[0]
            if self.host_stores is not None:
                self.host_stores[int(fp0 % D)].insert(
                    np.asarray([fp0], np.uint64)
                )
                visited = None
            elif self.exchange == "all_to_all":
                vis = np.full((D, self.vcap), np.uint64(0xFFFFFFFFFFFFFFFF))
                if self.use_hashstore:
                    hashstore.insert_np(
                        vis[int(fp0 % D)], np.asarray([fp0], np.uint64)
                    )
                else:
                    vis[int(fp0 % D), 0] = fp0
                    vis = np.sort(vis, axis=1)
                visited = jax.device_put(jnp.asarray(vis).reshape(-1), shard)
            else:
                vis = np.full(self.vcap, np.uint64(0xFFFFFFFFFFFFFFFF))
                vis[0] = fp0
                visited = jax.device_put(jnp.asarray(np.sort(vis)), repl)
            distinct, generated, depth = 1, 0, 0
            level_sizes = [1]
            trace_levels = []
            mult_slots_total = np.zeros(self.K, np.int64)

            # init-state invariants (host-side, single state)
            from ..engine.bfs import JaxChecker  # reuse the batched kernels

            chk0 = JaxChecker(cfg)
            init1 = jax.device_put(init_batch(cfg, 1), repl)
            bad0 = int(
                jax.device_get(chk0._inv_scan(init1, jnp.asarray(1, I64)))
            )
            if bad0 >= 0:
                name0 = chk0._bad_invariant_name(init1, bad0)
                return CheckResult(
                    False, 1, 0, 0, (1,),
                    (f"Invariant {name0} is violated", self._trace([], 0, 0)), {},
                )

        def grow_visited(v, new_vcap):
            """Grow every store shard: SENT-pad (sorted mode) or rehash
            into a bigger slab (hash mode — slot homes move with the
            capacity mask, so padding would orphan every entry).  A
            hash rehash failure (host OOM, injected fault) DEGRADES to
            the sorted layout mid-run — the automatic --no-hashstore —
            instead of dying: the slab's live slots hold exactly the
            per-shard visited sets, so the conversion is lossless."""
            arr = np.asarray(v).reshape(D, -1)
            if self.use_hashstore:
                try:
                    resilience.fault_fire("hashstore.grow")
                    out = hashstore.rebuild_np(arr, new_vcap)
                    self.vcap = new_vcap
                    return jax.device_put(
                        jnp.asarray(out).reshape(-1), shard
                    )
                except Exception as e:  # graftlint: waive[GL003]
                    # any rehash failure degrades; never mid-run death
                    print(
                        f"[resilience] mesh hash-store grow failed "
                        f"({e}); degrading to the sorted visited "
                        "layout for the rest of the run",
                        file=sys.stderr,
                    )
                    self.use_hashstore = False
                    sorted_v = np.full(
                        (D, new_vcap), np.uint64(SENT)
                    )
                    for o in range(D):
                        live = np.sort(arr[o][arr[o] != SENT])
                        sorted_v[o, : len(live)] = live
                    self.vcap = new_vcap
                    for k in ("level_step", "level_phase1",
                              "level_phase2", "cap_r", "cap_w"):
                        self.__dict__.pop(k, None)
                    return jax.device_put(
                        jnp.asarray(sorted_v).reshape(-1), shard
                    )
            pad = np.full((D, new_vcap - arr.shape[1]), np.uint64(SENT))
            self.vcap = new_vcap
            return jax.device_put(
                jnp.asarray(np.concatenate([arr, pad], axis=1)).reshape(-1), shard
            )

        # predictive capacity sizing (VERDICT r4 #7): once enough levels
        # are observed to trust the growth model, size cap_x/vcap for the
        # WHOLE remaining run in one step, so reactive growth — a full
        # level-program recompile per doubling, the round-4 depth-14
        # killer (docs/MESH_DEEP.json) — never fires.  Re-checked every
        # level; only grows (a later, better forecast can top it up, but
        # typically this resizes exactly once).  The reactive loops below
        # stay as the backstop for forecast misses.
        from ..engine.forecast import (
            MIN_LEVELS, cap_margin, horizon_forecast,
        )
        self._gather_keep = 0  # all_gather: forecast floor for store trim
        self._cand_hist = []  # per-level max-device candidates / new states

        def maybe_presize(visited):
            sig = horizon_forecast(level_sizes, distinct, max_depth)
            if sig is None:
                return visited
            peak_new, final_distinct, budget = sig
            # cap_x holds one device's candidates for a level — forecast
            # it from the MEASURED per-device candidates-per-new ratio
            # (duplicate fan-out lanes make the hand-modeled ratio
            # undershoot at shallow depths; cand_max tracks the truth)
            r_cd = max(self._cand_hist[-3:]) if self._cand_hist else 4.0 / D
            want_x = pow2ceil(int(r_cd * peak_new * cap_margin()) + 1)
            if self.cap_x_max is not None:
                want_x = min(want_x, self.cap_x_max)
            # absolute backstops: a forecast gone wrong must degrade to
            # the reactive path, never to an absurd allocation/compile.
            # With cap_r = cap_x, the six all_to_all routing buffers cost
            # 48*D bytes per cap_x lane — keep them inside the budget.
            want_x = min(want_x, 1 << 22, pow2ceil(budget // (48 * D)) // 2)
            if want_x > self.cap_x:
                print(
                    f"[mesh] presize: cap_x {self.cap_x} -> {want_x} "
                    f"(forecast peak {peak_new}/level, measured "
                    f"cand/new ratio {r_cd:.2f})", file=sys.stderr,
                )
                self.cap_x = want_x
                for k in ("level_step", "level_phase1", "level_phase2",
                          "cap_r", "cap_w"):
                    self.__dict__.pop(k, None)
            if self.host_stores is None and self.exchange == "all_to_all":
                # reactive trigger is distinct > D*vcap//2; stay under it
                want_v = pow2ceil(int(2.2 * final_distinct / D) + 1)
                want_v = min(want_v, pow2ceil(budget // (8 * D)))
                if want_v > self.vcap:
                    print(
                        f"[mesh] presize: vcap {self.vcap} -> {want_v} "
                        f"(forecast {final_distinct} final distinct)",
                        file=sys.stderr,
                    )
                    visited = grow_visited(visited, want_v)
            elif self.host_stores is None:  # all_gather
                # ratchet only — a later, lower forecast must not shrink
                # the trim floor (shrinking mints a new store shape)
                self._gather_keep = max(self._gather_keep, min(
                    pow2ceil(int(1.05 * final_distinct)),
                    pow2ceil(budget // 8),
                ))
            return visited

        while True:
            resilience.fault_fire("level.start")
            if resilience.preempt_requested():
                # mdelta records are written synchronously on this
                # path, so the log is already complete — exit resumable
                raise resilience.Preempted(
                    checkpoint_dir if checkpoint_every else None, depth
                )
            if max_depth is not None and depth >= max_depth:
                break
            if self.watchdog is not None:
                self.watchdog.arm(f"mesh level {depth + 1}")
            resilience.fault_fire("device.lost")
            resilience.fault_fire("device.hang")
            if presize and len(level_sizes) > MIN_LEVELS:
                visited = maybe_presize(visited)
            if self.host_stores is not None:
                out = self._hosted_level(frontier, msum, n_f)
            else:
                if self.exchange == "all_to_all" and distinct > D * self.vcap // 2:
                    visited = grow_visited(visited, self.vcap * 4)
                # the level step is pure, so failed (overflowed) outputs
                # drop and the retry recomputes at the grown capacity
                grows = 0
                while True:
                    out = self.level_step(frontier, msum, n_f, visited)
                    ovf_v, ovf_x = jax.device_get(
                        (out.overflow_v, out.overflow_x)
                    )
                    if not (bool(ovf_v) or bool(ovf_x)):
                        break
                    if grows >= 8:
                        raise RuntimeError(
                            f"capacity overflow at level {depth + 1} "
                            f"(cap_x={self.cap_x}, cap_r={self.cap_r}, "
                            f"vcap={self.vcap})"
                        )
                    grows += 1
                    self.reactive_grows += 1
                    print(
                        f"[mesh] REACTIVE grow at level {depth + 1}: "
                        f"{'vcap' if bool(ovf_v) else 'cap_x'} "
                        f"(cap_x={self.cap_x}, cap_r={self.cap_r}, "
                        f"vcap={self.vcap})", file=sys.stderr,
                    )
                    if bool(ovf_v):
                        visited = grow_visited(visited, self.vcap * 4)
                    else:
                        # candidate compaction / routing lanes overflowed:
                        # grow cap_x (recompiles the level step — rare)
                        self.cap_x *= 2
                        for k in ("level_step", "cap_r", "cap_w"):
                            self.__dict__.pop(k, None)
            # one fused fetch of the level's control scalars (the ledger
            # of intended per-level syncs the sanitizer audits against)
            (abort_np, mult_np, gen_np, nnew_np, inv_np, cand_np,
             nloc_np) = jax.device_get((
                out.abort, out.mult_slots, out.generated,
                out.n_new_total, out.inv_bad, out.cand_max,
                out.n_new_local,
            ))
            if bool(abort_np):
                # locate the aborting parent (a current-frontier state) and
                # replay its slot chain, exactly like the single-device path
                bad_at = np.asarray(jax.device_get(out.abort_at))
                devs = np.nonzero(bad_at >= 0)[0]
                cap_f = frontier.voted_for.shape[0] // D
                gidx = int(devs[0]) * cap_f + int(bad_at[devs[0]])
                # action_counts stays None on violations, like the oracle
                return CheckResult(
                    False, distinct, generated, depth, tuple(level_sizes),
                    (
                        'Assert "split brain" (Raft.tla:185)',
                        self._trace(trace_levels, depth, gidx),
                    ),
                )
            mult_slots_total += np.asarray(mult_np)
            generated += int(gen_np)
            n_new = int(nnew_np)
            # per-owner count reconciliation across the exchange: the
            # psum'd owner-store admissions must equal the winners the
            # origins shipped and materialized
            resilience.integrity.reconcile(
                "mesh owner exchange", n_new,
                int(np.asarray(nloc_np, np.int64).sum()),
                level=depth + 1,
            )
            if n_new == 0:
                break
            self.skew.note(
                depth + 1, rows=np.asarray(nloc_np, np.int64).reshape(-1)
            )
            cap_f_prev = frontier.voted_for.shape[0] // D
            distinct += n_new
            level_sizes.append(n_new)
            self._cand_hist.append(int(cand_np) / n_new)
            depth += 1
            # gpidx/slots are the level's two largest host-bound arrays:
            # their copies start now and complete through the ledgered
            # window drain AFTER the store trim / next-frontier device
            # work below has been dispatched (window 0 = serial fetch)
            tail = graft_pipeline.DeferredFetch(
                self.pipeline, (out.gpidx, out.slots)
            )
            if self.host_stores is None:
                visited = out.visited
                if self.exchange == "all_gather":
                    # the replicated store grows by D*cap_x sentinel-padded
                    # slots per level; trim back to the tightest pow2 that
                    # holds every distinct fp (store is sorted, SENT-
                    # padded).  The presize forecast floors the trim so
                    # the store shape stays constant over the run instead
                    # of stepping through every magnitude (one level-step
                    # compile per magnitude otherwise); SENT-pad up to the
                    # floor when the merged store is still shorter (SENT
                    # sorts last, so the pad keeps the array sorted).
                    keep = max(4096, 1 << distinct.bit_length(),
                               self._gather_keep)
                    vis = out.visited[:keep]
                    if vis.shape[0] < keep:
                        vis = jnp.concatenate([
                            vis,
                            jnp.full((keep - vis.shape[0],), SENT, U64),
                        ])
                    visited = jax.device_put(vis, repl)
            frontier, msum = out.children, out.child_msum
            n_f = jax.device_put(out.n_new_local, shard)
            gp_np, sl_np = tail.get()
            trace_levels.append(
                (np.asarray(gp_np, np.int64), np.asarray(sl_np, np.int64))
            )
            graft_obs.level_commit(depth, n_new, distinct, generated)
            if self.progress is not None:
                self.progress(
                    dict(
                        level=depth, frontier=n_new, distinct=distinct,
                        generated=generated, elapsed=time.monotonic() - t0,
                    )
                )
            if graft_sanitize.CURRENT is not None:
                sig = (
                    frontier.voted_for.shape[0],
                    0 if visited is None else visited.shape[0],
                    self.cap_x, self.cap_w, self.vcap,
                )
                if sig != getattr(self, "_san_sig", None):
                    graft_sanitize.note_shape_event(f"mesh level {sig}")
                    self._san_sig = sig
                graft_sanitize.level_tick()
            if int(inv_np) > 0:
                bad_at = np.asarray(jax.device_get(out.inv_bad_at))
                devs = np.nonzero(bad_at >= 0)[0]
                gidx = int(devs[0]) * (out.children.voted_for.shape[0] // D) + int(
                    bad_at[devs[0]]
                )
                trace = self._trace(trace_levels, depth, gidx)
                # identify which configured invariant tripped by re-checking
                # the violating state host-side
                from ..oracle.explicit import resolve_invariant

                name = next(
                    (
                        n
                        for n in cfg.invariants
                        if not resolve_invariant(n)(cfg, trace[-1][1])
                    ),
                    cfg.invariants[0],
                )
                return CheckResult(
                    False, distinct, generated, depth, tuple(level_sizes),
                    (f"Invariant {name} is violated", trace),
                )
            # checkpoint only invariant-clean levels (a resumed run never
            # re-checks the loaded frontier).  Delta-log format: the
            # replay chain needs EVERY level, so checkpoint_every only
            # gates whether checkpointing happens at all.
            if checkpoint_dir and checkpoint_every:
                # pass the HOST copies fetched above — _save_mdelta on
                # the raw LevelOut would re-fetch gpidx/slots (the two
                # largest per-level arrays) a second time per level
                self._save_mdelta(
                    checkpoint_dir, depth,
                    SimpleNamespace(
                        gpidx=gp_np, slots=sl_np,
                        n_new_local=nloc_np, mult_slots=mult_np,
                    ),
                    cap_f_prev,
                )
            if self.watchdog is not None:
                # per-level disarm records this level's wall time so
                # the next arm's budget adapts (max(floor, 8x last))
                self.watchdog.disarm()

        return CheckResult(
            True, distinct, generated, depth, tuple(level_sizes), None,
            self._action_counts(mult_slots_total),
        )
