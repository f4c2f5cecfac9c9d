"""Whole-level megakernel: one jitted device program per BFS level.

Round 5's silicon profile pinned the hot-path cost structure on launch
count, not FLOPs: every chunk paid ~38 ms of fixed dispatch/queue
latency on that device, and a staged level is still 4-5
separate device programs (expand span, visited filter, fused dedup,
materialize slices, invariant scan) plus their control fetches — small
and mid-size levels run at the launch floor rather than hardware speed.
PR 6's MXU rewrite removed the structural blocker (the hot kernels are
gather/scatter-free matmul pipelines), so the whole level fuses into
ONE program, the stage-fusion move BLEST and "Graph Traversal on
Tensor Cores" (PAPERS.md) use to keep BFS resident on the accelerator:

1. **chunked expand inside a ``lax.while_loop``** — the trip count is
   data-bounded (``i * chunk < n_f``) while every shape is static, the
   repo's fixed-shape idiom, so padded frontier capacity never costs
   dead chunk expansions; each trip runs the engine's unchanged
   ``_expand_chunk_impl`` body (MXU guards + compact + materialize +
   fingerprints) and lands its cap_x compacted candidates in a
   preallocated lane buffer via ``dynamic_update_slice``;
2. **fused hashstore probe-and-insert** over the whole level's lanes
   (ops/hashstore.py ``probe_and_insert_impl`` — uniqueness, visited
   membership and the store update in one pass, the min-(fp_full,
   payload) representative per view fingerprint preserved, so counts
   stay bit-identical to the staged path);
3. **materialize** of the fresh frontier as a ``lax.scan`` over
   slice-bounded ``_mat_slice_impl`` bodies (the transient message-set
   inflate stays slice-sized, exactly the staged path's memory bound);
4. **invariant/abort scan** folded into the materialize slices, reduced
   to one first-bad index.

The program returns the new frontier, the pending slab, and a small
control vector (new-frontier count, abort position, overflow flags,
first-bad index, slab load) plus the level's trace/delta arrays
(pidx/slot/fps, pre-cast to their checkpoint dtypes) — the host
completes ONE ledgered fetch per level (through the pipeline's
``DeferredFetch``, so the transfer ledger and the ``pipeline.window``
fault site both still see it) and dispatches the next level.  Every
overflow class re-enters the engine's existing grow-and-redo machinery
against the ORIGINAL slab (the kernels are functional; the pending
slab is discarded), and checkpoint/delta commits, trace
reconstruction and the ``--audit N`` legacy re-expansion consume the
fused outputs unchanged.

Buffer donation: on backends that support it (TPU/GPU — the CPU runner
ignores donation), the frontier argument is donated and returned as a
pass-through output.  Input-output aliasing makes the pass-through
zero-copy, which keeps the parent frontier alive for the overflow-redo
loop and the integrity audit while giving XLA in-place freedom over
the frontier-shaped intermediates.

The staged path is retained verbatim as the A/B and audit reference:
``--megakernel 0`` / ``TLA_RAFT_MEGAKERNEL=0`` reverts, and the engine
falls back per level for the regimes the fused program does not cover
(orbit's split programs, the external host store beyond the group
fusion, a degraded hash store, and grouped ultra-deep levels where the
staged visited pre-filter bounds the candidate working set).
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np

U64 = jnp.uint64
I64 = jnp.int64
I32 = jnp.int32
# numpy scalars: module import stays device-free (graftlint GL001)
SENT = np.uint64(0xFFFFFFFFFFFFFFFF)
BIG = np.int64(1 << 62)

# control-vector layout (i64[CTRL_LEN]); the one fused scalar bundle the
# host reads per level
CTRL_N_NEW = 0      # fresh (new-frontier) states this level
CTRL_ABORT = 1      # first split-brain parent index, BIG if none
CTRL_OVF_X = 2      # a chunk overflowed its cap_x compaction budget
CTRL_OVF_SLAB = 3   # a probe window filled (grow + redo vs ORIGINAL slab)
CTRL_OVF_M = 4      # a child overflowed the cap_m sparse msg-id width
CTRL_BAD = 5        # first invariant-violating new row, -1 if none
CTRL_SLAB_LIVE = 6  # live slots of the pending slab (= distinct', free
#                     conservation signal for integrity.occupancy_check)
CTRL_TIER_HITS = 7  # fresh lanes the spill sieve flagged as POSSIBLE
#                     generation revisits (0 = provably none: the level
#                     can commit without any host tier correction)
CTRL_LEN = 8


def enabled_by_env() -> bool:
    """Megakernel default: ON; ``TLA_RAFT_MEGAKERNEL=0`` reverts to the
    staged per-stage program chain (the A/B and audit reference)."""
    return os.environ.get("TLA_RAFT_MEGAKERNEL", "1") != "0"


def donation_supported() -> bool:
    """Input buffer donation is a no-op (with a log-spam warning) on the
    CPU runner; only enable it where XLA honors the aliasing."""
    try:
        return jax.default_backend() in ("tpu", "gpu", "cuda", "rocm")
    except Exception:  # graftlint: waive[GL003] — a backend probe
        # failure just means "no donation"; it must never take the
        # checker down
        return False


def mat_slice_width(cap_out: int, chunk: int) -> int:
    """Materialize slice width: the largest chunk multiple <= 8*chunk
    that tiles ``cap_out`` evenly (capacities are {2^k, 3*2^(k-1)}
    chunk multiples, so a divisor always exists down to ``chunk``).
    Mirrors the staged path's 8x-chunk slice bound — the in-program
    transient (the per-slice message-set inflate) stays slice-sized."""
    if cap_out <= 8 * chunk:
        return cap_out
    for mult in (8, 4, 2, 1):
        if cap_out % (mult * chunk) == 0:
            return mult * chunk
    return chunk


# shared jit cache for the fused program, keyed on the engine's
# ``LevelSpec`` (engine/bfs.py: everything the traced body reads) and
# the donation flag.  The program closes over the spec alone, so every
# engine on one config at the same budgets shares ONE jitted program
# and its traced shape rungs — a new checker re-traces nothing its
# predecessors traced (the benchmark's back-to-back checks, the test
# suite's dozens of same-config checkers) — and no entry pins an
# engine.  Bounded LRU: each entry holds its compiled executables.
_PROG_CACHE: "dict" = {}
_PROG_CACHE_MAX = 16


def cached_program(cache: dict, key, build, stats: dict | None = None):
    """LRU lookup shared by the level and superstep program caches: the
    program under ``key``, made by ``build()`` on a miss.  ``stats``
    counts the lookups as ``program_hits`` and ``program_builds``."""
    prog = cache.pop(key, None)
    hit = prog is not None
    if not hit:
        prog = build()
    cache[key] = prog
    while len(cache) > _PROG_CACHE_MAX:
        cache.pop(next(iter(cache)))
    if stats is not None:
        stats["program_hits" if hit else "program_builds"] += 1
    return prog


def level_program_for(spec, donate: bool, stats: dict | None = None):
    def build():
        # flight-recorder breadcrumb: a fresh fused program was built
        # (the compile itself lands on the compile track when it runs)
        from ..obs import telemetry as _obs

        _obs.emit("program", kind="megakernel", chunk=spec.chunk,
                  cap_x=spec.cap_x, cap_m=spec.cap_m)
        return build_level_program(spec, donate)

    return cached_program(_PROG_CACHE, (spec, bool(donate)), build, stats)


def fused_level_core(spec, frontier, slab, n_f, sieve, cap_out: int):
    """The traced body of ONE fused BFS level — the shared core both the
    per-level program below and the multi-level superstep driver
    (engine/superstep.py) trace, so the two paths can never drift on
    the level semantics (same expand while_loop, same probe-and-insert,
    same materialize scan, same invariant reduce).

    ``spec`` is the engine's ``LevelSpec``: the budgets and kernels
    frozen when the program was built, so a rung traced later reads the
    same ``chunk``/``cap_x`` as the first.  ``sieve`` is the spill
    sieve's device word image (``u64[M]``, M a power of two; the 1-word
    all-zero sentinel while tiering is off) — fresh lanes are probed
    in-program (ops/sieve.py) and the hit count returned, so a level
    with zero hits provably contains no spilled revisits.  Returns
    ``(new_frontier [cap_out], slab2, n_new i64, abort_at i64,
       ovf_x bool, ovf_slab bool, ovf_m bool, bad_global i64,
       mult i64[K], fps_out u64[cap_out], pay_out i64[cap_out],
       tier_hits i64)``
    with ``pay_out`` the survivors' raw payloads (pidx*K+slot) in lane
    (= payload-ascending) order.
    """
    from ..ops import hashstore
    from ..ops import sieve as sieve_mod

    K = spec.K
    chunk = spec.chunk
    cap_x = spec.cap_x
    cap_f = frontier.voted_for.shape[0]
    n_chunks = cap_f // chunk
    N = n_chunks * cap_x  # level-wide candidate lane budget

    # -- 1. chunked expand: while_loop with a data-bounded trip
    # count over static shapes — dead chunks beyond n_f never run
    def cond(c):
        i = c[0]
        return i.astype(I64) * chunk < n_f

    def body(c):
        i, cv_b, cf_b, cp_b, mult, ab, ovf = c
        start = i.astype(I64) * chunk
        part = jax.tree.map(
            lambda x: jax.lax.dynamic_slice_in_dim(
                x, i * chunk, chunk
            ),
            frontier,
        )
        cv, cf, cp, m, a, o = spec._expand_chunk_impl(part, start, n_f)
        off = i * cap_x
        cv_b = jax.lax.dynamic_update_slice(cv_b, cv, (off,))
        cf_b = jax.lax.dynamic_update_slice(cf_b, cf, (off,))
        cp_b = jax.lax.dynamic_update_slice(cp_b, cp, (off,))
        return (
            i + 1, cv_b, cf_b, cp_b,
            mult + m, jnp.minimum(ab, a), ovf | o,
        )

    init = (
        jnp.zeros((), I32),
        jnp.full((N,), SENT, U64),
        jnp.full((N,), SENT, U64),
        jnp.full((N,), -1, I64),
        jnp.zeros((K,), I64),
        jnp.asarray(BIG, I64),
        jnp.zeros((), bool),
    )
    (_i, cv_buf, cf_buf, cp_buf, mult, abort_at,
     ovf_x) = jax.lax.while_loop(cond, body, init)

    # -- 2. fused probe-and-insert: uniqueness + membership + store
    # update in one pass; fresh lanes compact to a prefix in LANE
    # (= payload-ascending) order, the staged path's exact contract
    slab2, fresh, n_new, ovf_slab = hashstore.probe_and_insert_impl(
        slab, cv_buf, cf_buf, cp_buf
    )
    new_fps, new_pay = hashstore.compact_fresh(fresh, cv_buf, cp_buf, N)
    if cap_out > N:
        # tiny cap_x configs: the frontier-capacity quantizer's
        # >= chunk floor can exceed the lane budget — pad with dead
        # lanes (n_new <= N always, so nothing real is cut)
        new_fps = jnp.concatenate(
            [new_fps, jnp.full((cap_out - N,), SENT, U64)]
        )
        new_pay = jnp.concatenate(
            [new_pay, jnp.full((cap_out - N,), -1, I64)]
        )
    fps_out = new_fps[:cap_out]
    pay_out = new_pay[:cap_out]

    # -- spill-sieve probe over the fresh lanes: a definite-miss never
    # leaves the device; dead (SENT-padded) lanes can never count (a
    # fresh view fingerprint is never the sentinel)
    tier_hit = sieve_mod.probe_impl(sieve, fps_out) & (fps_out != SENT)
    tier_hits = tier_hit.sum().astype(I64)

    # -- 3+4. materialize + invariant scan over slice-bounded scan
    # steps.  cap_out is a forecast (it overshoots n_new by design,
    # that is what makes the shape static), so slices wholly beyond
    # n_new are SKIPPED via lax.cond — the scan body is sequential,
    # the dead branch emits zeros (exactly the staged path's
    # zero-padded frontier tail) and the overshoot costs nothing
    sl = mat_slice_width(cap_out, chunk)
    n_slices = cap_out // sl

    def live_slice(args):
        pay_slice, take = args
        return spec._mat_slice_impl(frontier, pay_slice, take)

    def dead_slice(args):
        pay_slice, _take = args
        child = jax.tree.map(
            lambda x: jnp.zeros(
                (sl,) + x.shape[1:], x.dtype
            ),
            frontier,
        )
        return child, jnp.asarray(-1, I64), jnp.zeros((), bool)

    def mat_body(_carry, si):
        pay_slice = jax.lax.dynamic_slice_in_dim(pay_out, si * sl, sl)
        take = jnp.clip(n_new - si.astype(I64) * sl, 0, sl)
        child, bad_at, ovf_m = jax.lax.cond(
            take > 0, live_slice, dead_slice, (pay_slice, take)
        )
        return _carry, (child, bad_at, ovf_m)

    _c, (children, bad_ats, ovf_ms) = jax.lax.scan(
        mat_body, jnp.zeros((), I32), jnp.arange(n_slices, dtype=I32)
    )
    new_frontier = jax.tree.map(
        lambda x: x.reshape((cap_out,) + x.shape[2:]), children
    )
    # first bad global index: slices stack in order, so the minimum
    # of (si*sl + first_bad_in_slice) IS the first bad overall
    sli = jnp.arange(n_slices, dtype=I64)
    badg = jnp.where(bad_ats >= 0, sli * sl + bad_ats, BIG)
    bad_min = badg.min()
    bad_global = jnp.where(bad_min >= BIG, jnp.asarray(-1, I64), bad_min)

    return (new_frontier, slab2, n_new, abort_at, ovf_x, ovf_slab,
            ovf_ms.any(), bad_global, mult, fps_out, pay_out, tier_hits)


def build_level_program(spec, donate: bool):
    """The jitted whole-level program for one ``LevelSpec``.

    Closes over the spec alone (never an engine), so a grown budget —
    a new spec — gets a new program through ``level_program_for``
    while every rung of this one keeps its own budgets.  ``cap_out`` — the
    new frontier's static capacity — is a static argument: the shape
    ladder quantizes it through ``_frontier_cap`` and the AOT prewarmer
    compiles the forecast rungs ahead of depth.

    Returns outputs
      ``(new_frontier, slab2, ctrl i64[CTRL_LEN], mult i64[K],
         fps u64[cap_out], pidx u32[cap_out], slot u16|u32[cap_out]
         [, frontier_passthrough])``
    with the pass-through present only under donation (input-output
    aliasing makes it zero-copy; it keeps the parent frontier alive for
    redo and audit).
    """
    K = spec.K
    slot_dt = jnp.uint16 if K <= 0xFFFF else jnp.uint32

    def level_body(frontier, slab, n_f, sieve, cap_out: int):
        (new_frontier, slab2, n_new, abort_at, ovf_x, ovf_slab, ovf_m,
         bad_global, mult, fps_out, pay_out, tier_hits) = fused_level_core(
            spec, frontier, slab, n_f, sieve, cap_out
        )

        ctrl = jnp.stack([
            n_new.astype(I64),
            abort_at,
            ovf_x.astype(I64),
            ovf_slab.astype(I64),
            ovf_m.astype(I64),
            bad_global,
            (slab2 != SENT).sum().astype(I64),
            tier_hits,
        ])
        pidx_out = (pay_out // K).astype(jnp.uint32)
        slot_out = (pay_out % K).astype(slot_dt)
        outs = (new_frontier, slab2, ctrl, mult, fps_out, pidx_out,
                slot_out)
        if donate:
            # pass-through keeps the donated parent alive for the
            # overflow-redo loop and the audit (aliased, zero-copy)
            outs = outs + (frontier,)
        return outs

    return jax.jit(
        level_body,
        static_argnames=("cap_out",),
        donate_argnums=(0,) if donate else (),
    )


def ledger_trace(cfg=None):
    """Closed jaxpr of the megakernel at the audit's tiny reference
    shapes — the graftlint layer-2 registration (golden ledger + the
    GL010 gather/scatter budget: the MXU expand/materialize inside
    contribute 0 data-indexed gathers; the ledgered residue is the
    hashstore probe rounds and the materialize parent-row gathers)."""
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
    prog = build_level_program(eng.level_spec(), donate=False)
    return jax.make_jaxpr(
        lambda f, s, n, sv: prog(f, s, n, sv, cap_out=64)
    )(fr, slab, n_f, sieve)
