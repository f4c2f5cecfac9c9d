"""Config-batched checking: many CONSTANT bindings, one dispatch stream.

Production sweep traffic is huge numbers of *small* configs (CI runs,
parameter sweeps, per-user models), each paying the ~38 ms/dispatch
fixed cost and the compile ladder alone when run through a per-config
``check.py`` process (docs/PERF.md round-2 findings; ROADMAP item 2).
This module is the batched device-execution core of the sweep service:
it stacks the state spaces of a whole **shape bucket** of configs into
ONE flat frontier and runs the existing expand / fingerprint /
probe-and-insert kernels over the union, so hundreds of small state
spaces ride a single dispatch stream and share a single compiled
program ladder.

**Shape bucket.**  Every tensor shape and every hash table in the
pipeline derives from (S, Vals, MaxElection): the state layout from
(S, L=V+1), the message universe and fingerprint tables from
(S, V, T=MaxElection).  ``MaxRestart`` is the one CONSTANT that appears
*only* as a guard threshold (``restartCount < MaxRestart`` in the
Restart family) — it never shapes a tensor and never enters a hash
table.  The bucket key is therefore the config with ``max_restart``
struck out (:func:`bucket_key`): configs in one bucket differ only in
MaxRestart (and per-job depth caps), the bucket kernel is compiled once
at the bucket's MAX MaxRestart, and each config's tighter bound is
applied as a per-row refinement mask on the Restart slots outside the
kernel — ``role = Leader ∧ rc < min(mr_c, mr_max) ≡ rc < mr_c``, so the
per-config guard semantics are exact, not approximated.

**Per-config separation.**  Rows of the flat frontier carry a config
id; fingerprints are salted per config (``fp ^ splitmix64(slot)``)
before entering the ONE shared open-addressing slab, so dedup is
config-scoped with the same 2^-64 collision odds the checker already
accepts, while membership for the whole bucket is a single fused
probe-and-insert.  Per-config liveness masks gate expansion;
per-config abort / invariant / fixpoint flags retire configs
independently (a violation in one tenant's model never stalls the
rest of the bucket).

**Parity.**  Because the bucket kernel, universe and fingerprint
tables are byte-identical to the ones a sequential ``check.py`` run of
each member builds (MaxRestart does not enter any of them), and the
in-level representative rule is the same min-(fp_full, payload) group
reduce, per-config ``distinct`` / ``generated`` / ``depth`` /
``level_sizes`` are **bit-identical** to sequential runs
(tests/test_service.py diffs them config by config).  Violating
configs retire with the engine's exact stop-point counts and
violation string (parity-gated too); the batched core deliberately
keeps no per-level (parent, slot) spills, so a TLC-style
counterexample *trace* needs a sequential ``check.py`` re-run of that
one config (docs/SERVICE.md degradation ladder).
"""

from __future__ import annotations

import dataclasses
import functools
import json
import os
import time

import numpy as np

import jax
import jax.numpy as jnp

from .. import resilience
from ..analysis import devprof as graft_devprof
from ..analysis import sanitize as graft_sanitize
from ..obs import telemetry as graft_obs
from ..config import RaftConfig
from ..engine import forecast
from ..engine import megakernel as graft_megakernel
from ..engine import superstep as graft_superstep
from ..engine.invariants import resolve_invariant_kernel
from ..models.raft import RaftState, init_batch
from ..ops import hashstore
from ..ops import tables as _tables
from ..ops.hashstore import SENT
from ..ops.mxu_expand import mxu_enabled_by_env
from ..ops.successor import get_kernel

I32 = jnp.int32
I64 = jnp.int64
U64 = jnp.uint64

# the Restart family's id in the slot grid (ops/successor.py family
# table) — the one family whose guard reads max_restart
RESTART_FAMILY = 11

# bucket-state checkpoint records (crash-safe batched runs): write-once
# per-level names, so the rename-beat-manifest crash window leaves an
# UNMANIFESTED new record (adoptable, like the engine's delta log)
# instead of making a rolling name look corrupt
BSTATE_FMT = "bstate_{:04d}.npz"
BSTATE_GLOB = "bstate_*.npz"

_STATE_FIELDS = RaftState._fields


def bucket_key(cfg: RaftConfig) -> RaftConfig:
    """The shape-bucket key: the config with MaxRestart struck out.

    Two configs share a compiled program iff their keys are equal (see
    module docstring for why MaxRestart — and only MaxRestart — is the
    free axis)."""
    return dataclasses.replace(cfg, max_restart=0)


def _splitmix64(x: np.ndarray) -> np.ndarray:
    """The splitmix64 finalizer (numpy u64, vectorized)."""
    with np.errstate(over="ignore"):
        z = (x + np.uint64(0x9E3779B97F4A7C15)).astype(np.uint64)
        z = (z ^ (z >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
        z = (z ^ (z >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
        return z ^ (z >> np.uint64(31))


def config_salts(n: int) -> np.ndarray:
    """Per-config-slot fingerprint salts (deterministic, never zero-ish
    by construction of splitmix64 on distinct inputs)."""
    return _splitmix64(np.arange(1, n + 1, dtype=np.uint64))


class BucketPrograms:
    """The jitted device programs of one shape bucket, shared across
    every bucket run of that key in the process (lru-cached below) —
    the queue's whole compile ladder is paid once per (key, C) pair.

    ``C`` is the pow2-padded config-slot count: the per-config segment
    reductions bake it into the trace, so padding it quantizes the
    program space (a 5-config and a 7-config bucket of the same key
    share the C=8 programs)."""

    def __init__(self, kcfg: RaftConfig, mxu: bool, C: int):
        self.kcfg = kcfg
        self.C = C
        self.kern = get_kernel(kcfg, mxu=mxu)
        self.fpr = self.kern.fpr
        self.K = self.kern.K
        self._fam_rs = jnp.asarray(self.kern.slot_family == RESTART_FAMILY)
        self.inv_fns = [
            (name, resolve_invariant_kernel(name))
            for name in kcfg.invariants
        ]
        # every program takes the kernel's device tables as its first
        # operand and traces on a view bound to them (ops/tables.py)
        self.step = self._program(self._level_step)
        self.mat = self._program(self._mat_step)
        # whole-level fusion (the service slice of the megakernel,
        # engine/megakernel.py): step + on-device survivor-lane
        # compaction + materialize + invariant scan as ONE program —
        # a bucket level becomes one dispatch + one fused fetch
        self.fused = self._program(
            self._fused_level, static_argnames=("g_cap",)
        )
        # multi-level resident superstep (the service slice of
        # engine/superstep.py): up to N whole bucket levels inside one
        # lax.while_loop around the fused level body, per-config
        # retirement (depth caps, aborts, fixpoints) tracked ON DEVICE
        # and per-level ledgers spooled into a ring — small configs
        # retire whole jobs in one or two dispatches
        self.sstep = self._program(
            self._superstep, static_argnames=("g_cap", "span", "ring")
        )
        self.inv_ok = self._program(self._inv_ok)
        # shape keys seen by the jitted entry points — the honest
        # "programs traced" ledger behind the bench's
        # configs-per-compile stat (jax's jit cache is keyed on
        # exactly these abstract shapes)
        self.shape_keys: set = set()

    def _program(self, method, **jit_kwargs):
        return _tables.method_program(
            method, self.kern.device_tables, **jit_kwargs
        )

    # -- traced bodies -----------------------------------------------------

    def _inv_ok(self, st: RaftState):
        ok = jnp.ones((st.voted_for.shape[0],), bool)
        for _name, fn in self.inv_fns:
            ok = ok & fn(self.kcfg, st, self.kern.tables)
        return ok

    def _level_step(self, st, live, crow, mr_row, salt_row, slab):
        """One bucket level on the device: expand the whole flat
        frontier, refine the Restart guards per config, salt + dedup +
        visited-insert through the shared slab, and reduce the
        per-config ledgers.  Returns
        (slab', fresh bool[B*K], salted fps u64[B*K], gen i64[C],
        new i64[C], abort bool[C], overflow)."""
        K = self.K
        msum = self.fpr.msg_hash(st.msgs)
        exp = self.kern.expand(st, msum)
        # per-row config lookup as a one-hot masked reduce (the repo's
        # scatter/gather-free idiom; C is tiny)
        oh = crow[:, None] == jnp.arange(self.C)[None, :]  # [B, C]
        mr_of_row = jnp.where(oh, mr_row[None, :], 0).sum(1, dtype=I32)
        salt_of_row = jnp.where(
            oh, salt_row[None, :], jnp.uint64(0)
        ).sum(1, dtype=jnp.uint64)
        # per-config MaxRestart refinement: the kernel was compiled at
        # the bucket max; a member's tighter bound masks its Restart
        # slots here (rc < min(mr_c, mr_max) == rc < mr_c — exact)
        rc = st.restart_count.astype(I32)
        ok = live[:, None] & (
            ~self._fam_rs[None, :] | (rc[:, None] < mr_of_row[:, None])
        )
        valid = exp.valid & ok
        mult = jnp.where(valid, exp.mult, 0)
        gen_c = jax.ops.segment_sum(
            mult.sum(1).astype(I64), crow, num_segments=self.C
        )
        abort_c = (
            jax.ops.segment_sum(
                (exp.abort & live).astype(I64), crow, num_segments=self.C
            )
            > 0
        )
        B = live.shape[0]
        vflat = valid.reshape(-1)
        salt_flat = jnp.repeat(salt_of_row, K)
        fps = jnp.where(
            vflat, exp.fp_view.reshape(-1) ^ salt_flat, jnp.uint64(SENT)
        )
        keys = exp.fp_full.reshape(-1)  # unsalted: intra-group tie-break
        pays = jnp.arange(B * K, dtype=I64)
        slab2, fresh, _n, ovf = hashstore.probe_and_insert_impl(
            slab, fps, keys, pays
        )
        new_c = jax.ops.segment_sum(
            fresh.astype(I64), jnp.repeat(crow, K), num_segments=self.C
        )
        return slab2, fresh, fps, gen_c, new_c, abort_c, ovf

    def _mat_step(self, st, rows, slots, n_g):
        """Materialize the level's survivors into the next frontier and
        scan the configured invariants over them in the same program."""
        parents = jax.tree.map(lambda x: x[rows], st)
        children = self.kern.materialize(parents, slots)
        in_range = jnp.arange(rows.shape[0], dtype=I64) < n_g
        bad = (~self._inv_ok(children)) & in_range
        return children, bad

    def _fused_level(self, st, live, crow, mr_row, salt_row, slab,
                     done_c, g_cap: int):
        """One whole bucket level as ONE device program: the step body,
        the survivor-lane compaction the host used to do with
        ``np.nonzero`` (cumsum + trash-slot scatter, lane order
        preserved — identical to the host's ascending-lane selection),
        the materialize and the invariant scan.  ``done_c`` carries the
        pre-level retirement flags so lanes of configs that abort THIS
        level (or were already done) are dropped exactly as the host
        filter dropped them; padding lanes resolve to (row 0, slot 0)
        like the host's zero-filled ``rows_p``/``slots_p``, keeping the
        padded children bit-identical between the paths.  On
        ``ovf_g`` (more survivors than ``g_cap``) the host redoes with
        the exact capacity from the control fetch."""
        (slab2, fresh, fps, gen_c, new_c, abort_c,
         ovf) = self._level_step(st, live, crow, mr_row, salt_row, slab)
        K = self.K
        B = live.shape[0]
        lane_cfg = jnp.repeat(crow, K)
        keep = fresh & ~(done_c[lane_cfg] | abort_c[lane_cfg])
        n_g = keep.sum().astype(I64)
        dest = jnp.cumsum(keep) - 1
        tgt = jnp.where(keep, dest, g_cap)
        lanes_pad = jnp.zeros((g_cap,), I64).at[tgt].set(
            jnp.arange(B * K, dtype=I64), mode="drop"
        )
        rows = lanes_pad // K
        slots = lanes_pad % K
        children, bad = self._mat_step(
            st, rows, slots, jnp.minimum(n_g, g_cap)
        )
        return (slab2, children, bad, rows, fresh, fps, gen_c, new_c,
                abort_c, ovf, n_g > g_cap, n_g)

    def _superstep(self, st, live, crow, mr_row, salt_row, slab,
                   done_c, depth_c, cap_c, g_cap: int, span: int,
                   ring: int):
        """Up to ``span`` whole bucket levels as ONE device program:
        a ``lax.while_loop`` around ``_fused_level`` with per-config
        retirement resident on device — depth caps retire members at
        the top of each level (the engine's break-BEFORE-expanding
        order), aborts and fixpoints retire them at the bottom — and
        each level's per-config ledgers (new/gen/abort counts, the
        inserted-fps ring for the slab-rebuild source) spooled into
        preallocated meta arrays the host unpacks from ONE fetch.

        Commit discipline mirrors engine/superstep.py: a level commits
        only when fully clean (no slab overflow, no g_cap overflow, no
        invariant violation anywhere in the bucket, ring fits);
        anything else stops the loop uncommitted and the host replays
        that level through the per-level fused path.  ``cap_c`` holds
        per-config depth caps (-1 = none).  Returns the carried state
        (next frontier/live/crow at ``g_cap``, slab, done, depth), the
        control scalars (levels committed, reason, ring offset) and
        the per-level meta + ring arrays."""
        K = self.K
        C = self.C
        B = live.shape[0]
        R = ring
        RUN = graft_superstep.REASON_RUN
        STOP = graft_superstep.REASON_STOP
        RING = graft_superstep.REASON_RING
        FIX = graft_superstep.REASON_FIX
        if B < g_cap:
            # seat the input batch in the span-wide frontier buffer
            # (dead rows: live is False there, crow 0 — the staged
            # path's zero-fill convention)
            st = jax.tree.map(
                lambda x: jnp.concatenate(
                    [x, jnp.zeros((g_cap - B,) + x.shape[1:], x.dtype)]
                ),
                st,
            )
            live = jnp.concatenate(
                [live, jnp.zeros((g_cap - B,), bool)]
            )
            crow = jnp.concatenate(
                [crow, jnp.zeros((g_cap - B,), crow.dtype)]
            )

        def cond(c):
            lvl, reason = c[0], c[2]
            return (reason == RUN) & (lvl < span)

        def body(c):
            (lvl, off, _reason, st, live, crow, slab, done, depth,
             rf, m_new, m_gen, m_abort, m_ins, m_ng) = c
            # top-of-level: depth-cap retirement (BEFORE expanding)
            capped = (cap_c >= 0) & (depth >= cap_c) & ~done
            done1 = done | capped
            live1 = live & ~done1[crow]
            (slab2, children, bad, rows, fresh, fps, gen_c, new_c,
             abort_c, ovf, ovfg, n_g) = self._fused_level(
                st, live1, crow, mr_row, salt_row, slab, done1,
                g_cap=g_cap,
            )
            n_ins = fresh.sum().astype(I64)
            ring_ovf = off + n_ins > R
            stop = ovf | ovfg | bad.any()
            commit = (~stop) & (~ring_ovf)
            # ring append of the inserted (salted) fps, lane-ascending
            # — the same order the host's np.nonzero selection pins
            dest = jnp.cumsum(fresh) - 1
            tgt = jnp.where(fresh, off + dest, jnp.asarray(R, I64))
            rf = rf.at[tgt].set(fps, mode="drop")
            m_new = jax.lax.dynamic_update_slice(
                m_new, new_c[None, :], (lvl, jnp.zeros((), I32))
            )
            m_gen = jax.lax.dynamic_update_slice(
                m_gen, gen_c[None, :], (lvl, jnp.zeros((), I32))
            )
            m_abort = jax.lax.dynamic_update_slice(
                m_abort, abort_c[None, :], (lvl, jnp.zeros((), I32))
            )
            m_ins = m_ins.at[lvl].set(n_ins)
            m_ng = m_ng.at[lvl].set(n_g)
            # bottom-of-level retirement: aborts, then fixpoints
            alive = ~done1
            done2 = (done1 | (alive & abort_c)
                     | (alive & ~abort_c & (new_c == 0)))
            depth2 = depth + (
                alive & ~abort_c & (new_c > 0)
            ).astype(I64)
            live_new = jnp.arange(g_cap, dtype=I64) < n_g
            crow_new = jnp.where(live_new, crow[rows], 0)
            ended = done2.all() | (n_g == 0)
            fix = commit & ended
            reason2 = jnp.where(
                stop, STOP,
                jnp.where(ring_ovf, RING, jnp.where(fix, FIX, RUN)),
            ).astype(I32)
            sel = lambda a, b: jnp.where(commit, a, b)  # noqa: E731
            return (
                lvl + commit.astype(I32),
                off + jnp.where(commit, n_ins, 0),
                reason2,
                jax.tree.map(sel, children, st),
                sel(live_new, live),
                sel(crow_new, crow),
                sel(slab2, slab),
                sel(done2, done),
                sel(depth2, depth),
                rf, m_new, m_gen, m_abort, m_ins, m_ng,
            )

        init = (
            jnp.zeros((), I32),
            jnp.zeros((), I64),
            jnp.full((), RUN, I32),
            st, live, crow.astype(I64), slab,
            done_c, depth_c,
            jnp.full((R,), jnp.uint64(SENT), jnp.uint64),
            jnp.zeros((span, self.C), I64),
            jnp.zeros((span, self.C), I64),
            jnp.zeros((span, self.C), bool),
            jnp.zeros((span,), I64),
            jnp.zeros((span,), I64),
        )
        (lvl, off, reason, st, live, crow, slab, done, depth, rf,
         m_new, m_gen, m_abort, m_ins, m_ng) = jax.lax.while_loop(
            cond, body, init
        )
        ctrl = jnp.stack([lvl.astype(I64), reason.astype(I64), off])
        return (st, live, crow, slab, done, depth, ctrl, m_new, m_gen,
                m_abort, m_ins, m_ng, rf)

    # -- cold-path helpers -------------------------------------------------

    def bad_invariant_name(self, children: RaftState, idx: int) -> str:
        """Which invariant a known-bad state violates (cold path,
        mirrors engine/bfs._bad_invariant_name)."""
        one = jax.tree.map(lambda x: x[idx: idx + 1], children)
        for name, fn in self.inv_fns:
            ok = jax.device_get(fn(self.kcfg, one, self.kern.tables))
            if not bool(np.asarray(ok)[0]):
                return name
        return self.inv_fns[0][0]

    def note_shapes(self, tag: str, *shapes) -> None:
        self.shape_keys.add((tag,) + shapes)


@functools.lru_cache(maxsize=32)
def _get_programs(kcfg: RaftConfig, mxu: bool, C: int) -> BucketPrograms:
    return BucketPrograms(kcfg, mxu, C)


class BatchedChecker:
    """One bucket run: N same-key configs checked as one device stream.

    Parameters:
      cfgs: the bucket members — every ``bucket_key(cfg)`` must match.
      max_depths: optional per-config depth caps (None = fixpoint).
      use_mxu: expand-kernel selector, as in JaxChecker.
      progress: optional callable(stats dict) per level.

    ``run(checkpoint_dir=...)`` commits a rolling ``bstate.npz`` bucket
    snapshot through the atomic manifest writer after every level, and
    resumes from it when the directory holds a digest-verified record
    of the SAME job set (run-config fingerprint match) — a SIGKILL'd
    bucket resumes rather than restarts.  Returns one summary dict per
    config in the ``check.py --json`` schema.
    """

    def __init__(
        self,
        cfgs: list[RaftConfig],
        max_depths: list[int | None] | None = None,
        use_mxu: bool | None = None,
        megakernel: bool | None = None,
        superstep: int | None = None,
        progress=None,
    ):
        if not cfgs:
            raise ValueError("empty bucket")
        self.cfgs = list(cfgs)
        self.C = len(self.cfgs)
        key = bucket_key(self.cfgs[0])
        for c in self.cfgs[1:]:
            if bucket_key(c) != key:
                raise ValueError(
                    f"bucket mixes shape keys: {bucket_key(c)} != {key}"
                )
        self.kcfg = dataclasses.replace(
            key, max_restart=max(c.max_restart for c in self.cfgs)
        )
        if use_mxu is None:
            use_mxu = mxu_enabled_by_env()
        # fused bucket levels (one program + one fetch per level) ride
        # the same lever as the engine megakernel: TLA_RAFT_MEGAKERNEL
        if megakernel is None:
            megakernel = graft_megakernel.enabled_by_env()
        self.megakernel = bool(megakernel)
        # multi-level bucket supersteps ride the engine's span lever
        # (TLA_RAFT_SUPERSTEP / --superstep); need the fused path
        if superstep is None:
            superstep = graft_superstep.span_from_env()
        self.superstep_span = (
            max(1, int(superstep)) if self.megakernel else 1
        )
        self.C_pad = max(2, forecast.pow2ceil(self.C))
        self.progs = _get_programs(self.kcfg, bool(use_mxu), self.C_pad)
        self.kern = self.progs.kern
        self.use_mxu = self.kern.use_mxu
        self.K = self.kern.K
        self.max_depths = list(max_depths or [None] * self.C)
        if len(self.max_depths) != self.C:
            raise ValueError("max_depths length mismatch")
        self.progress = progress
        self.salts = config_salts(self.C_pad)
        mr = [c.max_restart for c in self.cfgs]
        self._mr = np.asarray(
            mr + [0] * (self.C_pad - self.C), np.int32
        )
        # run identity for the bucket checkpoint: the job SET (bucket
        # key + each member's (mr, depth cap) in slot order) — a
        # different set must never adopt this bucket's snapshot
        self._run_fp = resilience.run_config_fingerprint(
            self.kcfg,
            engine="service.bucket/1",
            jobs=tuple(
                (int(m), -1 if d is None else int(d))
                for m, d in zip(mr, self.max_depths)
            ),
            mxu=self.use_mxu,
        )
        # stats for the bench record
        self.stats = dict(
            levels=0, dispatches=0, programs=0, redos=0,
            supersteps=0, superstep_levels=0, slab_presizes=0,
        )

    # -- slab management ---------------------------------------------------

    def _fresh_slab(self, entries: int):
        cap = max(
            hashstore.MIN_CAP,
            forecast.pow2ceil(hashstore.slab_rows(max(entries, 1), 0.25)),
        )
        return jnp.asarray(
            np.full((cap,), SENT, np.uint64)
        ), cap

    def _rebuild_slab(self, all_fps: list[np.ndarray], cap: int):
        fps = (
            np.concatenate(all_fps)
            if all_fps else np.zeros((0,), np.uint64)
        )
        while cap < 4 * max(len(fps), 1):
            cap *= 2
        slab_np = np.full((cap,), SENT, np.uint64)
        slab_np = hashstore.insert_np(slab_np, fps)
        return jnp.asarray(slab_np), cap

    # -- checkpointing -----------------------------------------------------

    def _save_bstate(self, ckdir, lvl, st_np, live, crow,
                     all_fps, gen, depth, level_sizes, done, results):
        arrays = {f"st_{f}": st_np[f] for f in _STATE_FIELDS}
        maxlv = max(len(ls) for ls in level_sizes)
        ls_pad = np.full((self.C, maxlv), -1, np.int64)
        for i, ls in enumerate(level_sizes):
            ls_pad[i, : len(ls)] = ls
        # results are JSON-safe summary dicts (or None for running)
        res_blob = json.dumps(results)
        arrays.update(
            lvl=np.int64(lvl),
            live=live,
            crow=crow,
            all_fps=np.concatenate(all_fps)
            if all_fps else np.zeros((0,), np.uint64),
            gen=gen,
            depth=depth,
            level_sizes=ls_pad,
            done=done,
            results=np.frombuffer(res_blob.encode(), np.uint8),
            run_fp=np.frombuffer(self._run_fp.encode(), np.uint8),
        )
        name = BSTATE_FMT.format(int(lvl))
        resilience.commit_npz(
            ckdir, name, arrays, kind="bstate", depth=int(lvl),
            run_fp=self._run_fp,
        )
        # keep the latest two records (the previous one is the fallback
        # if the newest turns out torn on the next resume); sweep older
        import glob as _glob

        old = sorted(_glob.glob(os.path.join(ckdir, BSTATE_GLOB)))[:-2]
        if old:
            m = resilience.Manifest.load(ckdir)
            for p in old:
                try:
                    os.unlink(p)
                except OSError:
                    pass
                m.forget(os.path.basename(p))
            m.commit()

    @staticmethod
    def _read_bstate(path):
        try:
            with np.load(path) as z:
                return {k: z[k] for k in z.files}
        except (OSError, ValueError, KeyError, EOFError):
            return None

    def _load_bstate(self, ckdir):
        """Newest healable bucket snapshot, or None (fresh start).

        Heal-first resume, the engine's delta-log policy shaped to the
        snapshot log: newest record first — a digest-verified record of
        this job set is used as-is; a structurally-valid UNMANIFESTED
        record of this job set (the rename-beat-manifest crash window)
        is ADOPTED into the ledger and used; anything torn, corrupt or
        belonging to another job set is quarantined and the walk falls
        back to the next-older record."""
        import glob as _glob

        resilience.sweep_tmp(ckdir)
        names = sorted(
            os.path.basename(p)
            for p in _glob.glob(os.path.join(ckdir, BSTATE_GLOB))
        )
        m = resilience.Manifest.load(ckdir)
        dirty = False
        out = None
        for name in reversed(names):
            status = m.verify(name)
            data = self._read_bstate(os.path.join(ckdir, name))
            fp = (
                bytes(data["run_fp"]).decode()
                if data is not None and "run_fp" in data else None
            )
            if fp != self._run_fp:
                resilience.quarantine(
                    ckdir, name,
                    "bstate unreadable" if data is None
                    else "bstate from another job set", m,
                )
                dirty = True
                continue
            if status == "ok":
                out = data
                break
            if status == "unmanifested":
                if dirty:  # flush quarantine edits before adopt reloads
                    m.commit()
                    dirty = False
                resilience.adopt_file(
                    ckdir, name, kind="bstate", depth=int(data["lvl"]),
                    run_fp=self._run_fp,
                )
                out = data
                break
            resilience.quarantine(ckdir, name, f"bstate {status}", m)
            dirty = True
        if dirty:
            m.commit()
        return out

    # -- the run -----------------------------------------------------------

    def run(self, checkpoint_dir: str | None = None) -> list[dict]:
        t0 = time.monotonic()
        C, C_pad, K = self.C, self.C_pad, self.K
        progs = self.progs
        # programs = the DELTA of traces this run added: the program
        # cache is lru-shared across bucket runs of one key, so the
        # cumulative ledger would double-count reuse (the whole point
        # of sharing) as fresh compilation
        progs_before = len(progs.shape_keys)
        if checkpoint_dir:
            resilience.sweep_tmp(checkpoint_dir)

        results: list[dict | None] = [None] * C
        done = np.zeros(C, bool)
        gen = np.zeros(C, np.int64)
        depth = np.zeros(C, np.int64)
        level_sizes: list[list[int]] = [[1] for _ in range(C)]

        def finish(c, ok, kind=None):
            done[c] = True
            # bucket-member retirement into the flight recorder: the
            # service timeline shows WHEN each tenant config stopped
            # (fixpoint / depth cap / violation) inside the shared
            # dispatch stream
            graft_obs.retire(c, bool(ok), int(depth[c]), kind)
            results[c] = dict(
                ok=bool(ok),
                distinct=int(sum(level_sizes[c])),
                generated=int(gen[c]),
                depth=int(depth[c]),
                level_sizes=[int(x) for x in level_sizes[c]],
                mxu=self.use_mxu,
                superstep=self.superstep_span,
                seconds=round(time.monotonic() - t0, 3),
                violation=kind,
                batched=True,
                bucket_configs=C,
            )

        # ---- init level (or bucket-snapshot resume) ----------------------
        ck = self._load_bstate(checkpoint_dir) if checkpoint_dir else None
        if ck is not None:
            lvl = int(ck["lvl"])
            live_h = np.asarray(ck["live"], bool)
            crow_h = np.asarray(ck["crow"], np.int64)
            gen = np.asarray(ck["gen"], np.int64).copy()
            depth = np.asarray(ck["depth"], np.int64).copy()
            done = np.asarray(ck["done"], bool).copy()
            ls_pad = np.asarray(ck["level_sizes"])
            level_sizes = [
                [int(x) for x in row[row >= 0]] for row in ls_pad
            ]
            res_list = json.loads(bytes(ck["results"]).decode())
            for i, r in enumerate(res_list):
                if r is not None:
                    results[i] = r
            all_fps = [np.asarray(ck["all_fps"], np.uint64)]
            st = RaftState(
                **{
                    f: jnp.asarray(ck[f"st_{f}"])
                    for f in _STATE_FIELDS
                }
            )
            slab, _cap = self._rebuild_slab(
                all_fps, hashstore.MIN_CAP
            )
        else:
            lvl = 0
            st1 = init_batch(self.kcfg, 1)
            fv0, _ff0, _ms = progs.fpr.state_fingerprints(st1)
            fp0 = np.asarray(jax.device_get(fv0)).astype(np.uint64)[0]
            salted0 = (fp0 ^ self.salts[:C]).astype(np.uint64)
            all_fps = [salted0]
            slab, _cap = self._fresh_slab(64 * C)
            slab_np = np.asarray(jax.device_get(slab))
            slab_np = hashstore.insert_np(slab_np, salted0)
            slab = jnp.asarray(slab_np)
            # invariant check on Init (all members share the state)
            ok0 = bool(
                np.asarray(jax.device_get(progs.inv_ok(st1)))[0]
            )
            if not ok0:
                name = progs.bad_invariant_name(st1, 0)
                for c in range(C):
                    finish(c, False, f"Invariant {name} is violated")
                return [r for r in results if r is not None]
            B0 = max(8, forecast.pow2ceil(C))
            st = init_batch(self.kcfg, B0)
            live_h = np.arange(B0) < C
            crow_h = np.minimum(np.arange(B0), C - 1).astype(np.int64)

        mr_dev = jnp.asarray(self._mr)
        salt_dev = jnp.asarray(self.salts)
        # bucket-aggregate per-level new-state totals: the forecast
        # signal that presizes the frontier capacity ahead of growth
        # (engine/forecast.py), so the bucket compiles one program per
        # forecast magnitude instead of one per pow2 step it crawls
        # through
        level_totals = [
            int(sum(ls[i] for ls in level_sizes if len(ls) > i))
            for i in range(max(len(ls) for ls in level_sizes))
        ]
        g_floor = 8  # frontier-capacity ratchet (grows only: one
        # program per magnitude, never a shrink retrace)
        last_n_g = 8  # previous level's survivor count: the fused
        # path's pre-dispatch g_cap signal before the forecast warms
        # per-config depth caps as a device vector (-1 = fixpoint run)
        cap_pad = np.asarray(
            [-1 if d is None else int(d) for d in self.max_depths]
            + [-1] * (C_pad - C),
            np.int64,
        )
        # a stopped superstep (uncommitted overflow/violation level)
        # routes that level through the per-level path exactly once
        skip_ss = False

        # ---- level loop --------------------------------------------------
        while True:
            # chaos site: a `kill` here dies mid-bucket with the bstate
            # snapshot behind it; a `pause` zombifies the worker between
            # level commits (resilience/faults.py, service/chaos.py)
            resilience.faults.fire("bucket.level")
            # retire members that reached their depth cap (the engine
            # breaks BEFORE expanding at max_depth — same here)
            for c in range(C):
                if (
                    not done[c]
                    and self.max_depths[c] is not None
                    and depth[c] >= self.max_depths[c]
                ):
                    finish(c, True)
                    live_h = live_h & (crow_h != c)
            if done.all() or not live_h.any():
                for c in range(C):
                    if not done[c]:  # frontier drained externally
                        finish(c, True)
                break

            B = int(live_h.shape[0])
            live = jnp.asarray(live_h)
            crow = jnp.asarray(crow_h)
            # ---- multi-level superstep: up to N bucket levels in ONE
            # program + ONE fetch (engine/superstep.py, service slice).
            # Per-config retirement runs resident; the per-level
            # ledgers replay below in exactly the staged order --------
            if self.megakernel and self.superstep_span > 1 and not skip_ss:
                span = self.superstep_span
                g_cap = max(g_floor, forecast.pow2ceil(last_n_g), B)
                if len(level_totals) > forecast.MIN_LEVELS:
                    peak = forecast.forecast_peak_new(level_totals, None)
                    peak = min(
                        max(peak, 1), 4 * max(last_n_g, 8), 1 << 20
                    )
                    g_cap = max(g_cap, forecast.pow2ceil(peak))
                ring = forecast.pow2ceil(2 * span * g_cap)
                # presize the slab for the WHOLE span's inserts (the
                # engine path's hstore.reserve()): a mid-span probe-
                # window fill stops the window uncommitted and replays
                # per-level, so every slab growth step would otherwise
                # cost one wasted span-N dispatch — eroding the 1/N
                # amortization on exactly the growing levels that need
                # it.  Same content, bigger capacity: dedup semantics
                # and per-config counts are unchanged.
                n_led = sum(len(a) for a in all_fps)
                need = hashstore.slab_rows(n_led + span * g_cap, 0.25)
                if need > int(slab.shape[0]):
                    self.stats["slab_presizes"] += 1
                    slab, _cap = self._rebuild_slab(all_fps, need)
                done_pad = np.concatenate(
                    [done, np.ones(C_pad - C, bool)]
                )
                depth_pad = np.concatenate(
                    [depth, np.zeros(C_pad - C, np.int64)]
                )
                progs.note_shapes(
                    "sstep", B, int(slab.shape[0]), g_cap, span, ring
                )
                graft_sanitize.superstep_begin()
                done_dev = jnp.asarray(done_pad)
                depth_dev = jnp.asarray(depth_pad)
                cap_dev = jnp.asarray(cap_pad)
                # device-cost observatory: harvest the bucket
                # superstep's XLA cost/memory ledger once per shape
                # (compile-time only; see analysis/devprof.py)
                graft_devprof.profile_program(
                    "service.superstep", progs.sstep,
                    st, live, crow, mr_dev, salt_dev, slab,
                    done_dev, depth_dev, cap_dev,
                    statics=dict(g_cap=g_cap, span=span, ring=ring),
                )
                (st2, live2_d, crow2_d, slab2, done2_d, depth2_d,
                 ctrl_d, mnew_d, mgen_d, mabort_d, mins_d, mng_d,
                 rf_d) = progs.sstep(
                    st, live, crow, mr_dev, salt_dev, slab,
                    done_dev, depth_dev, cap_dev,
                    g_cap=g_cap, span=span, ring=ring,
                )
                self.stats["dispatches"] += 1
                graft_sanitize.note_dispatch("service.superstep")
                (ctrl, m_new, m_gen, m_abort, m_ins, m_ng, rf_h,
                 live2, crow2) = jax.device_get((
                    ctrl_d, mnew_d, mgen_d, mabort_d, mins_d, mng_d,
                    rf_d, live2_d, crow2_d,
                ))
                levels_done = int(ctrl[0])
                reason = graft_superstep.REASON_NAMES.get(
                    int(ctrl[1]), "stop"
                )
                graft_sanitize.superstep_tick(levels_done)
                self.stats["supersteps"] += 1
                self.stats["superstep_levels"] += levels_done
                self.stats["levels"] += levels_done
                lvl_before = lvl
                off = 0
                for i in range(levels_done):
                    # replay one committed level's bookkeeping in the
                    # staged order: depth-cap retirement, aborts, gen,
                    # fps ledger, fixpoints/level_sizes, totals
                    for c in range(C):
                        if (
                            not done[c]
                            and self.max_depths[c] is not None
                            and depth[c] >= self.max_depths[c]
                        ):
                            finish(c, True)
                    active = ~done
                    for c in range(C):
                        if active[c] and bool(m_abort[i][c]):
                            finish(
                                c, False,
                                'Assert "split brain" (Raft.tla:185)',
                            )
                    for c in range(C):
                        if not done[c]:
                            gen[c] += int(m_gen[i][c])
                    n_ins = int(m_ins[i])
                    if n_ins:
                        all_fps.append(
                            np.asarray(
                                rf_h[off:off + n_ins], np.uint64
                            )
                        )
                    off += n_ins
                    for c in range(C):
                        if done[c]:
                            continue
                        n_new = int(m_new[i][c])
                        if n_new == 0:
                            finish(c, True)
                        else:
                            level_sizes[c].append(n_new)
                            depth[c] += 1
                    level_totals.append(
                        int(sum(int(x) for x in m_new[i][:C]))
                    )
                    last_n_g = int(m_ng[i])
                    lvl += 1
                    graft_obs.level_commit(
                        lvl, level_totals[-1],
                        int(sum(sum(ls) for ls in level_sizes)),
                        int(gen.sum()),
                    )
                    if self.progress is not None:
                        self.progress(
                            dict(
                                level=lvl,
                                frontier=last_n_g,
                                configs_alive=int((~done).sum()),
                                distinct=int(
                                    sum(sum(ls) for ls in level_sizes)
                                ),
                                generated=int(gen.sum()),
                                elapsed=time.monotonic() - t0,
                            )
                        )
                g_floor = max(g_floor, g_cap)
                st = st2
                slab = slab2
                live_h = np.asarray(live2, bool)
                crow_h = np.asarray(crow2, np.int64)
                if reason == "stop" or (
                    reason == "ring" and levels_done == 0
                ):
                    skip_ss = True
                if checkpoint_dir and lvl > lvl_before:
                    n_led = sum(len(a) for a in all_fps)
                    every = 1 if 8 * n_led <= (1 << 24) else 8
                    if (lvl // every) > (lvl_before // every):
                        st_np = {
                            f: np.asarray(
                                jax.device_get(getattr(st, f))
                            )
                            for f in _STATE_FIELDS
                        }
                        self._save_bstate(
                            checkpoint_dir, lvl, st_np, live_h,
                            crow_h, all_fps, gen, depth, level_sizes,
                            done, results,
                        )
                continue
            skip_ss = False
            children = bad_h = rows_h = n_g_dev = None
            if self.megakernel:
                # ---- fused bucket level: ONE program + ONE fetch ----
                # g_cap (the survivor-lane capacity) must be static
                # BEFORE the dispatch: ratchet floor + forecast, with
                # the exact count from the control fetch driving the
                # rare redo (the engine megakernel's cap_out shape)
                done_pad = np.concatenate(
                    [done, np.ones(C_pad - C, bool)]
                )
                g_cap = max(g_floor, forecast.pow2ceil(last_n_g))
                if len(level_totals) > forecast.MIN_LEVELS:
                    peak = forecast.forecast_peak_new(level_totals, None)
                    peak = min(
                        max(peak, 1), 4 * max(last_n_g, 8), 1 << 20
                    )
                    g_cap = max(g_cap, forecast.pow2ceil(peak))
                while True:  # slab / g_cap redo loop (engine-shaped)
                    progs.note_shapes(
                        "fused", B, int(slab.shape[0]), g_cap
                    )
                    done_dev = jnp.asarray(done_pad)
                    # device-cost observatory (see the sstep site)
                    graft_devprof.profile_program(
                        "service.fused", progs.fused,
                        st, live, crow, mr_dev, salt_dev, slab,
                        done_dev,
                        statics=dict(g_cap=g_cap),
                    )
                    (slab2, children, bad_d, rows_d, fresh_d, fps_d,
                     gen_d, new_d, abort_d, ovf_d, ovfg_d,
                     n_g_dev) = progs.fused(
                        st, live, crow, mr_dev, salt_dev, slab,
                        done_dev, g_cap=g_cap,
                    )
                    (fresh_h, fps_h, gen_c, new_c, abort_c, ovf, ovf_g,
                     n_g_fused, bad_h, rows_h) = jax.device_get((
                        fresh_d, fps_d, gen_d, new_d, abort_d, ovf_d,
                        ovfg_d, n_g_dev, bad_d, rows_d,
                    ))
                    self.stats["dispatches"] += 1
                    graft_sanitize.note_dispatch("service.fused")
                    if bool(ovf):
                        # probe-window overflow: rebuild a bigger slab
                        # from the inserted-fps ledger and redo (the
                        # pending slab2 is discarded — functional)
                        self.stats["redos"] += 1
                        slab, _cap = self._rebuild_slab(
                            all_fps, 2 * int(slab.shape[0])
                        )
                        continue
                    if bool(ovf_g):
                        # exact survivor count is in the control fetch:
                        # one redo lands the capacity
                        self.stats["redos"] += 1
                        g_cap = max(
                            2 * g_cap, forecast.pow2ceil(int(n_g_fused))
                        )
                        continue
                    slab = slab2
                    break
                G_cap = g_floor = g_cap
                bad_h = np.asarray(bad_h)
                rows_h = np.asarray(rows_h, np.int64)
            else:
                while True:  # slab-overflow redo loop (engine-shaped)
                    progs.note_shapes("step", B, int(slab.shape[0]))
                    out = progs.step(
                        st, live, crow, mr_dev, salt_dev, slab
                    )
                    (slab2, fresh_d, fps_d, gen_d, new_d, abort_d,
                     ovf_d) = out
                    fresh_h, fps_h, gen_c, new_c, abort_c, ovf = (
                        jax.device_get(
                            (fresh_d, fps_d, gen_d, new_d, abort_d, ovf_d)
                        )
                    )
                    self.stats["dispatches"] += 1
                    graft_sanitize.note_dispatch("service.step")
                    if not bool(ovf):
                        slab = slab2
                        break
                    # probe-window overflow: rebuild a bigger slab from
                    # the inserted-fps ledger and redo the level (the
                    # pending slab2 is discarded — kernels are
                    # functional)
                    self.stats["redos"] += 1
                    slab, _cap = self._rebuild_slab(
                        all_fps, 2 * int(slab.shape[0])
                    )
            self.stats["levels"] += 1

            # abort (in-kernel Assert) fires BEFORE the level is
            # counted, like the engine's abort_at return
            active = ~done
            for c in range(C):
                if active[c] and bool(abort_c[c]):
                    finish(
                        c, False, 'Assert "split brain" (Raft.tla:185)'
                    )
                    live_h = live_h & (crow_h != c)
            for c in range(C):
                if not done[c]:
                    gen[c] += int(gen_c[c])

            if not self.megakernel:
                lanes = np.nonzero(fresh_h)[0]
                lane_cfg = crow_h[lanes // K]
                keep = ~done[lane_cfg]
                lanes = lanes[keep]
                lane_cfg = lane_cfg[keep]
            if len(fps_h):
                # ledger of every inserted fp (slab rebuild source) —
                # includes retired members' lanes already in the slab
                ins = np.nonzero(fresh_h)[0]
                all_fps.append(fps_h[ins].astype(np.uint64))

            for c in range(C):
                if done[c]:
                    continue
                n_new = int(new_c[c])
                if n_new == 0:
                    finish(c, True)  # fixpoint: gen counted, depth kept
                    live_h = live_h & (crow_h != c)
                else:
                    level_sizes[c].append(n_new)
                    depth[c] += 1

            if self.megakernel:
                # survivor selection already ran on device (identical
                # keep-mask semantics: fresh & ~done & ~abort, lane
                # order ascending); rows beyond n_g are 0-filled like
                # the staged ``rows_p``
                n_g = int(n_g_fused)
                rows = rows_h[:n_g]
            else:
                lanes = lanes[~done[lane_cfg]]
                n_g = len(lanes)
            if n_g == 0:
                for c in range(C):
                    if not done[c]:
                        finish(c, True)
                break

            level_totals.append(int(sum(int(x) for x in new_c[:C])))
            if not self.megakernel:
                rows = (lanes // K).astype(np.int64)
                slots = (lanes % K).astype(np.int64)
            crow_next = crow_h[rows]
            if not self.megakernel:
                G_cap = max(g_floor, forecast.pow2ceil(n_g))
                if len(level_totals) > forecast.MIN_LEVELS:
                    # presize ONE magnitude ahead when the forecast says
                    # growth continues: saves the next pow2 retrace
                    # without inflating the padded per-level compute (a
                    # wide cap was measured 3x slower on CPU — dead
                    # padded lanes are not free)
                    peak = forecast.forecast_peak_new(level_totals, None)
                    peak = min(max(peak, n_g), 2 * max(n_g, 1), 1 << 20)
                    G_cap = max(G_cap, forecast.pow2ceil(peak))
                g_floor = G_cap
                rows_p = np.zeros(G_cap, np.int64)
                rows_p[:n_g] = rows
                slots_p = np.zeros(G_cap, np.int64)
                slots_p[:n_g] = slots
                progs.note_shapes("mat", B, G_cap)
                children, bad_d = progs.mat(
                    st, jnp.asarray(rows_p), jnp.asarray(slots_p),
                    jnp.asarray(n_g, I64),
                )
                bad_h = np.asarray(jax.device_get(bad_d))
                self.stats["dispatches"] += 1
                graft_sanitize.note_dispatch("service.mat")
            last_n_g = n_g
            lvl += 1

            graft_obs.level_commit(
                lvl, int(sum(int(x) for x in new_c[:C])),
                int(sum(sum(ls) for ls in level_sizes)),
                int(gen.sum()),
            )
            if self.progress is not None:
                self.progress(
                    dict(
                        level=lvl,
                        frontier=n_g,
                        configs_alive=int((~done).sum()),
                        distinct=int(sum(sum(ls) for ls in level_sizes)),
                        generated=int(gen.sum()),
                        elapsed=time.monotonic() - t0,
                    )
                )

            crow_pad = np.zeros(G_cap, np.int64)
            crow_pad[:n_g] = crow_next
            live_next = np.zeros(G_cap, bool)
            live_next[:n_g] = True
            # invariant violations: counted level, then fail (engine
            # order: bookkeeping -> bad check); first bad lane per
            # config in lane order decides the reported invariant
            if bad_h.any():
                for i in np.nonzero(bad_h[:n_g])[0]:
                    c = int(crow_pad[i])
                    if done[c]:
                        continue
                    name = progs.bad_invariant_name(children, int(i))
                    finish(c, False, f"Invariant {name} is violated")
                live_next = live_next & ~done[crow_pad]
            st = children
            live_h = live_next
            crow_h = crow_pad

            if checkpoint_dir:
                # size-aware cadence: the snapshot rewrites the WHOLE
                # cumulative fps ledger + frontier, so past ~2M entries
                # a per-level dump would re-add an O(|visited|) level
                # tail — snapshot every 8th level there (a crash then
                # redoes at most 7 levels from the previous record)
                n_led = sum(len(a) for a in all_fps)
                every = 1 if 8 * n_led <= (1 << 24) else 8
                if lvl % every == 0:
                    st_np = {
                        f: np.asarray(jax.device_get(getattr(st, f)))
                        for f in _STATE_FIELDS
                    }
                    self._save_bstate(
                        checkpoint_dir, lvl, st_np, live_h, crow_h,
                        all_fps, gen, depth, level_sizes, done, results,
                    )

        self.stats["programs"] = len(progs.shape_keys) - progs_before
        out = [r for r in results if r is not None]
        assert len(out) == C
        return out
