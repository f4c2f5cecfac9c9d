"""MXU-native successor hot path: guard matmul + gather-free materialize.

The dense pass-1 expand (ops/dense_expand.py) already carries the
*fingerprint* algebra on factored matmuls, but two per-lane paths
survived it on the hot loop:

* **guard truth** — the static (message-independent) half of every
  action guard was evaluated family by family as broadcast compares;
  the scalar reference (and the materialize trace below it) still
  reads state through per-lane ``_get1``/``_get2``/``_bit_get``
  data-indexed accesses, the round-2 gather cliff (docs/PERF.md).
* **materialize** — pass 2 ran as ``lax.switch`` over twelve scalar
  action branches vmapped per lane: ~32 data-indexed gathers and a
  scatter per lowered kernel (the ledgered ``successor.materialize``
  histogram), all on the VPU.

This module re-derives both as batched small-matrix ops over packed
state blocks, the BLEST / "Graph Traversal on Tensor Cores" move
(PAPERS.md) applied to guard evaluation and field updates:

* :class:`MXUTables` precomputes, at trace-construction time, the
  per-action coefficient tables (extending ops/successor.GuardTables):
  a 0/1 **guard coefficient matrix** ``W [feat, K]`` + threshold
  ``theta [K]`` such that the static guard conjunction of every slot
  holds iff ``(phi @ W)[b, k] == theta[k]`` for the packed per-state
  predicate block ``phi [B, feat]`` — guard truth across the whole
  action family is ONE ``[lanes, feat] x [feat, actions]`` matmul plus
  a threshold compare, no per-lane indexed reads; and the per-slot
  **update constant block** ``BIG [K, X]`` (family/server/witness
  one-hots, precomputed message-id bases, log-rewrite select rows)
  fetched for a lane batch by a single one-hot contraction
  ``oh [G, K] @ BIG`` — a select-matrix product, not a gather.

* :class:`MXUExpand` routes the two kernels:

  - ``guards``: static matmul & the message-dependent guard terms
    (``DenseExpand.msg_guard_parts`` — contiguous-column counts over
    the mixed-radix message blocks, and each guarded bit selected by
    its flat offset in the pair block, computed from the lane's own
    fields);
  - ``materialize``/``materialize_added``: every field update of every
    family expressed as masked row/rank-1 updates over the packed
    block (``new = old + onehot * delta`` style selects), combined by
    the disjoint family masks — the dynamic ``.at[...]``-equivalent
    select class and the ``lax.switch`` both gone.

Bit-exactness contract: each family body below is a term-for-term
transcription of the scalar action in ops/successor.py (same clips,
same cast points, same encoder arithmetic), so (valid, mult, abort)
and the materialized children are bit-identical to the legacy kernels
on EVERY input, not just reachable states — tests/test_mxu_expand.py
diffs both kernels directly and the engines end to end.

Selection: default ON (``TLA_RAFT_MXU=0`` / ``--no-mxu-expand``
reverts); the legacy kernels stay jitted alongside for A/B.
"""

from __future__ import annotations

import os

import numpy as np

import jax
import jax.numpy as jnp

from ..config import CANDIDATE, FOLLOWER, LEADER

I32 = jnp.int32
F32 = jnp.float32
U32 = jnp.uint32


def mxu_enabled_by_env() -> bool:
    """MXU expand default: ON; ``TLA_RAFT_MXU=0`` reverts to legacy."""
    return os.environ.get("TLA_RAFT_MXU", "1") != "0"


def _pair(a0, b0, S: int) -> int:
    """(src-1, dst-1) -> the src-major pair digit (msg_universe layout)."""
    return a0 * (S - 1) + (b0 - (1 if b0 > a0 else 0))


def _rank_select_median(x, median_index: int):
    """Median(F) (Raft.tla:70-75) over the trailing axis, no sort op:
    the stable ascending-sort position of element u is #(x_w < x_u) +
    #(w < u with x_w == x_u); select the element whose position is the
    median index.  ONE implementation for both MXU sites (the guard
    predicate bank and the F10 materialize) — the parity contract with
    the scalar kernel requires the copies to stay bit-identical."""
    S = x.shape[-1]
    xu = x[..., :, None]
    xw = x[..., None, :]
    tri = (jnp.arange(S)[:, None] > jnp.arange(S)[None, :]).astype(I32)
    pos = (xw < xu).sum(-1, dtype=I32) + ((xw == xu) * tri).sum(-1, dtype=I32)
    return (x * (pos == median_index)).sum(-1, dtype=I32)


class MXUTables:
    """Per-action coefficient tables for the MXU expand (trace-time).

    Two table groups, both indexed by the global slot id (the
    family-order witness-grid raveling of SuccessorKernel.families):

    * guard coefficients: ``W [feat, K]`` (0/1, float32 so the product
      runs on the MXU; counts are tiny integers, exact in f32),
      ``theta [K]`` and the static slot mask ``slot_ok [K]`` (the
      ``not_self`` witness cuts, which are compile-time constants);
    * update constants: ``BIG [K, X]`` int32 — one matrix whose named
      column groups hold every per-slot constant the materialize pass
      needs (family/server/coord one-hots, message-id bases with the
      pair digit folded in, the FollowerAcceptEntry log-rewrite select
      rows).  A lane batch fetches all of it with one
      ``oh [G, K] @ BIG`` contraction.
    """

    # predicate block layout of phi (see MXUExpand._guard_features);
    # widths are filled in per config at construction
    _BLOCKS = (
        "roleF", "roleC", "roleL", "roleFC", "has_term", "ec", "rc",
        "tgt", "vs0", "llL", "pend0", "pend1", "nille", "vfok",
        "plill", "oksucc", "okfail", "medgt",
    )

    def __init__(self, cfg, uni, families, slot_family, slot_coords):
        S, T, L, V = cfg.S, cfg.T, cfg.L, cfg.V
        E = uni.n_entry
        NPLI = uni.ap_npli
        A = max(S - 1, 1)
        K = int(slot_family.shape[0])
        self.K, self.A = K, A
        fam = slot_family
        c = slot_coords
        legacy_ae = "legacy-append" in cfg.mutations
        double_vote = "double-vote" in cfg.mutations

        # ---- guard coefficient matrix ----------------------------------
        widths = dict(
            roleF=S, roleC=S, roleL=S, roleFC=S, has_term=S, ec=1, rc=1,
            tgt=S * T, vs0=V, llL=S, pend0=S * S, pend1=S * S,
            nille=S * S, vfok=S * S, plill=S * L,
            oksucc=S * S * L, okfail=S * S * L, medgt=S,
        )
        off, acc = {}, 0
        for name in self._BLOCKS:
            off[name] = acc
            acc += widths[name]
        self.n_feat = acc
        W = np.zeros((acc, K), np.float32)
        theta = np.zeros((K,), np.float32)
        ok = np.ones((K,), bool)

        def req(k, name, idx=0):
            W[off[name] + idx, k] += 1.0
            theta[k] += 1.0

        for k in range(K):
            f = int(fam[k])
            s = int(c[k, 0])
            if f == 0:  # BecomeCandidate: ec < MaxElection, role in {F, C}
                req(k, "roleFC", s)
                req(k, "ec")
            elif f == 1:  # UpdateTerm (a): t > currentTerm[s]
                req(k, "tgt", s * T + int(c[k, 1]))
            elif f == 2:  # UpdateTerm (b): Candidate with a term
                req(k, "roleC", s)
                req(k, "has_term", s)
            elif f == 3:  # ResponseVote(s, cand)
                cand = int(c[k, 1])
                req(k, "roleF", s)
                req(k, "has_term", s)
                if not double_vote:  # votedFor free-or-matching guard
                    req(k, "vfok", s * S + cand)
                ok[k] = cand != s
            elif f == 4:  # BecomeLeader: the vote count is message-side
                req(k, "roleC", s)
            elif f == 5:  # ClientReq(s, v)
                req(k, "roleL", s)
                req(k, "vs0", int(c[k, 1]))
                req(k, "llL", s)
            elif f == 6:  # LeaderAppendEntry(s, d)
                d = int(c[k, 1])
                req(k, "roleL", s)
                req(k, "pend0", s * S + d)
                req(k, "nille", s * S + d)
                ok[k] = d != s
            elif f == 7:  # FollowerAcceptEntry(s, src, pli, e, lc)
                src = int(c[k, 1])
                req(k, "roleF", s)
                req(k, "has_term", s)
                req(k, "plill", s * L + int(c[k, 2]))
                ok[k] = src != s
            elif f == 8:  # FollowerRejectEntry(s, src, pli)
                src = int(c[k, 1])
                req(k, "roleF", s)
                req(k, "has_term", s)
                ok[k] = src != s
            elif f == 9:  # HandleAppendResp(s, src, pli, succ)
                src, l0, x = int(c[k, 1]), int(c[k, 2]), int(c[k, 3])
                req(k, "roleL", s)
                req(k, "has_term", s)
                req(k, "pend1", s * S + src)
                req(k, "oksucc" if x == 1 else "okfail",
                    (s * S + src) * L + l0)
                ok[k] = src != s
            elif f == 10:  # LeaderCanCommit: median > commitIndex
                req(k, "roleL", s)
                req(k, "medgt", s)
            else:  # Restart
                req(k, "roleL", s)
                req(k, "rc")

        self.feat_off = off
        self.W = jnp.asarray(W)
        self.theta = jnp.asarray(theta)
        self.slot_ok = jnp.asarray(ok)

        # ---- per-slot update constants (BIG) ---------------------------
        cols: list[tuple[str, np.ndarray]] = []

        def col(name, arr):
            arr = np.asarray(arr, np.int32)
            if arr.ndim == 1:
                arr = arr[:, None]
            cols.append((name, arr))

        NF = len(families)
        col("fam", (fam[:, None] == np.arange(NF)).astype(np.int32))
        col("oh_s", (c[:, 0:1] == np.arange(S)).astype(np.int32))
        # c1 as the second-server digit (cand / d / src), zero elsewhere
        is_pairfam = np.isin(fam, (3, 6, 7, 8, 9))
        oh_d = (c[:, 1:2] == np.arange(S)) & is_pairfam[:, None]
        col("oh_d", oh_d.astype(np.int32))
        # ClientReq value digit one-hot (zero rows off-family, so the
        # val_sent update needs no extra family mask)
        col("oh_v", ((c[:, 1:2] == np.arange(V)) & (fam[:, None] == 5)
                     ).astype(np.int32))
        col("s_idx", c[:, 0])
        col("t1", np.where(fam == 1, c[:, 1] + 1, 0))
        col("cand1", np.where(fam == 3, c[:, 1] + 1, 0))
        col("v5p1", np.where(fam == 5, c[:, 1] + 1, 0))
        pli9 = np.where(fam == 9, c[:, 2] + 1, 0)
        col("pli9", pli9)
        col("sc9", np.where(fam == 9, c[:, 3], 0))

        # message-id bases: the pair digit (and every other per-slot
        # constant digit) folded into one int at table-build time, so the
        # kernel's id arithmetic is base + the data-dependent digits only
        grant = np.zeros(K, np.int64)
        aq6 = np.zeros(K, np.int64)
        apc7 = np.zeros(K, np.int64)
        apc8 = np.zeros(K, np.int64)
        peer = np.zeros((K, A), np.int64)
        pli7 = np.where(fam == 7, c[:, 2] + 1, 0)
        e7 = np.where(fam == 7, c[:, 3], 0)
        lc7 = np.where(fam == 7, c[:, 4] + 1, 0)
        el7 = (e7 > 0).astype(np.int64)
        eterm7 = np.where(e7 > 0, (e7 - 1) // V + 1, 0)
        eval7 = np.where(e7 > 0, (e7 - 1) % V + 1, 0)
        nl7 = pli7 + el7
        minlc7 = np.minimum(lc7, nl7)
        vq_stride = T * L * T
        aq_stride = T * L * (T + 1) * E * L
        ap_pair_stride = T * NPLI * 2
        for k in range(K):
            f = int(fam[k])
            s = int(c[k, 0])
            if f == 0:
                for r in range(A):
                    p0 = (s + 1 + r) % S if S > 1 else 0
                    pr = _pair(s, p0, S) if S > 1 else 0
                    peer[k, r] = uni.vq_off + pr * vq_stride
            elif f == 3:
                grant[k] = uni.vp_off + _pair(s, int(c[k, 1]), S) * T
            elif f == 6:
                aq6[k] = uni.aq_off + _pair(s, int(c[k, 1]), S) * aq_stride
            elif f == 7:
                rpli = int(np.clip(pli7[k] + el7[k], 1, L))
                apc7[k] = (uni.ap_off
                           + _pair(s, int(c[k, 1]), S) * ap_pair_stride
                           + (rpli - uni.ap_pli_min) * 2 + 1)
            elif f == 8:
                # the dead FollowerAppendEntry's reject carries
                # prevLogIndex - 1 (Raft.tla:364) vs the live :314's value
                rej_pli = int(c[k, 2]) + (0 if legacy_ae else 1)
                apc8[k] = (uni.ap_off
                           + _pair(s, int(c[k, 1]), S) * ap_pair_stride
                           + (rej_pli - uni.ap_pli_min) * 2)
        col("grant_base", grant)
        col("aq_base6", aq6)
        col("apc7", apc7)
        col("apc8", apc8)
        col("peer_base", peer)
        col("pli7", pli7)
        col("el7", el7)
        col("eterm7", eterm7)
        col("eval7", eval7)
        col("nl7", nl7)
        col("minlc7", minlc7)
        # FollowerAcceptEntry log-rewrite select rows (constants of the
        # slot's pli/e witness): keep = j < pli, the carried-entry slot,
        # and the conflict-read position one-hot
        ar = np.arange(L)
        keep7 = (ar[None, :] < pli7[:, None]).astype(np.int32)
        pos7 = np.minimum(pli7, L - 1)  # 0-based carried-entry slot
        posoh7 = (ar[None, :] == pos7[:, None]).astype(np.int32)
        ate7 = posoh7 * (el7[:, None] == 1) * (fam[:, None] == 7)
        col("keep7", keep7 * (fam[:, None] == 7))
        col("posoh7", posoh7 * (fam[:, None] == 7))
        col("ate7", ate7.astype(np.int32))

        offc, acc = {}, 0
        parts = []
        for name, arr in cols:
            offc[name] = slice(acc, acc + arr.shape[1])
            acc += arr.shape[1]
            parts.append(arr)
        self.col_off = offc
        self.BIG = jnp.asarray(np.concatenate(parts, axis=1))  # [K, X]


class MXUExpand:
    """The MXU-factored successor kernels for one SuccessorKernel.

    Holds only references (cfg, universe, DenseExpand for the message-
    side guard terms) plus the coefficient tables; the owning
    SuccessorKernel jits ``guards``/``materialize``/``materialize_added``
    and keeps the legacy kernels alongside for A/B.
    """

    def __init__(self, kern):
        self.cfg = kern.cfg
        self.uni = kern.uni
        self.dense = kern.dense
        self.K = kern.K
        self.A = kern.A
        self.tables = MXUTables(
            kern.cfg, kern.uni, kern.families, kern.slot_family,
            kern.slot_coords,
        )

    # ---- pass 1: guards as one matmul + threshold -----------------------

    def _guard_features(self, st):
        """The packed static predicate block phi f32[B, feat].

        Block order/layout is MXUTables._BLOCKS; every entry is a 0/1
        predicate of the state alone (the message-dependent guard terms
        stay on DenseExpand.msg_guard_parts).  The LeaderCanCommit
        median is the one irreducibly data-indexed read left; it is
        computed lane-exactly (the S^2 rank-select grid, no sort) and
        enters the bank as a plain predicate.
        """
        cfg = self.cfg
        S, T, L, V = cfg.S, cfg.T, cfg.L, cfg.V
        i32 = lambda x: x.astype(I32)
        role = i32(st.role)
        ct = i32(st.current_term)
        vf = i32(st.voted_for)
        ll = i32(st.log_len)
        mi = i32(st.match_index)
        ni = i32(st.next_index)
        ci = i32(st.commit_index)
        pend = i32(st.pending)
        vs = i32(st.val_sent)
        B = role.shape[0]
        t_ax = jnp.arange(1, T + 1, dtype=I32)
        pli_ax = jnp.arange(1, L + 1, dtype=I32)

        # Median(matchIndex[s]) rank-select (ops/successor.py F10)
        med = _rank_select_median(mi, cfg.median_index)

        blocks = [
            role == FOLLOWER,
            role == CANDIDATE,
            role == LEADER,
            (role == FOLLOWER) | (role == CANDIDATE),
            ct >= 1,
            (i32(st.election_count) < cfg.max_election)[:, None],
            (i32(st.restart_count) < cfg.max_restart)[:, None],
            (t_ax[None, None, :] > ct[:, :, None]).reshape(B, S * T),
            vs == 0,
            ll < L,
            (pend == 0).reshape(B, S * S),
            (pend == 1).reshape(B, S * S),
            (ni <= ll[:, :, None] + 1).reshape(B, S * S),
            ((vf[:, :, None] == 0)
             | (vf[:, :, None] == jnp.arange(1, S + 1, dtype=I32))
             ).reshape(B, S * S),
            (pli_ax[None, None, :] <= ll[:, :, None]).reshape(B, S * L),
            (mi[:, :, :, None] < pli_ax).reshape(B, S * S * L),
            ((pli_ax + 1 == ni[:, :, :, None])
             & (pli_ax > mi[:, :, :, None])).reshape(B, S * S * L),
            med > ci,
        ]
        return jnp.concatenate(
            [b.astype(F32) for b in blocks], axis=1
        )

    def guards(self, st):
        """(valid bool[B,K], mult i32[B,K] unmasked, abort bool[B]).

        ``phi @ W == theta`` resolves every static guard conjunction in
        one [B, feat] x [feat, K] MXU matmul (counts are tiny integers,
        exact in f32).  The message-side terms
        (``DenseExpand.msg_guard_parts``) sum contiguous columns of the
        message blocks and select each guarded bit by its mixed-radix
        flat offset in the pair block (``iota == offset``, a masked
        sum): no gather and no one-hot contraction.  Bit-identical to
        the legacy decomposition: the two factors partition exactly the
        conjuncts of each scalar guard.
        """
        t = self.tables
        # the name groups the guard pass's device ops in a profile
        with jax.named_scope("raft.guards"):
            msg_ok, mult, abort = self.dense.msg_guard_parts(st)
            phi = self._guard_features(st)
            cnt = jnp.einsum("bf,fk->bk", phi, t.W)
            static_ok = (cnt == t.theta[None, :]) & t.slot_ok[None, :]
            return static_ok & msg_ok, mult, abort

    # ---- pass 2: materialize as select-matrix products ------------------

    def materialize_added(self, st, slots):
        """Children + sent message ids for G (parent, slot) lanes.

        One ``oh [G, K] @ BIG`` contraction fetches every per-slot
        constant; per-lane state reads are one-hot contractions against
        the packed block ([G, S] x [G, S, ...] reductions — batched
        matvecs); field updates are masked row/rank-1 selects combined
        under the mutually-exclusive family masks.  No lax.switch, no
        data-indexed gather, no scatter.
        """
        cfg, uni = self.cfg, self.uni
        t = self.tables
        S, T, L, V = cfg.S, cfg.T, cfg.L, cfg.V
        E = uni.n_entry
        NPLI = uni.ap_npli
        K, A = self.K, self.A
        i32 = lambda x: x.astype(I32)

        oh = (slots[:, None].astype(I32)
              == jnp.arange(K, dtype=I32)[None, :]).astype(I32)  # [G, K]
        lane = jnp.einsum("gk,kx->gx", oh, t.BIG)  # ONE constant fetch

        def colv(name):
            v = lane[:, t.col_off[name]]
            return v[:, 0] if v.shape[1] == 1 else v

        famm = colv("fam")  # [G, NF]
        f = [famm[:, i] > 0 for i in range(famm.shape[1])]
        os_ = colv("oh_s")  # [G, S]
        osb = os_ > 0
        od = colv("oh_d")
        odb = od > 0
        ar_L = jnp.arange(L, dtype=I32)[None, :]

        ct = i32(st.current_term)
        vf = i32(st.voted_for)
        ll = i32(st.log_len)
        ci = i32(st.commit_index)
        lt = i32(st.log_term)
        lv = i32(st.log_val)
        mi = i32(st.match_index)
        ni = i32(st.next_index)
        pend = i32(st.pending)
        role = i32(st.role)

        ct_s = jnp.einsum("gs,gs->g", os_, ct)
        vf_s = jnp.einsum("gs,gs->g", os_, vf)
        role_s = jnp.einsum("gs,gs->g", os_, role)
        ll_s = jnp.einsum("gs,gs->g", os_, ll)
        ci_s = jnp.einsum("gs,gs->g", os_, ci)
        lt_row = jnp.einsum("gs,gsl->gl", os_, lt)
        lv_row = jnp.einsum("gs,gsl->gl", os_, lv)
        mi_row = jnp.einsum("gs,gsu->gu", os_, mi)
        ni_row = jnp.einsum("gs,gsu->gu", os_, ni)
        pend_row = jnp.einsum("gs,gsu->gu", os_, pend)
        mi_sd = jnp.einsum("gu,gu->g", od, mi_row)
        ni_sd = jnp.einsum("gu,gu->g", od, ni_row)

        # -- F0 BecomeCandidate ------------------------------------------
        new_term0 = jnp.clip(ct_s + 1, 1, T)
        llt0 = jnp.clip(
            ((ar_L == jnp.clip(ll_s - 1, 0, None)[:, None]) * lt_row
             ).sum(-1, dtype=I32),
            0, T - 1,
        )
        rest0 = ((new_term0 - 1) * L + (ll_s - 1)) * T + llt0
        peer_ids = colv("peer_base").reshape(-1, A) + rest0[:, None]

        # -- F1/F2/F3 -----------------------------------------------------
        t1 = colv("t1")
        if "become-follower" in cfg.mutations:
            # FollowerUpdateTerm keeps votedFor (Raft.tla:192-197)
            nvf1 = jnp.where(role_s == FOLLOWER, vf_s, 0)
        else:
            nvf1 = jnp.zeros_like(vf_s)
        cand1 = colv("cand1")
        grant3 = colv("grant_base") + jnp.clip(ct_s, 1, None) - 1

        # -- F4 BecomeLeader ---------------------------------------------
        row4_mi = jnp.where(osb, ll_s[:, None], 1)
        row4_ni = jnp.broadcast_to((ll_s + 1)[:, None], row4_mi.shape)

        # -- F5 ClientReq -------------------------------------------------
        at_w = ar_L == jnp.clip(ll_s, 0, L - 1)[:, None]
        row5_lt = jnp.where(at_w, ct_s[:, None], lt_row)
        row5_lv = jnp.where(at_w, colv("v5p1")[:, None], lv_row)
        row5_mi = jnp.where(osb, (ll_s + 1)[:, None], mi_row)

        # -- F6 LeaderAppendEntry ----------------------------------------
        pli6 = jnp.clip(ni_sd - 1, 1, L)
        prev_oh = ar_L == jnp.clip(ni_sd - 2, 0, L - 1)[:, None]
        plt6 = jnp.clip((prev_oh * lt_row).sum(-1, dtype=I32), 0, T)
        has_e = ni_sd <= ll_s
        epos_oh = ar_L == jnp.clip(ni_sd - 1, 0, L - 1)[:, None]
        et6 = jnp.clip((epos_oh * lt_row).sum(-1, dtype=I32), 1, T)
        ev6 = jnp.clip((epos_oh * lv_row).sum(-1, dtype=I32), 1, V)
        ecode6 = jnp.where(has_e, 1 + (et6 - 1) * V + (ev6 - 1), 0)
        mid6 = colv("aq_base6") + (
            ((((jnp.clip(ct_s, 1, T) - 1) * L + (pli6 - 1)) * (T + 1)
              + plt6) * E + ecode6) * L + (ci_s - 1)
        )
        row6_pend = jnp.where(odb, 1, pend_row)

        # -- F7 FollowerAcceptEntry --------------------------------------
        pli7 = colv("pli7")
        el7 = colv("el7")
        eterm7 = colv("eterm7")
        eval7 = colv("eval7")
        nl7 = colv("nl7")
        keep7 = colv("keep7")
        posoh7 = colv("posoh7")
        ate7 = colv("ate7")
        append_new = nl7 > ll_s
        conflict = (
            (el7 == 1)
            & (pli7 < ll_s)
            & (((posoh7 * lt_row).sum(-1, dtype=I32) != eterm7)
               | ((posoh7 * lv_row).sum(-1, dtype=I32) != eval7))
        )
        updated7 = append_new | conflict
        new_lt7 = jnp.where(ate7 > 0, eterm7[:, None],
                            jnp.where(keep7 > 0, lt_row, 0))
        new_lv7 = jnp.where(ate7 > 0, eval7[:, None],
                            jnp.where(keep7 > 0, lv_row, 0))
        row7_lt = jnp.where(updated7[:, None], new_lt7, lt_row)
        row7_lv = jnp.where(updated7[:, None], new_lv7, lv_row)
        ll7 = jnp.where(updated7, nl7, ll_s)
        ci7 = jnp.maximum(ci_s, colv("minlc7"))
        resp7 = colv("apc7") + (jnp.clip(ct_s, 1, T) - 1) * (NPLI * 2)

        # -- F8 FollowerRejectEntry (no state change) --------------------
        rej8 = colv("apc8") + (jnp.clip(ct_s, 1, T) - 1) * (NPLI * 2)

        # -- F9 HandleAppendResp -----------------------------------------
        pli9 = colv("pli9")
        sc9 = colv("sc9")
        row9_mi = jnp.where(odb, jnp.where(sc9 == 1, pli9, mi_sd)[:, None],
                            mi_row)
        row9_ni = jnp.where(odb, (pli9 + sc9)[:, None], ni_row)
        row9_pend = jnp.where(odb, 0, pend_row)

        # -- F10 LeaderCanCommit (rank-select median) --------------------
        med10 = _rank_select_median(mi_row, cfg.median_index)

        # -- combine: masked selects under the disjoint family masks -----
        def set1(field, mask, val):
            """field[:, s] := val where mask — the _set1 select, batched."""
            return jnp.where(
                (mask[:, None] & osb), val[:, None].astype(field.dtype),
                field,
            )

        def set_row(field, mask, row):
            return jnp.where(
                (mask[:, None] & osb)[:, :, None],
                row[:, None, :].astype(field.dtype), field,
            )

        vf_val = jnp.where(f[0], colv("s_idx") + 1,
                           jnp.where(f[1], nvf1, cand1))
        voted_for = set1(st.voted_for, f[0] | f[1] | f[3], vf_val)
        ct_val = jnp.where(f[0], new_term0, t1)
        current_term = set1(st.current_term, f[0] | f[1], ct_val)
        role_val = jnp.where(
            f[0], CANDIDATE, jnp.where(f[4], LEADER, FOLLOWER)
        ) * jnp.ones_like(ct_s)
        role_new = set1(st.role, f[0] | f[1] | f[2] | f[4] | f[11], role_val)
        lt_new = set_row(st.log_term, f[5] | f[7],
                         jnp.where(f[5][:, None], row5_lt, row7_lt))
        lv_new = set_row(st.log_val, f[5] | f[7],
                         jnp.where(f[5][:, None], row5_lv, row7_lv))
        ll_new = set1(st.log_len, f[5] | f[7],
                      jnp.where(f[5], ll_s + 1, ll7))
        mi_new = set_row(
            st.match_index, f[4] | f[5] | f[9],
            jnp.where(f[4][:, None], row4_mi,
                      jnp.where(f[5][:, None], row5_mi, row9_mi)),
        )
        ni_new = set_row(st.next_index, f[4] | f[9],
                         jnp.where(f[4][:, None], row4_ni, row9_ni))
        pend_new = set_row(
            st.pending, f[4] | f[6] | f[9],
            jnp.where(f[4][:, None], jnp.zeros_like(pend_row),
                      jnp.where(f[6][:, None], row6_pend, row9_pend)),
        )
        ci_new = set1(st.commit_index, f[7] | f[10],
                      jnp.where(f[7], ci7, med10))
        ec_new = jnp.where(f[0], st.election_count + jnp.uint8(1),
                           st.election_count)
        rc_new = jnp.where(f[11], st.restart_count + jnp.uint8(1),
                           st.restart_count)
        ovs = colv("oh_v").reshape(-1, V)  # zero rows off-family
        vs_new = jnp.where(ovs > 0, jnp.uint8(1), st.val_sent)

        # -- added message ids + the SendMsg bit-OR (Raft.tla:43-45) -----
        a0 = jnp.where(
            f[0], peer_ids[:, 0],
            jnp.where(f[3], grant3,
                      jnp.where(f[6], mid6,
                                jnp.where(f[7], resp7,
                                          jnp.where(f[8], rej8, -1)))),
        )
        addcols = [a0.astype(I32)]
        for r in range(1, A):
            addcols.append(
                jnp.where(f[0], peer_ids[:, r], -1).astype(I32)
            )
        added = jnp.stack(addcols, axis=1)  # [G, A]

        msgs = st.msgs
        n_words = msgs.shape[1]
        for a in range(A):
            mid = added[:, a]
            live = mid >= 0
            w = jnp.clip(mid, 0, None) >> 5
            bit = jnp.where(live, U32(1) << (mid & 31).astype(U32), U32(0))
            word_hit = jnp.arange(n_words, dtype=I32)[None, :] == w[:, None]
            msgs = jnp.where(word_hit, msgs | bit[:, None], msgs)

        child = st._replace(
            voted_for=voted_for,
            current_term=current_term,
            role=role_new,
            log_term=lt_new,
            log_val=lv_new,
            log_len=ll_new,
            match_index=mi_new,
            next_index=ni_new,
            commit_index=ci_new,
            election_count=ec_new,
            restart_count=rc_new,
            pending=pend_new,
            val_sent=vs_new,
            msgs=msgs,
        )
        return child, added

    def materialize(self, st, slots):
        return self.materialize_added(st, slots)[0]
