"""Tensorized successor fan-out: ``Next`` as dense block algebra, no gathers.

The scalar-per-witness formulation in ops/successor.py (action functions
vmap'd over a coordinate grid) is semantically exact but maps poorly onto
the TPU backend: every ``x[s]`` / table-row read inside the vmap is a
data-indexed gather, and a launched program containing gathers pays a
fixed multi-millisecond penalty on this platform (measured — see
docs/PERF.md), putting expand at ~40 us/state.

This module re-derives pass 1 (validity, multiplicity, child
fingerprints, split-brain abort) in fully dense form:

* **witness digits are array axes** — per-server state reads are
  axis-aligned broadcasts, never gathers;
* **the message set is viewed as mixed-radix blocks** — static reshapes
  of the unpacked bit vector (``[B, pair, term, ...]`` per message type),
  so guard existence/counting is reductions plus tiny one-hot
  contractions over the data-dependent digits (term, prevLogTerm, ...);
  ``msg_guard_parts``, the MXU guard pass's half, selects those digits
  by flat offset instead;
* **fingerprints are incremental** — ``h(child) = h(parent) +
  sum C_eff[changed] * delta`` over the byte-plane-linearized
  multilinear hash (ops/fingerprint.py); added-message coefficients are
  computed arithmetically (``mix32`` + the pair-digit permutation trick,
  ops/msg_universe.py) — no per-candidate feature extraction, no
  coefficient table.

Slot layout (family order, witness-grid raveling) is IDENTICAL to
SuccessorKernel.families, so payloads, traces, coverage accounting and
the materialize pass are unchanged.  tests/test_dense_expand.py asserts
bit-exact equality of (valid, mult, fp_view, fp_full, abort) against the
scalar kernel on reachable states.

Spec citations live with the scalar transcriptions in ops/successor.py
(Raft.tla:107-414); this file implements the same guarded effects.
"""

from __future__ import annotations

import numpy as np

import jax.numpy as jnp

from ..config import CANDIDATE, FOLLOWER, LEADER, RaftConfig
from .fingerprint import Fingerprinter, _effective_u32
from .msg_universe import MsgUniverse, _dst_from_idx

I32 = jnp.int32
U32 = jnp.uint32


def _oh(x, n):
    """One-hot over a tiny digit range; i32 for exact integer contraction."""
    return (x[..., None] == jnp.arange(n, dtype=x.dtype)).astype(I32)


def _flat(*digits):
    """Mixed-radix offset of ``(digit, radix)`` pairs, most significant
    first; -1 where any digit lies outside ``0..radix-1``."""
    off, ok = 0, True
    for d, r in digits:
        off = off * r + d
        ok = ok & (d >= 0) & (d < r)
    return jnp.where(ok, off, -1)


def _pick(blk, off):
    """``blk[b, n, off[b, n, ...]]`` by ``iota == off`` and a masked sum.

    blk [B, N, R, *U], off i32[B, N, *O] -> i32[B, N, *O, *U].  No gather:
    every offset compares against the R positions.  An offset outside
    0..R-1 picks 0, as a one-hot of an out-of-range digit does."""
    R, no, nu = blk.shape[2], off.ndim - 2, blk.ndim - 3
    hit = off[..., None] == jnp.arange(R, dtype=off.dtype)
    hit = hit.reshape(hit.shape + (1,) * nu)
    blk = blk.reshape(blk.shape[:2] + (1,) * no + blk.shape[2:])
    return jnp.where(hit, blk, 0).sum(2 + no, dtype=I32)


class DenseExpand:
    """Dense pass-1 expand for one RaftConfig.

    Constructed by SuccessorKernel; shares the fingerprinter (coefficient
    tables, seed) and the message universe (layout constants)."""

    def __init__(self, cfg: RaftConfig, uni: MsgUniverse, fpr: Fingerprinter):
        self.cfg = cfg
        self.uni = uni
        self.fpr = fpr
        S, T, L, V = cfg.S, cfg.T, cfg.L, cfg.V
        E = uni.n_entry
        NP = S * (S - 1)
        self.S, self.T, self.L, self.V, self.E, self.NP = S, T, L, V, E, NP
        P, NC = fpr.P, fpr.N_CHAN

        # ---- pair-digit constants ---------------------------------------
        pair_of = np.zeros((S, S), np.int64)  # (a0, b0) -> pair digit a->b
        for src in range(1, S + 1):
            for di in range(S - 1):
                dst = _dst_from_idx(src, di)
                pair_of[src - 1, dst - 1] = (src - 1) * (S - 1) + di
        self._pair_of = pair_of
        # SELP[a, b, p]: one-hot of pair(a->b); zero row on the diagonal
        selp = np.zeros((S, S, NP), np.int64)
        for a in range(S):
            for b in range(S):
                if a != b:
                    selp[a, b, pair_of[a, b]] = 1
        self.SELP = jnp.asarray(selp, I32)
        # SELD[b, p]: pairs delivering TO b (sum over sources)
        self.SELD = jnp.asarray(selp.sum(0), I32)
        self.PPERM = uni.pair_perm_table.astype(np.int64)  # [P, NP] host

        # ResponseVote up-to-date qualifier (Raft.tla:145-147):
        # QUAL[llt, lli0, myllt, mylli0] = llt > myllt \/ (= /\ lli >= mylli)
        q = np.zeros((T, L, T + 1, L), np.int64)
        for k in range(T):
            for l0 in range(L):
                for m in range(T + 1):
                    for j0 in range(L):
                        q[k, l0, m, j0] = int((k > m) or (k == m and l0 >= j0))
        self.QUAL = jnp.asarray(q, I32)

        # ---- effective feature-coefficient blocks -----------------------
        ceff = _effective_u32(fpr._Cp_np).transpose(2, 0, 1)  # [F, P, chan]
        sp = fpr.spec

        def cf(slice_, *shape):
            return jnp.asarray(
                ceff[slice_].reshape(*shape, P, NC), jnp.uint32
            )

        self.C_ct = cf(sp.ct, S)
        self.C_role = cf(sp.role, S)
        self.C_lt = cf(sp.lt, S, L)
        self.C_lv = cf(sp.lv, S, L)
        self.C_ll = cf(sp.ll, S)
        self.C_mi = cf(sp.mi, S, S)
        self.C_ni = cf(sp.ni, S, S)
        self.C_ci = cf(sp.ci, S)
        self.C_vf = cf(sp.vf_oh, S, S + 1)
        self.C_ec = cf(sp.ec, 1)[0]  # [P, chan]
        self.C_rc = cf(sp.rc, 1)[0]
        self.C_pend = cf(sp.pend, S, S)
        self.C_vs = cf(sp.vs, V)
        cvf = np.asarray(self.C_vf)
        self.C_vf_self = jnp.asarray(
            np.stack([cvf[s, s + 1] for s in range(S)]), jnp.uint32
        )  # votedFor[s] := s+1
        cmi = np.asarray(self.C_mi)
        self.C_mi_diag = jnp.asarray(
            np.stack([cmi[s, s] for s in range(S)]), jnp.uint32
        )

        # FollowerAcceptEntry witness constants over (pli0=l, e, lc0=h)
        EL = np.array([0] + [1] * (E - 1), np.int64)  # entry carried?
        ETERM = np.array(
            [0] + [(e - 1) // V + 1 for e in range(1, E)], np.int64
        )
        EVAL = np.array([0] + [(e - 1) % V + 1 for e in range(1, E)], np.int64)
        NL = (np.arange(L)[:, None] + 1) + EL[None, :]  # new_len [l, e]
        PI = np.minimum(NL, L)  # resp prevLogIndex [l, e]
        self.EL = jnp.asarray(EL, I32)
        self.ETERM = jnp.asarray(ETERM, I32)
        self.EVAL = jnp.asarray(EVAL, I32)
        self.NL = jnp.asarray(NL, I32)
        self.MINLC = jnp.asarray(
            np.minimum(np.arange(1, L + 1)[None, None, :], NL[:, :, None]),
            I32,
        )  # min(lc, new_len) [l, e, h]
        # keep/at-entry masks for the log rewrite [j, l] / [j, l, e]
        jj, ll_ = np.meshgrid(np.arange(L), np.arange(L), indexing="ij")
        KEEP = (jj <= ll_).astype(np.int64)  # j < pli  (j0 <= l)
        POS = np.minimum(np.arange(L) + 1, L - 1)  # entry slot per l
        AT = np.zeros((L, L, E), np.int64)
        for l0 in range(L):
            AT[POS[l0], l0, 1:] = 1
        self.KEEPX = jnp.asarray(KEEP[:, :, None] * (1 - AT) , I32)  # [j, l, e]
        self.AT = jnp.asarray(AT, I32)
        self.PI = jnp.asarray(PI, I32)

        # BecomeCandidate peers (s -> the S-1 others, broadcast order)
        if S > 1:
            peers = np.stack(
                [[(s + 1 + r) % S for r in range(S - 1)] for s in range(S)]
            )
            self._pair_peers = pair_of[np.arange(S)[:, None], peers]  # [S, S-1]
            selpeer = np.zeros((S, S - 1, NP), np.int64)
            for s in range(S):
                for r in range(S - 1):
                    selpeer[s, r, self._pair_peers[s, r]] = 1
            self.SELPEER = jnp.asarray(selpeer, I32)
        self._pair_ab = pair_of  # [a, b] np (diagonal entries unused)

    # ---- added-message hash contribution --------------------------------

    def _add_msg(self, pair_const: np.ndarray, type_idx: int, rest, live):
        """One added message per lane: pair_const np[*axes], rest i32[B,*axes],
        live i32[B,*axes] (1 = actually added).  u32[B, *axes, P, chan]."""
        off = self.uni.type_offsets[type_idx]
        stride = self.uni.type_strides[type_idx]
        pp = np.moveaxis(self.PPERM[:, pair_const], 0, -1)  # [*axes, P]
        id_p = jnp.asarray(off + pp * stride, I32) + rest[..., None]
        g = self.fpr.msg_coef_eff(id_p)
        return jnp.where(live[..., None, None] != 0, g, U32(0))

    # ---- message-side guard terms (the MXU split) -----------------------

    def _pairs(self, x):
        """[B, S, S, ...] -> [B, NP, ...]: x[b, a, c] at pair digit a->c.

        The pair digit is the row-major rank of (a, c) off the diagonal,
        so dropping the diagonal is a static slice of the flat grid."""
        S, B, rest = self.S, x.shape[0], x.shape[3:]
        x = x.reshape(B, S * S, *rest)[:, 1:]
        x = x.reshape(B, S - 1, S + 1, *rest)[:, :, :S]
        return x.reshape(B, self.NP, *rest)

    def _grid(self, xp):
        """[B, NP, ...] -> [B, S(src), S(dst), ...], zero on the diagonal
        (the inverse of ``_pairs``: static pads and reshapes)."""
        S, B, rest = self.S, xp.shape[0], xp.shape[2:]
        pad = [(0, 0)] * len(rest)
        x = xp.reshape(B, S - 1, S, *rest)
        x = jnp.pad(x, [(0, 0), (0, 0), (0, 1)] + pad)
        x = jnp.pad(x.reshape(B, S * S - 1, *rest), [(0, 0), (1, 0)] + pad)
        return x.reshape(B, S, S, *rest)

    def msg_guard_parts(self, st):
        """(msg_ok bool[B,K], mult i32[B,K], abort bool[B]).

        The message-dependent half of every guard, the same values as
        ``__call__``'s terms at every lane.  The static (message-
        independent) half lives in ops/mxu_expand.py as the guard
        coefficient matmul; the two factors partition exactly the
        conjuncts of each scalar guard in ops/successor.py, so
        ``static & msg`` is bit-identical to the fused ``valid``.
        Families with no message guard emit all-true / mult 1.

        Each message type is a static column range of the unpacked row,
        viewed as ``[B, pair, rows, run]`` with the widest contiguous
        digit run last.  A guarded message's digits (term, prevLogIndex,
        prevLogTerm, entry, leaderCommit, ...) come from the row's own
        fields; their mixed-radix flat offset inside the pair block is
        selected by ``iota == offset`` and a masked sum (``_pick``), so no
        one-hot operand is contracted.  Counts sum contiguous columns
        first and select the term digit after.  Per-server fields reach
        the pair axis, and per-pair results the ``[s, c]`` witness grid,
        by static slices and pads (``_pairs`` / ``_grid``).
        """
        cfg, uni = self.cfg, self.uni
        S, T, L, V, E, NP = self.S, self.T, self.L, self.V, self.E, self.NP
        B = st.voted_for.shape[0]
        i32 = lambda x: x.astype(I32)
        role = i32(st.role)
        ct = i32(st.current_term)
        ll = i32(st.log_len)
        lt = i32(st.log_term)
        ci = i32(st.commit_index)
        NPLI = uni.ap_npli
        legacy_ae = "legacy-append" in cfg.mutations

        # message blocks: per pair, the term digit leads every block
        bits = self.fpr.unpack_bits(st.msgs)  # i8 [B, M]
        vq = bits[:, : uni.vp_off].reshape(B, NP, T * L * T)  # (t, lli, llt)
        vp = bits[:, uni.vp_off : uni.aq_off].reshape(B, NP, T)
        aq = bits[:, uni.aq_off : uni.ap_off].reshape(
            B, NP, T * L * (T + 1), E * L
        )  # (t, pli, plt) x (entry, lc)
        ap = bits[:, uni.ap_off :].reshape(B, NP, T * NPLI, 2)  # (t, pli) x succ

        # per-server fields on the pair axis: the sender's, the receiver's
        grid = lambda x: jnp.broadcast_to(x, (B, S, S) + x.shape[3:])
        at_src = lambda x: self._pairs(grid(x[:, :, None]))
        at_dst = lambda x: self._pairs(grid(x[:, None]))
        # per-pair results on the witness grid [B, s, c]: the pair s->c,
        # or the pair c->s (s receives)
        from_s = self._grid
        to_s = lambda xp: jnp.swapaxes(self._grid(xp), 1, 2)

        tsel = jnp.clip(ct - 1, 0, T - 1)  # cur-term digit
        has_term = ct >= 1
        oh_ll_pos = _oh(jnp.clip(ll - 1, 0, L - 1), L)
        llt_val = (oh_ll_pos * lt).sum(-1, dtype=I32)
        tcur1 = jnp.clip(ct, 1, T)
        pli_ax = jnp.arange(1, L + 1, dtype=I32)
        t_src, t_dst = at_src(tsel), at_dst(tsel)  # [B, NP]
        true_ = lambda *sh: jnp.ones((B, *sh), bool)
        one_ = lambda *sh: jnp.ones((B, *sh), I32)

        # per-(pair, term) counts: static sums over contiguous columns
        aq_rows = aq.sum(3, dtype=I32)  # [B, NP, T*L*(T+1)]
        aq_r = aq_rows.reshape(B, NP, T, L * (T + 1)).sum(3, dtype=I32)
        tot = (
            vq.reshape(B, NP, T, L * T).sum(3, dtype=I32)
            + vp
            + aq_r
            + ap.reshape(B, NP, T, NPLI * 2).sum(3, dtype=I32)
        )
        to_cnt = from_s(tot).sum(1, dtype=I32)  # [B, d, T]: pairs to d
        aq_to_cnt = from_s(aq_r).sum(1, dtype=I32)

        ok_parts, mult_parts = [], []

        def emit(ok, mult):
            ok_parts.append(ok.reshape(B, -1))
            mult_parts.append(mult.reshape(B, -1))

        # F0 BecomeCandidate: no message guard
        emit(true_(S), one_(S))
        # F1 UpdateTerm (a): any message to s at term t
        emit(to_cnt > 0, to_cnt)
        # F2 UpdateTerm (b) + the split-brain Assert (Raft.tla:185)
        cnt2 = _pick(aq_to_cnt, tsel)
        has2 = has_term & (cnt2 > 0)
        if "become-follower" in cfg.mutations:
            abort = jnp.zeros((B,), bool)
        else:
            abort = (has2 & (role == LEADER)).any(1)
        emit(cnt2 > 0, cnt2)
        # F3 ResponseVote: an up-to-date VoteReq c->s at s's term (llt >
        # myllt, or equal with lli >= mylli), grant s->c not re-sent
        w = np.arange(T * L * T, dtype=np.int32)
        w_t, w_l, w_k = w // (L * T), (w // T) % L, w % T
        m_ = jnp.clip(llt_val, 0, T)[..., None]
        j_ = jnp.clip(ll - 1, 0, L - 1)[..., None]
        qual = (w_t == tsel[..., None]) & (
            (w_k > m_) | ((w_k == m_) & (w_l >= j_))
        )  # [B, s, T*L*T]
        qual_cnt = to_s(jnp.where(at_dst(qual), vq, 0).sum(2, dtype=I32))
        grant_bit = from_s(_pick(vp, t_src))
        emit((qual_cnt > 0) & (grant_bit == 0), qual_cnt)
        # F4 BecomeLeader: the vote-count threshold (Raft.tla:160-164)
        votes = from_s(_pick(vp, t_dst)).sum(1, dtype=I32)
        emit(votes + 1 >= cfg.majority, one_(S))
        # F5 ClientReq: no message guard
        emit(true_(S, V), one_(S, V))
        # F6 LeaderAppendEntry: the exact request s->d not already in flight
        ni = i32(st.next_index)
        lv = i32(st.log_val)
        pli6 = jnp.clip(ni - 1, 1, L)
        prev_oh = _oh(jnp.clip(ni - 2, 0, L - 1), L)
        plt6 = jnp.clip((prev_oh * lt[:, :, None]).sum(-1, dtype=I32), 0, T)
        has_e = ni <= ll[:, :, None]
        epos_oh = _oh(jnp.clip(ni - 1, 0, L - 1), L)
        et6 = jnp.clip((epos_oh * lt[:, :, None]).sum(-1, dtype=I32), 1, T)
        ev6 = jnp.clip((epos_oh * lv[:, :, None]).sum(-1, dtype=I32), 1, V)
        ecode6 = jnp.where(has_e, 1 + (et6 - 1) * V + (ev6 - 1), 0)
        off6 = _flat(
            (grid(tcur1[:, :, None]) - 1, T), (pli6 - 1, L), (plt6, T + 1),
            (ecode6, E), (grid(jnp.clip(ci, 1, L)[:, :, None]) - 1, L),
        )  # [B, s, d]
        present6 = from_s(_pick(aq.reshape(B, NP, -1), self._pairs(off6)))
        emit(present6 == 0, one_(S, S))
        # F7 FollowerAcceptEntry: the exact request c->s present, at s's
        # term and prevLogTerm = log_term[s, pli-1] (+ the dead
        # FollowerAppendEntry's resp/commit-advance gate under mutation)
        plt7 = jnp.clip(lt, 0, T)  # [B, s, l]
        off7 = at_dst(
            _flat((tsel[..., None], T), (pli_ax - 1, L), (plt7, T + 1))
        )  # [B, NP, l]: the (t, pli, plt) row of pair c->s
        present7 = to_s(_pick(aq, off7))  # [B, s, c, l, E*L]
        ok7 = present7.reshape(B, S, S, L, E, L) > 0
        if legacy_ae:
            pi = self.PI - uni.ap_pli_min  # [l, e]
            resp_present7 = from_s(
                _pick(ap[..., 1], t_src[:, :, None, None] * NPLI + pi)
            )  # [B, s, c, l, e]
            ci_adv = self.MINLC[None, None] > ci[:, :, None, None, None]
            ok7 = ok7 & (
                (resp_present7[:, :, :, :, :, None] == 0) | ci_adv[:, :, None]
            )
        emit(ok7, one_(S, S, L, E, L))
        # F8 FollowerRejectEntry: mismatching blocks c->s present, the
        # reject s->c unsent
        log_match = pli_ax[None, None, :] <= ll[:, :, None]
        tot8 = to_s(
            _pick(aq_rows.reshape(B, NP, T, L, T + 1).sum(4, dtype=I32), t_dst)
        )  # [B, s, c, l]
        match8 = to_s(_pick(aq_rows, off7))
        cnt8 = tot8 - jnp.where(log_match[:, :, None, :], match8, 0)
        # the reject's pli digit is l0 in both pli domains (section F8 of
        # ``__call__``)
        rej_bit = from_s(
            _pick(ap[..., 0], t_src[..., None] * NPLI + pli_ax - 1)
        )
        emit((cnt8 > 0) & (rej_bit == 0), cnt8)
        # F9 HandleAppendResp: the response bit c->s present (witness pli
        # 1..L: digit l0 + 1 - ap_pli_min)
        bit9 = to_s(
            _pick(ap, t_dst[..., None] * NPLI + pli_ax - uni.ap_pli_min)
        )  # [B, s, c, l, 2]
        emit(bit9 > 0, one_(S, S, L, 2))
        # F10 LeaderCanCommit / F11 Restart: no message guard
        emit(true_(S), one_(S))
        emit(true_(S), one_(S))

        return (
            jnp.concatenate(ok_parts, axis=1),
            jnp.concatenate(mult_parts, axis=1),
            abort,
        )

    # ---- the expand ------------------------------------------------------

    def __call__(self, st, msum, want_fp: bool = True):
        """Dense pass 1.  ``want_fp=False`` computes guards only (valid,
        mult, abort; fp outputs are None) — the late-canonicalization
        engine path fingerprints the few compacted *candidates* from their
        materialized states instead of folding the P-wide symmetry hash
        into every one of the B*K fan-out lanes, which is what makes
        large symmetry groups (S=5: P=120, S=7: P=5040) affordable."""
        cfg, uni = self.cfg, self.uni
        S, T, L, V, E, NP = self.S, self.T, self.L, self.V, self.E, self.NP
        P, NC = self.fpr.P, self.fpr.N_CHAN
        B = st.voted_for.shape[0]
        i32 = lambda x: x.astype(I32)

        ct = i32(st.current_term)  # [B, S]
        vf = i32(st.voted_for)
        role = i32(st.role)
        ll = i32(st.log_len)
        lt = i32(st.log_term)  # [B, S, L]
        lv = i32(st.log_val)
        mi = i32(st.match_index)  # [B, S, S]
        ni = i32(st.next_index)
        ci = i32(st.commit_index)
        pend = i32(st.pending)
        ec = i32(st.election_count)  # [B]
        rc = i32(st.restart_count)
        vs = i32(st.val_sent)  # [B, V]

        # ---- message-block views (static reshapes) ----------------------
        bits = self.fpr.unpack_bits(st.msgs).astype(I32)  # [B, M]
        vq = bits[:, : uni.vp_off].reshape(B, NP, T, L, T)
        vp = bits[:, uni.vp_off : uni.aq_off].reshape(B, NP, T)
        aq = bits[:, uni.aq_off : uni.ap_off].reshape(B, NP, T, L, T + 1, E, L)
        # AppendResp pli digit spans ap_pli_min..L (0..L under the
        # legacy-append mutation, whose reject carries prevLogIndex - 1)
        NPLI = uni.ap_npli
        legacy_ae = "legacy-append" in cfg.mutations
        ap = bits[:, uni.ap_off :].reshape(B, NP, T, NPLI, 2)

        # ---- per-chunk aggregates ---------------------------------------
        vq_r = vq.sum((3, 4), dtype=I32)  # [B, NP, T]
        aq_r = aq.sum((3, 4, 5, 6), dtype=I32)
        ap_r = ap.sum((3, 4), dtype=I32)
        to_cnt = jnp.einsum("bpt,dp->bdt", vq_r + vp + aq_r + ap_r, self.SELD)
        aq_to_cnt = jnp.einsum("bpt,dp->bdt", aq_r, self.SELD)
        AQR = aq.sum((5, 6), dtype=I32)  # [B, NP, T, L, T+1]
        ap0, ap1 = ap[..., 0], ap[..., 1]  # [B, NP, T, L]

        # shared one-hots / scalars
        oh_ct = _oh(jnp.clip(ct - 1, 0, T - 1), T)  # cur-term digit
        has_term = ct >= 1
        oh_ll_pos = _oh(jnp.clip(ll - 1, 0, L - 1), L)  # mylli digit (ll-1)
        llt_val = (oh_ll_pos * lt).sum(-1, dtype=I32)  # lt[b, s, ll-1]
        not_self = ~jnp.eye(S, dtype=bool)[None]
        tcur1 = jnp.clip(ct, 1, T)  # term clamped to >= 1 for encoders

        if want_fp:
            oh_vfw = _oh(vf, S + 1).astype(U32)
            old_vf_c = jnp.einsum("bsw,swpc->bspc", oh_vfw, self.C_vf)
            base = self.fpr.feat_hash(self.fpr.spec.features(st)) + msum  # [B,P,C]

        fpv_parts, fpf_parts, valid_parts, mult_parts = [], [], [], []

        def emit(valid, mult, dh=None):
            """valid bool[B,*W], mult i32[B,*W], dh u32[B,*W,P,chan]."""
            valid_parts.append(valid.reshape(B, -1))
            mult_parts.append(mult.reshape(B, -1))
            if dh is None:
                return
            h = base.reshape(B, *([1] * (dh.ndim - 3)), P, NC) + dh
            v, f = self.fpr.finalize(h)
            fpv_parts.append(v.reshape(B, -1))
            fpf_parts.append(f.reshape(B, -1))

        def dmul(C, delta):
            """C u32[*idx, P, chan] * delta i32[..., *idx] (broadcasted)."""
            return C * delta.astype(U32)[..., None, None]

        # ---- F0 BecomeCandidate(s)  axes [B, s] --------------------------
        valid0 = (ec[:, None] < cfg.max_election) & (
            (role == FOLLOWER) | (role == CANDIDATE)
        )
        dh0 = None
        if want_fp:
            new_term = jnp.clip(ct + 1, 1, T)
            llt_cand = jnp.clip(llt_val, 0, T - 1)  # lastLogTerm < minted term
            dh0 = (
                dmul(self.C_ct, new_term - ct)
                + dmul(self.C_role, CANDIDATE - role)
                + self.C_vf_self
                - old_vf_c
                + self.C_ec
            )
            if S > 1:
                oh_t0 = _oh(new_term - 1, T)
                oh_lli0 = oh_ll_pos
                oh_llt0 = _oh(llt_cand, T)
                present0 = jnp.einsum(
                    "bptlk,srp,bst,bsl,bsk->bsr",
                    vq, self.SELPEER, oh_t0, oh_lli0, oh_llt0,
                )  # [B, s, peer]
                rest0 = ((new_term - 1) * L + (ll - 1)) * T + llt_cand  # [B, s]
                dmsg0 = self._add_msg(
                    self._pair_peers, 0,
                    jnp.broadcast_to(rest0[:, :, None], (B, S, S - 1)),
                    1 - present0,
                ).sum(2, dtype=U32)
                dh0 = dh0 + dmsg0
        emit(valid0, jnp.ones((B, S), I32), dh0)

        # ---- F1 UpdateTerm branch (a)  axes [B, s, t0] -------------------
        t_ax = jnp.arange(1, T + 1, dtype=I32)
        valid1 = (t_ax[None, None, :] > ct[:, :, None]) & (to_cnt > 0)
        dh1 = None
        if want_fp:
            vf_delta1 = self.C_vf[:, 0] - old_vf_c
            if "become-follower" in cfg.mutations:
                # FollowerUpdateTerm (Raft.tla:192-197): a Follower keeps
                # its votedFor when adopting a higher term
                vf_delta1 = jnp.where(
                    (role == FOLLOWER)[:, :, None, None],
                    jnp.uint32(0),
                    vf_delta1,
                )
            dh1 = (
                dmul(self.C_ct[:, None], t_ax[None, None, :] - ct[:, :, None])
                + (dmul(self.C_role, FOLLOWER - role) + vf_delta1)[
                    :, :, None
                ]
            )
        emit(valid1, to_cnt, dh1)

        # ---- F2 UpdateTerm branch (b) + Assert  axes [B, s] --------------
        cnt2 = jnp.einsum("bdt,bdt->bd", aq_to_cnt, oh_ct)
        has2 = has_term & (cnt2 > 0)
        valid2 = has2 & (role == CANDIDATE)
        if "become-follower" in cfg.mutations:
            # the dead BecomeFollower family has no Assert (Raft.tla:228-231)
            abort = jnp.zeros((B,), bool)
        else:
            abort = (has2 & (role == LEADER)).any(1)
        dh2 = dmul(self.C_role, FOLLOWER - role) if want_fp else None
        emit(valid2, cnt2, dh2)

        # ---- F3 ResponseVote(s, cand)  axes [B, s, c] --------------------
        UP = jnp.einsum("bptlk,klmj->bptmj", vq, self.QUAL)
        oh_myllt = _oh(jnp.clip(llt_val, 0, T), T + 1)
        qual_cnt = jnp.einsum(
            "bptmj,csp,bst,bsm,bsj->bsc",
            UP, self.SELP, oh_ct, oh_myllt, oh_ll_pos,
        )
        grant_bit = jnp.einsum("bpt,scp,bst->bsc", vp, self.SELP, oh_ct)
        if "double-vote" in cfg.mutations:
            vf_ok = jnp.ones((B, S, S), bool)
        else:
            vf_ok = (vf[:, :, None] == 0) | (
                vf[:, :, None] == jnp.arange(1, S + 1, dtype=I32)[None, None, :]
            )
        valid3 = (
            (role == FOLLOWER)[:, :, None]
            & has_term[:, :, None]
            & not_self
            & vf_ok
            & (qual_cnt > 0)
            & (grant_bit == 0)
        )
        # votedFor[s]: old -> cand+1
        dh3 = None
        if want_fp:
            dh3 = self.C_vf[None, :, 1:] - old_vf_c[:, :, None]
            rest3 = jnp.broadcast_to((tcur1 - 1)[:, :, None], (B, S, S))
            dmsg3 = self._add_msg(self._pair_ab, 1, rest3, 1 - grant_bit)
            dh3 = dh3 + dmsg3
        emit(valid3, qual_cnt, dh3)

        # ---- F4 BecomeLeader(s)  axes [B, s] -----------------------------
        votes = jnp.einsum("bpt,sp,bst->bs", vp, self.SELD, oh_ct)
        valid4 = (role == CANDIDATE) & (votes + 1 >= cfg.majority)
        dh4 = None
        if want_fp:
            ar = jnp.arange(S, dtype=I32)
            mi_tgt = jnp.where(
                ar[None, None, :] == ar[None, :, None], ll[:, :, None], 1
            )
            dh4 = (
                dmul(self.C_role, LEADER - role)
                + jnp.einsum(
                    "bsu,supc->bspc", (mi_tgt - mi).astype(U32), self.C_mi
                )
                + jnp.einsum(
                    "bsu,supc->bspc", ((ll[:, :, None] + 1) - ni).astype(U32), self.C_ni
                )
                + jnp.einsum("bsu,supc->bspc", (-pend).astype(U32), self.C_pend)
            )
        emit(valid4, jnp.ones((B, S), I32), dh4)

        # ---- F5 ClientReq(s, v)  axes [B, s, v] --------------------------
        valid5 = (
            (role == LEADER)[:, :, None]
            & (vs[:, None, :] == 0)
            & (ll < L)[:, :, None]
        )
        dh5 = None
        if want_fp:
            pos_oh = _oh(jnp.clip(ll, 0, L - 1), L)  # append slot (0-based = ll)
            d_lt5 = jnp.einsum(
                "bsl,slpc->bspc",
                (pos_oh * (ct[:, :, None] - lt)).astype(U32), self.C_lt,
            )
            C_lv_pos = jnp.einsum("bsl,slpc->bspc", pos_oh.astype(U32), self.C_lv)
            lv_pos = (pos_oh * lv).sum(-1, dtype=I32)  # [B, s]
            v_val = jnp.arange(1, V + 1, dtype=I32)
            d_lv5 = C_lv_pos[:, :, None] * (
                (v_val[None, None, :] - lv_pos[:, :, None]).astype(U32)[..., None, None]
            )
            d_mid5 = dmul(self.C_mi_diag, (ll + 1) - jnp.einsum("bss->bs", mi))
            d_vs5 = dmul(self.C_vs, 1 - vs)  # [B, v, P, C]
            dh5 = (d_lt5 + self.C_ll + d_mid5)[:, :, None] + d_lv5 + d_vs5[:, None]
        emit(valid5, jnp.ones((B, S, V), I32), dh5)

        # ---- F6 LeaderAppendEntry(s, d)  axes [B, s, d] ------------------
        pli6 = jnp.clip(ni - 1, 1, L)
        prev_oh = _oh(jnp.clip(ni - 2, 0, L - 1), L)
        plt6 = jnp.clip(jnp.einsum("bsdl,bsl->bsd", prev_oh, lt), 0, T)
        has_e = ni <= ll[:, :, None]
        epos_oh = _oh(jnp.clip(ni - 1, 0, L - 1), L)
        et6 = jnp.clip(jnp.einsum("bsdl,bsl->bsd", epos_oh, lt), 1, T)
        ev6 = jnp.clip(jnp.einsum("bsdl,bsl->bsd", epos_oh, lv), 1, V)
        ecode6 = jnp.where(has_e, 1 + (et6 - 1) * V + (ev6 - 1), 0)
        lc6 = jnp.clip(ci, 1, L)[:, :, None]
        present6 = jnp.einsum(
            "bqtlmeh,sdq,bsdt,bsdl,bsdm,bsde,bsdh->bsd",
            aq, self.SELP,
            _oh(jnp.broadcast_to(tcur1[:, :, None], (B, S, S)) - 1, T),
            _oh(pli6 - 1, L), _oh(plt6, T + 1), _oh(ecode6, E),
            _oh(jnp.broadcast_to(lc6, (B, S, S)) - 1, L),
        )
        valid6 = (
            (role == LEADER)[:, :, None]
            & not_self
            & (ni <= ll[:, :, None] + 1)
            & (pend == 0)
            & (present6 == 0)
        )
        dh6 = None
        if want_fp:
            dh6 = jnp.einsum(
                "bsd,sdpc->bsdpc", (1 - pend).astype(U32), self.C_pend
            )
            rest6 = (
                (((tcur1[:, :, None] - 1) * L + (pli6 - 1)) * (T + 1) + plt6) * E
                + ecode6
            ) * L + (lc6 - 1)
            dmsg6 = self._add_msg(self._pair_ab, 2, rest6, 1 - present6)
            dh6 = dh6 + dmsg6
        emit(valid6, jnp.ones((B, S, S), I32), dh6)

        # ---- F7 FollowerAcceptEntry(s, src, pli, e, lc)  -----------------
        # axes [B, s, c(src), l(pli0), e, h(lc0)]
        plt7 = jnp.clip(lt, 0, T)  # lt[b, s, pli-1] axis-aligned over l
        oh_plt7 = _oh(plt7, T + 1)  # [B, s, l, T+1]
        present7 = jnp.einsum(
            "bqtlmeh,csq,bst,bslm->bscleh", aq, self.SELP, oh_ct, oh_plt7
        )
        pli_ax = jnp.arange(1, L + 1, dtype=I32)
        log_match = pli_ax[None, None, :] <= ll[:, :, None]  # [B, s, l]
        valid7 = (
            (role == FOLLOWER)[:, :, None, None, None, None]
            & has_term[:, :, None, None, None, None]
            & not_self[:, :, :, None, None, None]
            & log_match[:, :, None, :, None, None]
            & (present7 > 0)
        )
        # success AppendResp presence: s -> src at cur with prevLogIndex
        # PI[l, e] — needed by the fp delta, and under legacy-append also
        # by the guard (Raft.tla:347-348's resp∉msgs ∨ commit-advance);
        # skipped entirely on the unmutated guards-only hot path
        resp_present7 = None
        if legacy_ae or want_fp:
            oh_pi = _oh(self.PI - uni.ap_pli_min, NPLI)  # [l, e, NPLI]
            resp_present7 = jnp.einsum(
                "bqtj,scq,bst,lej->bscle", ap1, self.SELP, oh_ct, oh_pi
            )
        if legacy_ae:
            ci_adv = (
                self.MINLC[None, None] > ci[:, :, None, None, None]
            )  # new_ci > ci  [B, s, l, e, h]
            valid7 = valid7 & (
                (resp_present7[:, :, :, :, :, None] == 0)
                | ci_adv[:, :, None]
            )
        dh7 = None
        if want_fp:
            # log rewrite deltas (only when `updated`)
            append_new = self.NL[None, None] > ll[:, :, None, None]  # [B, s, l, e]
            lt_next = jnp.concatenate([lt[..., 1:], lt[..., -1:]], axis=-1)
            lv_next = jnp.concatenate([lv[..., 1:], lv[..., -1:]], axis=-1)
            conflict = (
                (self.EL[None, None, None] == 1)
                & (pli_ax[None, None, :, None] < ll[:, :, None, None])
                & (
                    (lt_next[:, :, :, None] != self.ETERM[None, None, None])
                    | (lv_next[:, :, :, None] != self.EVAL[None, None, None])
                )
            )
            updated = (append_new | conflict).astype(I32)  # [B, s, l, e]
            # delta_lt[b,s,j,l,e] = (KEEPX-1)*lt[j] + AT*ETERM[e]
            d_lt_j = (self.KEEPX[None, None] - 1) * lt[:, :, :, None, None] + (
                self.AT[None, None] * self.ETERM[None, None, None, None]
            )
            d_lv_j = (self.KEEPX[None, None] - 1) * lv[:, :, :, None, None] + (
                self.AT[None, None] * self.EVAL[None, None, None, None]
            )
            d_log7 = jnp.einsum(
                "bsjle,sjpc->bslepc", d_lt_j.astype(U32), self.C_lt
            ) + jnp.einsum("bsjle,sjpc->bslepc", d_lv_j.astype(U32), self.C_lv)
            d_ll7 = dmul(
                self.C_ll[:, None, None], self.NL[None, None] - ll[:, :, None, None]
            )
            d_upd7 = (d_log7 + d_ll7) * updated.astype(U32)[..., None, None]
            # commitIndex := max(ci, min(lc, new_len)) — unconditional
            d_ci7 = dmul(
                self.C_ci[:, None, None, None],
                jnp.maximum(ci[:, :, None, None, None], self.MINLC[None, None])
                - ci[:, :, None, None, None],
            )  # [B, s, l, e, h, P, C]
            rest7 = (
                (tcur1 - 1)[:, :, None, None] * NPLI
                + (self.PI[None, None] - uni.ap_pli_min)
            ) * 2 + 1
            dmsg7 = self._add_msg(
                self._pair_ab[:, :, None, None],  # [s, c, 1, 1] pair(s->c)
                3,
                jnp.broadcast_to(rest7[:, :, None], (B, S, S, L, E)),
                1 - resp_present7,
            )  # [B, s, c, l, e, P, C]
            dh7 = jnp.broadcast_to(
                d_upd7[:, :, None, :, :, None]
                + d_ci7[:, :, None]
                + dmsg7[:, :, :, :, :, None],
                (B, S, S, L, E, L, P, NC),
            )
        emit(valid7, jnp.ones((B, S, S, L, E, L), I32), dh7)

        # ---- F8 FollowerRejectEntry(s, src, pli)  axes [B, s, c, l] ------
        tot8 = jnp.einsum(
            "bqtlm,csq,bst->bscl", AQR, self.SELP, oh_ct
        )
        match8 = jnp.einsum(
            "bqtlm,csq,bst,bslm->bscl", AQR, self.SELP, oh_ct, oh_plt7
        )
        cnt8 = tot8 - jnp.where(
            log_match[:, :, None, :], match8, 0
        )
        # the reject response's pli digit per witness pli l0: live -> l0
        # (resp pli = pli); legacy-append -> also l0, but in the widened
        # 0..L domain (resp pli = pli - 1, digit = (pli-1) - 0) — only the
        # block slice and the encode stride differ
        ap0_rej = ap0 if uni.ap_pli_min == 1 else ap0[:, :, :, :L]
        rej_bit = jnp.einsum("bqtl,scq,bst->bscl", ap0_rej, self.SELP, oh_ct)
        valid8 = (
            (role == FOLLOWER)[:, :, None, None]
            & has_term[:, :, None, None]
            & not_self[:, :, :, None]
            & (cnt8 > 0)
            & (rej_bit == 0)
        )
        dh8 = None
        if want_fp:
            rest8 = jnp.broadcast_to(
                ((tcur1 - 1)[:, :, None, None] * NPLI + jnp.arange(L, dtype=I32))
                * 2,
                (B, S, S, L),
            )
            dh8 = self._add_msg(self._pair_ab[:, :, None], 3, rest8, 1 - rej_bit)
        emit(valid8, cnt8, dh8)

        # ---- F9 HandleAppendResp(s, src, pli, succ)  [B, s, c, l, x] -----
        # witness pli spans 1..L either way (a pli=0 legacy reject can
        # never satisfy the guard: pli > matchIndex >= 1, Raft.tla:392)
        ap9 = ap if uni.ap_pli_min == 1 else ap[:, :, :, 1:]
        bit9 = jnp.einsum("bqtlx,csq,bst->bsclx", ap9, self.SELP, oh_ct)
        pli9 = pli_ax[None, None, None, :]  # [1,1,1,l]
        mi_sc = mi[:, :, :, None]
        ni_sc = ni[:, :, :, None]
        ok_succ = mi_sc < pli9
        ok_fail = (pli9 + 1 == ni_sc) & (pli9 > mi_sc)
        ok9 = jnp.stack([ok_fail, ok_succ], axis=-1)
        valid9 = (
            (role == LEADER)[:, :, None, None, None]
            & has_term[:, :, None, None, None]
            & not_self[:, :, :, None, None]
            & (pend == 1)[:, :, :, None, None]
            & (bit9 > 0)
            & ok9
        )
        dh9 = None
        if want_fp:
            x_ax = jnp.arange(2, dtype=I32)
            d_mi9 = dmul(
                self.C_mi[:, :, None, None],
                x_ax * (pli9[..., None] - mi_sc[..., None]),
            )
            d_ni9 = dmul(
                self.C_ni[:, :, None, None],
                pli9[..., None] + x_ax - ni_sc[..., None],
            )
            d_p9 = dmul(self.C_pend[:, :, None, None], -pend[:, :, :, None, None])
            dh9 = d_mi9 + d_ni9 + d_p9
        emit(valid9, jnp.ones((B, S, S, L, 2), I32), dh9)

        # ---- F10 LeaderCanCommit(s)  axes [B, s] -------------------------
        # median_index-th order statistic without a sort op: the stable
        # ascending-sort position of row element u is #(x_w < x_u) +
        # #(w < u with x_w == x_u); select the element whose position is
        # the median index (S is tiny, so the S^2 compare grid is cheap)
        xu = mi[:, :, :, None]  # [B, s, u, w]
        xw = mi[:, :, None, :]
        tri = (jnp.arange(S)[:, None] > jnp.arange(S)[None, :]).astype(I32)
        pos = (xw < xu).sum(-1, dtype=I32) + ((xw == xu) * tri[None, None]).sum(
            -1, dtype=I32
        )
        med = (mi * (pos == cfg.median_index)).sum(-1, dtype=I32)
        valid10 = (role == LEADER) & (med > ci)
        dh10 = dmul(self.C_ci, med - ci) if want_fp else None
        emit(valid10, jnp.ones((B, S), I32), dh10)

        # ---- F11 Restart(s)  axes [B, s] ---------------------------------
        valid11 = (role == LEADER) & (rc[:, None] < cfg.max_restart)
        dh11 = (dmul(self.C_role, FOLLOWER - role) + self.C_rc) if want_fp else None
        emit(valid11, jnp.ones((B, S), I32), dh11)

        valid = jnp.concatenate(valid_parts, axis=1)
        mult = jnp.concatenate(mult_parts, axis=1)
        if not want_fp:
            return valid, mult, None, None, abort
        fpv = jnp.concatenate(fpv_parts, axis=1)
        fpf = jnp.concatenate(fpf_parts, axis=1)
        return valid, mult, fpv, fpf, abort
