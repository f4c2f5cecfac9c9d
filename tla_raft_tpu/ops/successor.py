"""The successor kernel: Raft's ``Next`` as a masked fan-out on TPU.

TLC evaluates ``Next`` (Raft.tla:416-430) as a disjunction walk — every
action x existential witness yields at most one successor (SURVEY.md §3.2).
All witness spaces are statically bounded by the model constants, so the
whole walk compiles to a fixed fan-out of K **slots** per state, each slot
a (family, server, witness...) coordinate with a validity mask:

  family  0 BecomeCandidate(s)            Raft.tla:107-130   W = S
  family  1 UpdateTerm(s) branch (a)      Raft.tla:178-182   W = S*T
  family  2 UpdateTerm(s) branch (b)      Raft.tla:183-188   W = S
  family  3 ResponseVote(s, cand)         Raft.tla:132-155   W = S*S
  family  4 BecomeLeader(s)               Raft.tla:157-173   W = S
  family  5 ClientReq(s, v)               Raft.tla:233-240   W = S*V
  family  6 LeaderAppendEntry(s, dst)     Raft.tla:242-269   W = S*S
  family  7 FollowerAcceptEntry(s, src,   Raft.tla:275-300   W = S*S*L*E*L
              pli, entry, leaderCommit)
  family  8 FollowerRejectEntry(s, src,   Raft.tla:302-321   W = S*S*L
              pli)
  family  9 HandleAppendResp(s, src,      Raft.tla:374-396   W = S*S*L*2
              pli, success)
  family 10 LeaderCanCommit(s)            Raft.tla:398-407   W = S
  family 11 Restart(s)                    Raft.tla:409-414   W = S

Existentials over the message set collapse onto the slot grid: where the
successor depends only on a few message fields (e.g. UpdateTerm only reads
``m.term``), the slot enumerates those fields and the guard becomes "any
message matching this pattern present" — a bitwise AND against a
precomputed pattern mask over the message universe.  Each slot also
reports its **multiplicity** (how many concrete message witnesses it
stands for), so the engine reproduces TLC's states-generated count
exactly.

Each family is written as a *scalar* transition function on one state and
one witness — a direct transcription of the spec's action, structured like
oracle/explicit.py — then ``vmap``'d over the witness grid and the state
batch.  Pass 1 (``expand``) returns per-slot validity, multiplicity and
the child's canonical fingerprints (features hashed fresh, message-set
hash incremental from the parent's).  Pass 2 (``materialize``) rebuilds
the full successor state for the slots that survived global dedup, via
``lax.switch`` over the family id.

The split-brain ``Assert(role[s] # Leader)`` (Raft.tla:185) is evaluated
in-kernel as a per-state abort flag, faithful to TLC aborting the run
during successor generation (SURVEY.md §3.3).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..config import (
    APPEND_REQ,
    CANDIDATE,
    FOLLOWER,
    LEADER,
    VOTE_REQ,
    VOTE_RESP,
    RaftConfig,
)
from ..models.raft import RaftState
from .dense_expand import DenseExpand
from .fingerprint import Fingerprinter, get_fingerprinter
from .msg_universe import get_universe
from . import tables as _tables
from .mxu_expand import MXUExpand, mxu_enabled_by_env

I32 = jnp.int32
U8 = jnp.uint8
U32 = jnp.uint32


class Expansion(NamedTuple):
    """Pass-1 output for a batch of B parent states and K slots each."""

    valid: jnp.ndarray  # bool[B, K]
    mult: jnp.ndarray  # i32[B, K] — concrete witness count of the slot
    fp_view: jnp.ndarray  # u64[B, K] (garbage where invalid)
    fp_full: jnp.ndarray  # u64[B, K]
    abort: jnp.ndarray  # bool[B] — split-brain Assert fired (Raft.tla:185)


def _pack(uni, bits: np.ndarray) -> np.ndarray:
    return uni.pack_bits(bits.astype(np.uint8))


class GuardTables:
    """Precomputed pattern masks over the message universe (numpy -> device).

    Each table row is a packed u32[n_words] bitmask selecting the messages
    that match a (type, src, dst, term, ...) pattern; guards evaluate as
    ``msgs & row`` followed by any/popcount.  Index conventions: servers
    and terms are offset to 0-based rows (term t -> row t-1).

    The MXU expand extends this table family with per-action guard/update
    *coefficient* tables (ops/mxu_expand.MXUTables, attached as ``.mxu``
    when the MXU path is selected): the 0/1 guard coefficient matrix +
    threshold that turns the static guard conjunctions into one
    [lanes, feat] x [feat, actions] matmul, and the per-slot update
    constant block behind the gather-free materialize.
    """

    def __init__(self, cfg: RaftConfig):
        uni = get_universe(cfg)
        self.uni = uni
        S, T, L = cfg.S, cfg.T, cfg.L
        u = uni

        # any message to dst at term t  (UpdateTerm branch (a), Raft.tla:178)
        self.any_to = jnp.asarray(u.dst_term_any_mask)  # [S, T, W]
        # AppendReq to dst at term t    (UpdateTerm branch (b) + Assert)
        self.aq_to = jnp.asarray(u.dst_term_appendreq_mask)  # [S, T, W]

        # VoteResp to dst at term t     (BecomeLeader count, Raft.tla:160-164)
        vp = np.zeros((S, T, u.n_words), np.uint32)
        for d in range(1, S + 1):
            for t in range(1, T + 1):
                vp[d - 1, t - 1] = _pack(u, (u.typ == VOTE_RESP) & (u.dst == d) & (u.term == t))
        self.vp_to = jnp.asarray(vp)

        # Up-to-date VoteReq from cand c to dst d at term t, given the
        # receiver's (lastLogTerm, lastLogIndex)  (Raft.tla:145-147):
        # qualifies iff m.llt > myllt \/ (m.llt = myllt /\ m.lli >= mylli).
        vq = np.zeros((S, S, T, T + 1, L, u.n_words), np.uint32)
        base_vq = u.typ == VOTE_REQ
        for c in range(1, S + 1):
            for d in range(1, S + 1):
                if c == d:
                    continue
                for t in range(1, T + 1):
                    sel = base_vq & (u.src == c) & (u.dst == d) & (u.term == t)
                    for myllt in range(T + 1):
                        for mylli in range(1, L + 1):
                            ok = (u.llt > myllt) | ((u.llt == myllt) & (u.lli >= mylli))
                            vq[c - 1, d - 1, t - 1, myllt, mylli - 1] = _pack(u, sel & ok)
        self.vq_uptodate = jnp.asarray(vq)

        # AppendReq blocks by (src, dst, term, prevLogIndex): all plt/entry/lc
        # (FollowerRejectEntry witness collapse, Raft.tla:304-308), plus the
        # per-prevLogTerm sub-blocks used to subtract the LogMatch cases.
        blk = np.zeros((S, S, T, L, u.n_words), np.uint32)
        sub = np.zeros((S, S, T, L, T + 1, u.n_words), np.uint32)
        base_aq = u.typ == APPEND_REQ
        for c in range(1, S + 1):
            for d in range(1, S + 1):
                if c == d:
                    continue
                for t in range(1, T + 1):
                    sel0 = base_aq & (u.src == c) & (u.dst == d) & (u.term == t)
                    for pli in range(1, L + 1):
                        sel = sel0 & (u.pli == pli)
                        blk[c - 1, d - 1, t - 1, pli - 1] = _pack(u, sel)
                        for plt in range(T + 1):
                            sub[c - 1, d - 1, t - 1, pli - 1, plt] = _pack(u, sel & (u.plt == plt))
        self.aq_block = jnp.asarray(blk)
        self.aq_plt = jnp.asarray(sub)


def _bit_get(msgs: jnp.ndarray, mid: jnp.ndarray) -> jnp.ndarray:
    """Membership test: packed u32[W] words, message id -> bool."""
    word = msgs[jnp.clip(mid, 0, None) >> 5]
    return ((word >> (mid & 31).astype(U32)) & U32(1)).astype(jnp.bool_)


# -- scatter-free updates --------------------------------------------------
# XLA:TPU miscompiles scatters whose index is *data* (not a trace-constant)
# at large batch shapes under vmap — updates are dropped or land as zeros
# (observed twice: ClientReq's log append in round 1, and every
# materialize-path `.at[s].set` at cap>=1024 in round 2; both caught by the
# oracle differential).  All action updates therefore use iota-mask
# selects: index spaces are tiny (S servers, L log slots), so a masked
# select is also faster than a scatter on TPU.


def _set1(vec: jnp.ndarray, i, val) -> jnp.ndarray:
    """vec.at[i].set(val) as a select; vec 1-D, i scalar."""
    return jnp.where(
        jnp.arange(vec.shape[0]) == i, jnp.asarray(val).astype(vec.dtype), vec
    )


def _set_row(mat: jnp.ndarray, i, row) -> jnp.ndarray:
    """mat.at[i].set(row) as a select; mat [n, m], i scalar, row [m]."""
    return jnp.where(
        (jnp.arange(mat.shape[0]) == i)[:, None],
        jnp.asarray(row).astype(mat.dtype),
        mat,
    )


def _set2(mat: jnp.ndarray, i, j, val) -> jnp.ndarray:
    """mat.at[i, j].set(val) as a select; mat [n, m], i/j scalars."""
    mask = (jnp.arange(mat.shape[0]) == i)[:, None] & (
        jnp.arange(mat.shape[1]) == j
    )[None, :]
    return jnp.where(mask, jnp.asarray(val).astype(mat.dtype), mat)


# The matching scatter-free READS: the same backend charges a fixed
# multi-ms penalty to any launched program containing a data-indexed
# gather (docs/PERF.md), and the materialize pass runs per level on the
# deduped survivors, so its per-lane state reads use masked reduces too.
# (Reads that only feed guards/multiplicities stay as plain indexing:
# materialize dead-code-eliminates them, and the scalar expand reference
# is CPU-only.)


def _get1(vec: jnp.ndarray, i) -> jnp.ndarray:
    """vec[i] as a masked reduce (no gather); i32 scalar."""
    return jnp.where(jnp.arange(vec.shape[0]) == i, vec.astype(I32), 0).sum(
        dtype=I32
    )


def _get_row(mat: jnp.ndarray, i) -> jnp.ndarray:
    """mat[i] (row) as a masked reduce; [n, m] -> i32[m]."""
    return jnp.where(
        (jnp.arange(mat.shape[0]) == i)[:, None], mat.astype(I32), 0
    ).sum(0, dtype=I32)


def _get2(mat: jnp.ndarray, i, j) -> jnp.ndarray:
    """mat[i, j] as a masked reduce; i32 scalar."""
    mask = (jnp.arange(mat.shape[0]) == i)[:, None] & (
        jnp.arange(mat.shape[1]) == j
    )[None, :]
    return jnp.where(mask, mat.astype(I32), 0).sum(dtype=I32)


def _any(msgs: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    return jnp.any((msgs & mask) != 0)


def _popcount(msgs: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    return jax.lax.population_count(msgs & mask).sum().astype(I32)


class SuccessorKernel:
    """Compiled fan-out for one RaftConfig (SURVEY.md §7.2 step 2).

    ``mxu`` selects the MXU-factored hot path (ops/mxu_expand.py):
    ``expand_guards`` becomes the guard coefficient matmul + the dense
    message terms, and ``materialize``/``materialize_added`` the
    gather-free select-matrix formulation.  Default from TLA_RAFT_MXU
    (on); the legacy kernels stay jitted as ``*_legacy`` for A/B —
    both are bit-identical on every input (tests/test_mxu_expand.py).
    """

    def __init__(
        self,
        cfg: RaftConfig,
        fpr: Fingerprinter | None = None,
        mxu: bool | None = None,
    ):
        self.cfg = cfg
        self.uni = get_universe(cfg)
        self.fpr = fpr or get_fingerprinter(cfg)
        self.tables = GuardTables(cfg)
        S, T, L, V = cfg.S, cfg.T, cfg.L, cfg.V
        E = self.uni.n_entry
        self.A = max(S - 1, 1)  # max messages added by one action

        def grid(*dims):
            g = np.meshgrid(*[np.arange(d) for d in dims], indexing="ij")
            return np.stack([x.ravel() for x in g], axis=1).astype(np.int32)

        def pad5(c):
            out = np.zeros((c.shape[0], 5), np.int32)
            out[:, : c.shape[1]] = c
            return out

        # (name, scalar fn, witness coords [W, 5]); coord 0 is always s.
        # Mutation swaps keep the slot grid identical — the dead actions'
        # witness spaces coincide with their live successors' — only the
        # scalar semantics and trace names change (SURVEY.md §4.4).
        ut_name = (
            "BecomeFollower"
            if "become-follower" in cfg.mutations
            else "UpdateTerm"
        )
        legacy_ae = "legacy-append" in cfg.mutations
        self.families = [
            ("BecomeCandidate", self._become_candidate, pad5(grid(S))),
            (ut_name, self._update_term_a, pad5(grid(S, T))),
            (ut_name, self._update_term_b, pad5(grid(S))),
            ("ResponseVote", self._response_vote, pad5(grid(S, S))),
            ("BecomeLeader", self._become_leader, pad5(grid(S))),
            ("ClientReq", self._client_req, pad5(grid(S, V))),
            ("LeaderAppendEntry", self._leader_append, pad5(grid(S, S))),
            ("FollowerAppendEntry" if legacy_ae else "FollowerAcceptEntry",
             self._follower_accept, pad5(grid(S, S, L, E, L))),
            ("FollowerAppendEntry" if legacy_ae else "FollowerRejectEntry",
             self._follower_reject, pad5(grid(S, S, L))),
            ("HandleAppendResp", self._handle_append_resp, pad5(grid(S, S, L, 2))),
            ("LeaderCanCommit", self._leader_can_commit, pad5(grid(S))),
            ("Restart", self._restart, pad5(grid(S))),
        ]
        self.slot_family = np.concatenate(
            [np.full(c.shape[0], fi, np.int32) for fi, (_, _, c) in enumerate(self.families)]
        )
        self.slot_coords = np.concatenate([c for _, _, c in self.families])
        self.K = int(self.slot_family.shape[0])
        self._slot_family_dev = jnp.asarray(self.slot_family)
        self._slot_coords_dev = jnp.asarray(self.slot_coords)

        # pass-1 expand: dense/tensorized formulation (ops/dense_expand.py);
        # the scalar vmap formulation is kept as the differential reference
        self.dense = DenseExpand(cfg, self.uni, self.fpr)
        if mxu is None:
            mxu = mxu_enabled_by_env()
        self.use_mxu = bool(mxu)
        self.mxu = None
        if self.use_mxu:
            self.mxu = MXUExpand(self)
            self.tables.mxu = self.mxu.tables  # GuardTables extension
        self.device_tables = self._device_tables()
        # the kernels as programs of their own (tests, the audit, the
        # cost probes; the engine's programs trace the methods inline).
        # The legacy guards/materialize kernels are always built: the
        # A/B reference the MXU parity gates and the probe microbench
        # diff.  Off the MXU path the default kernels ARE the legacy ones.
        for name, fn in self._kernels().items():
            setattr(self, name, _kernel_program(self, name, fn.__name__))

    def _kernels(self) -> dict:
        """Kernel name -> the method of this kernel (or view) it runs."""
        k = dict(
            expand=self._expand_dense,
            expand_reference=self._expand,
            expand_guards_legacy=self._expand_guards,
            materialize_legacy=self._materialize,
            materialize_added_legacy=self._materialize_added,
        )
        if self.use_mxu:
            k.update(expand_guards=self._expand_guards_mxu,
                     materialize=self.mxu.materialize,
                     materialize_added=self.mxu.materialize_added)
        else:
            k.update(expand_guards=self._expand_guards,
                     materialize=self._materialize,
                     materialize_added=self._materialize_added)
        return k

    def _device_tables(self, orbit: bool = False) -> _tables.KernelTables:
        return _tables.KernelTables(
            kern=_tables.owned(self),
            guard=_tables.owned(self.tables),
            mxu=_tables.owned(self.mxu.tables) if self.mxu else {},
            dense=_tables.owned(self.dense),
            fpr=_tables.owned(self.fpr),
            orbit=self.fpr.orbit_tables if orbit else None,
        )

    @functools.cached_property
    def orbit_device_tables(self) -> _tables.KernelTables:
        """``device_tables`` plus the orbit-pruning tables (built on
        first use: only orbit checkers read them)."""
        return self._device_tables(orbit=True)

    def bind(self, tables: _tables.KernelTables) -> "SuccessorKernel":
        """This kernel as a traced program sees it: a view whose device
        arrays, and those of its fingerprinter, guard tables, dense and
        MXU expands, are ``tables``' leaves (the program's operand), and
        whose kernels are the plain methods of the view, traced inline.
        """
        fpr = self.fpr.bind(tables.fpr, tables.orbit)
        dense = _tables.view(self.dense, tables.dense, fpr=fpr)
        guard = _tables.view(self.tables, tables.guard)
        kern = _tables.view(self, tables.kern, fpr=fpr, dense=dense,
                            tables=guard)
        if self.mxu is not None:
            guard.mxu = _tables.view(self.mxu.tables, tables.mxu)
            kern.mxu = _tables.view(self.mxu, {}, tables=guard.mxu,
                                    dense=dense)
        for name, fn in kern._kernels().items():
            setattr(kern, name, fn)
        return kern

    def _expand_dense(self, st: RaftState, msum: jnp.ndarray) -> Expansion:
        valid, mult, fpv, fpf, abort = self.dense(st, msum)
        return Expansion(valid, mult & jnp.where(valid, -1, 0), fpv, fpf, abort)

    def _expand_guards(self, st: RaftState):
        """Guards-only pass 1: (valid bool[B,K], mult i32[B,K], abort bool[B]).

        No fingerprint work and no P-wide symmetry fold — the engine's
        late-canonicalization path (engine/bfs.py) fingerprints only the
        compacted candidates from their materialized states."""
        valid, mult, _fpv, _fpf, abort = self.dense(st, None, want_fp=False)
        return valid, mult & jnp.where(valid, -1, 0), abort

    def _expand_guards_mxu(self, st: RaftState):
        """MXU guards-only pass 1: the static guard conjunctions as ONE
        [lanes, feat] x [feat, actions] coefficient matmul + threshold,
        AND'd with the message-side dense terms — same contract and
        bit-identical outputs as ``_expand_guards``."""
        valid, mult, abort = self.mxu.guards(st)
        return valid, mult & jnp.where(valid, -1, 0), abort

    # -- scalar action transcriptions -------------------------------------
    # Each takes (st: RaftState with no batch dim, c: i32[5]) and returns
    #   (valid: bool, mult: i32, child_small: RaftState, added: i32[A],
    #    abort: bool)
    # child_small carries the parent's packed msgs untouched; added lists
    # the message ids this action sends (-1 padding).  All index arithmetic
    # is clamped so invalid slots still compute in-range garbage.

    def _no_add(self):
        return jnp.full((self.A,), -1, I32)

    def _become_candidate(self, st: RaftState, c):
        cfg, uni = self.cfg, self.uni
        S, T = cfg.S, cfg.T
        s = c[0]
        role = _get1(st.role, s)
        valid = (
            (st.election_count.astype(I32) < cfg.max_election)
            & ((role == FOLLOWER) | (role == CANDIDATE))
        )
        new_term = jnp.clip(_get1(st.current_term, s) + 1, 1, T)
        ll = _get1(st.log_len, s)
        llt = jnp.clip(_get2(st.log_term, s, jnp.clip(ll - 1, 0, None)), 0, T - 1)
        peers0 = (s + 1 + jnp.arange(S - 1, dtype=I32)) % S if S > 1 else jnp.zeros((1,), I32)
        ids = uni.encode_votereq(s + 1, peers0 + 1, new_term, ll, llt).astype(I32)
        added = jnp.full((self.A,), -1, I32).at[: ids.shape[0]].set(ids)
        child = st._replace(
            current_term=_set1(st.current_term, s, new_term),
            role=_set1(st.role, s, CANDIDATE),
            voted_for=_set1(st.voted_for, s, s + 1),
            election_count=st.election_count + U8(1),
        )
        return valid, I32(1), child, added, False

    def _update_term_a(self, st: RaftState, c):
        s, t = c[0], c[1] + 1  # term 1..T
        cur = st.current_term.astype(I32)[s]
        mask = self.tables.any_to[s, t - 1]
        hit = _any(st.msgs, mask)
        valid = (t > cur) & hit
        # the "become-follower" mutation compiles the dead BecomeFollower
        # family (Raft.tla:191-231): a Follower adopting a higher term
        # KEEPS its votedFor (FollowerUpdateTerm, Raft.tla:192-197);
        # Candidate/Leader reset it as in the live UpdateTerm
        if "become-follower" in self.cfg.mutations:
            new_vf = jnp.where(
                st.role[s] == FOLLOWER, _get1(st.voted_for, s), 0
            )
        else:
            new_vf = 0
        child = st._replace(
            role=_set1(st.role, s, FOLLOWER),
            current_term=_set1(st.current_term, s, t),
            voted_for=_set1(st.voted_for, s, new_vf),
        )
        return valid, _popcount(st.msgs, mask), child, self._no_add(), False

    def _update_term_b(self, st: RaftState, c):
        s = c[0]
        cur = st.current_term.astype(I32)[s]
        mask = self.tables.aq_to[s, jnp.clip(cur - 1, 0, None)]
        has = (cur >= 1) & _any(st.msgs, mask)
        role = st.role[s]
        valid = has & (role == CANDIDATE)
        if "become-follower" in self.cfg.mutations:
            abort = False  # the dead family has no Assert (Raft.tla:228-231)
        else:
            abort = has & (role == LEADER)  # Assert "split brain", Raft.tla:185
        child = st._replace(role=_set1(st.role, s, FOLLOWER))
        return valid, _popcount(st.msgs, mask), child, self._no_add(), abort

    def _response_vote(self, st: RaftState, c):
        cfg, uni = self.cfg, self.uni
        T = cfg.T
        s, cand = c[0], c[1]
        cur = _get1(st.current_term, s)
        ll = st.log_len.astype(I32)[s]
        llt = jnp.clip(st.log_term.astype(I32)[s, ll - 1], 0, T)
        qual = self.tables.vq_uptodate[cand, s, jnp.clip(cur - 1, 0, None), llt, ll - 1]
        vf = st.voted_for.astype(I32)[s]
        grant = uni.encode_voteresp(s + 1, cand + 1, jnp.clip(cur, 1, None)).astype(I32)
        # the "double-vote" mutation drops the votedFor guard (a classic
        # Raft bug that makes the split-brain Assert reachable — used to
        # exercise the abort path end to end, SURVEY.md §4.4)
        vf_ok = (
            True
            if "double-vote" in cfg.mutations
            else (vf == 0) | (vf == cand + 1)
        )
        valid = (
            (st.role[s] == FOLLOWER)
            & (cur >= 1)
            & (cand != s)
            & vf_ok
            & _any(st.msgs, qual)
            & ~_bit_get(st.msgs, grant)
        )
        child = st._replace(voted_for=_set1(st.voted_for, s, cand + 1))
        added = _set1(self._no_add(), 0, grant)
        return valid, _popcount(st.msgs, qual), child, added, False

    def _become_leader(self, st: RaftState, c):
        cfg = self.cfg
        S = cfg.S
        s = c[0]
        cur = st.current_term.astype(I32)[s]
        votes = _popcount(st.msgs, self.tables.vp_to[s, jnp.clip(cur - 1, 0, None)])
        valid = (st.role[s] == CANDIDATE) & (votes + 1 >= cfg.majority)
        ll = _get1(st.log_len, s).astype(U8)
        ar = jnp.arange(S)
        child = st._replace(
            role=_set1(st.role, s, LEADER),
            match_index=_set_row(st.match_index, s, jnp.where(ar == s, ll, U8(1))),
            next_index=_set_row(st.next_index, s, jnp.full((S,), 0, U8) + ll + U8(1)),
            pending=_set_row(st.pending, s, jnp.zeros((S,), U8)),
        )
        return valid, I32(1), child, self._no_add(), False

    def _client_req(self, st: RaftState, c):
        cfg = self.cfg
        L = cfg.L
        s, v = c[0], c[1]
        ll = _get1(st.log_len, s)
        valid = (st.role[s] == LEADER) & (st.val_sent[v] == 0) & (ll < L)
        # append position: 0-based slot of TLA index ll+1
        at_w = jnp.arange(L, dtype=I32) == jnp.clip(ll, 0, L - 1)
        lt_row = _get_row(st.log_term, s)
        lv_row = _get_row(st.log_val, s)
        child = st._replace(
            val_sent=_set1(st.val_sent, v, 1),  # := FALSE, Raft.tla:237
            log_term=_set_row(
                st.log_term, s,
                jnp.where(at_w, _get1(st.current_term, s), lt_row),
            ),
            log_val=_set_row(st.log_val, s, jnp.where(at_w, v + 1, lv_row)),
            log_len=_set1(st.log_len, s, ll + 1),
            match_index=_set2(st.match_index, s, s, ll + 1),
        )
        return valid, I32(1), child, self._no_add(), False

    def _leader_append(self, st: RaftState, c):
        cfg, uni = self.cfg, self.uni
        T, L = cfg.T, cfg.L
        s, d = c[0], c[1]
        ct = _get1(st.current_term, s)
        ni = _get2(st.next_index, s, d)
        ll = _get1(st.log_len, s)
        lt_row = _get_row(st.log_term, s)
        lv_row = _get_row(st.log_val, s)
        pli = jnp.clip(ni - 1, 1, L)
        oh_prev = jnp.arange(L, dtype=I32) == jnp.clip(ni - 2, 0, L - 1)
        plt = jnp.clip((oh_prev * lt_row).sum(dtype=I32), 0, T)
        has_entry = ni <= ll
        oh_epos = jnp.arange(L, dtype=I32) == jnp.clip(ni - 1, 0, L - 1)
        ecode = jnp.where(
            has_entry,
            self.uni.entry_code(
                jnp.clip((oh_epos * lt_row).sum(dtype=I32), 1, T),
                jnp.clip((oh_epos * lv_row).sum(dtype=I32), 1, cfg.V),
            ),
            0,
        )
        mid = uni.encode_appendreq(
            s + 1, d + 1, jnp.clip(ct, 1, T), pli, plt, ecode,
            _get1(st.commit_index, s),
        ).astype(I32)
        valid = (
            (st.role[s] == LEADER)
            & (d != s)
            & (ni <= ll + 1)
            & (st.pending[s, d] == 0)
            & ~_bit_get(st.msgs, mid)
        )
        child = st._replace(pending=_set2(st.pending, s, d, 1))
        return valid, I32(1), child, _set1(self._no_add(), 0, mid), False

    def _follower_accept(self, st: RaftState, c):
        cfg, uni = self.cfg, self.uni
        T, L, V = cfg.T, cfg.L, cfg.V
        s, src, pli, e, lc = c[0], c[1], c[2] + 1, c[3], c[4] + 1
        cur = _get1(st.current_term, s)
        ll = _get1(st.log_len, s)
        lt = _get_row(st.log_term, s)
        lv = _get_row(st.log_val, s)
        ar = jnp.arange(L, dtype=I32)
        oh_prev = ar == jnp.clip(pli - 1, 0, L - 1)
        plt = jnp.clip((oh_prev * lt).sum(dtype=I32), 0, T)
        mid = uni.encode_appendreq(
            src + 1, s + 1, jnp.clip(cur, 1, T), pli, plt, e, lc
        ).astype(I32)
        log_match = pli <= ll  # plt equals the log term by construction
        valid = (
            (st.role[s] == FOLLOWER) & (cur >= 1) & (src != s) & log_match
            & _bit_get(st.msgs, mid)
        )
        el = (e > 0).astype(I32)
        eterm = jnp.where(el == 1, (e - 1) // V + 1, 0)
        eval_ = jnp.where(el == 1, (e - 1) % V + 1, 0)
        new_len = pli + el
        append_new = new_len > ll
        pos = jnp.clip(pli, 0, L - 1)  # 0-based slot of the carried entry
        oh_pos = ar == pos
        conflict = (
            (el == 1)
            & (pli < ll)
            & (
                ((oh_pos * lt).sum(dtype=I32) != eterm)
                | ((oh_pos * lv).sum(dtype=I32) != eval_)
            )
        )
        updated = append_new | conflict
        keep = ar < pli
        at_entry = oh_pos & (el == 1)
        new_lt = jnp.where(keep, lt, 0)
        new_lt = jnp.where(at_entry, eterm, new_lt)
        new_lv = jnp.where(keep, lv, 0)
        new_lv = jnp.where(at_entry, eval_, new_lv)
        old_ci = _get1(st.commit_index, s)
        new_ci = jnp.maximum(old_ci, jnp.minimum(lc, new_len))
        child = st._replace(
            log_term=_set_row(st.log_term, s, jnp.where(updated, new_lt, lt)),
            log_val=_set_row(st.log_val, s, jnp.where(updated, new_lv, lv)),
            log_len=_set1(st.log_len, s, jnp.where(updated, new_len, ll)),
            commit_index=_set1(st.commit_index, s, new_ci),
        )
        resp = uni.encode_appendresp(
            s + 1, src + 1, jnp.clip(cur, 1, T), jnp.clip(pli + el, 1, L), 1
        ).astype(I32)
        if "legacy-append" in cfg.mutations:
            # the dead monolithic FollowerAppendEntry gates its accept on
            # resp \notin msgs \/ commit-advance (Raft.tla:347-348); the
            # live FollowerAcceptEntry has no send-guard
            valid = valid & (~_bit_get(st.msgs, resp) | (new_ci > old_ci))
        return valid, I32(1), child, _set1(self._no_add(), 0, resp), False

    def _follower_reject(self, st: RaftState, c):
        cfg, uni = self.cfg, self.uni
        T, L = cfg.T, cfg.L
        s, src, pli = c[0], c[1], c[2] + 1
        cur = _get1(st.current_term, s)
        ll = st.log_len.astype(I32)[s]
        tix = jnp.clip(cur - 1, 0, None)
        block = self.tables.aq_block[src, s, tix, pli - 1]
        match_plt = jnp.clip(st.log_term.astype(I32)[s, jnp.clip(pli - 1, 0, L - 1)], 0, T)
        sub = self.tables.aq_plt[src, s, tix, pli - 1, match_plt]
        qual = jnp.where(pli <= ll, block & ~sub, block)
        # the dead FollowerAppendEntry's reject carries prevLogIndex - 1
        # (Raft.tla:364) vs the live :314's unchanged value
        rej_pli = pli - 1 if "legacy-append" in cfg.mutations else pli
        rej = uni.encode_appendresp(
            s + 1, src + 1, jnp.clip(cur, 1, T), rej_pli, 0
        ).astype(I32)
        valid = (
            (st.role[s] == FOLLOWER) & (cur >= 1) & (src != s)
            & _any(st.msgs, qual) & ~_bit_get(st.msgs, rej)
        )
        return valid, _popcount(st.msgs, qual), st, _set1(self._no_add(), 0, rej), False

    def _handle_append_resp(self, st: RaftState, c):
        cfg, uni = self.cfg, self.uni
        T = cfg.T
        s, src, pli, sc = c[0], c[1], c[2] + 1, c[3]
        cur = _get1(st.current_term, s)
        mid = uni.encode_appendresp(
            src + 1, s + 1, jnp.clip(cur, 1, T), pli, sc
        ).astype(I32)
        mi = _get2(st.match_index, s, src)
        ni = _get2(st.next_index, s, src)
        base = (
            (st.role[s] == LEADER) & (cur >= 1) & (src != s)
            & (st.pending[s, src] == 1) & _bit_get(st.msgs, mid)
        )
        ok = jnp.where(sc == 1, mi < pli, (pli + 1 == ni) & (pli > mi))
        valid = base & ok
        child = st._replace(
            match_index=_set2(st.match_index, s, src, jnp.where(sc == 1, pli, mi)),
            next_index=_set2(st.next_index, s, src, pli + sc),
            pending=_set2(st.pending, s, src, 0),
        )
        return valid, I32(1), child, self._no_add(), False

    def _leader_can_commit(self, st: RaftState, c):
        cfg = self.cfg
        S = cfg.S
        s = c[0]
        # Median(F), Raft.tla:70-75 (or the median-bug mutation): the
        # median_index-th order statistic via rank-select, no sort op
        row = _get_row(st.match_index, s)
        ar = jnp.arange(S)
        pos = (row[None, :] < row[:, None]).sum(-1, dtype=I32) + (
            (row[None, :] == row[:, None]) & (ar[None, :] < ar[:, None])
        ).sum(-1, dtype=I32)
        med = (row * (pos == cfg.median_index)).sum(dtype=I32)
        valid = (st.role[s] == LEADER) & (med > _get1(st.commit_index, s))
        child = st._replace(commit_index=_set1(st.commit_index, s, med))
        return valid, I32(1), child, self._no_add(), False

    def _restart(self, st: RaftState, c):
        cfg = self.cfg
        s = c[0]
        valid = (st.role[s] == LEADER) & (
            st.restart_count.astype(I32) < cfg.max_restart
        )
        child = st._replace(
            role=_set1(st.role, s, FOLLOWER),
            restart_count=st.restart_count + U8(1),
        )
        return valid, I32(1), child, self._no_add(), False

    # -- pass 1: expand + fingerprint -------------------------------------

    def _family_expand(self, fn, coords, st: RaftState, msum: jnp.ndarray):
        """One family for one state: vmap over the witness grid."""

        def one(cw):
            valid, mult, child, added, abort = fn(st, cw)
            feats = self.fpr.spec.features(child)
            # Union semantics: a message already present contributes nothing
            # (relevant for FollowerAcceptEntry's un-guarded response).
            live = (added >= 0) & ~jax.vmap(lambda i: _bit_get(st.msgs, i))(added)
            fv, ff = self.fpr.child_fingerprints(feats, msum, added, live)
            return valid, mult, fv, ff, abort

        return jax.vmap(one)(coords)

    def _expand(self, st: RaftState, msum: jnp.ndarray) -> Expansion:
        """Batched fan-out. st leaves have leading dim B; msum u32[B, P, C]."""

        def per_state(st1, msum1):
            outs = [
                self._family_expand(fn, jnp.asarray(coords), st1, msum1)
                for _, fn, coords in self.families
            ]
            valid = jnp.concatenate([o[0] for o in outs])
            mult = jnp.concatenate([o[1] for o in outs])
            fv = jnp.concatenate([o[2] for o in outs])
            ff = jnp.concatenate([o[3] for o in outs])
            abort = jnp.any(jnp.stack([jnp.any(o[4]) for o in outs]))
            return valid, mult, fv, ff, abort

        valid, mult, fv, ff, abort = jax.vmap(per_state)(st, msum)
        return Expansion(valid, mult & jnp.where(valid, -1, 0), fv, ff, abort)

    # -- pass 2: materialize surviving slots ------------------------------

    def _materialize_one(self, st: RaftState, slot: jnp.ndarray):
        # slot -> (family, coords) via one-hot contraction over the K-row
        # constants (a per-lane gather would hit the slow-gather path)
        oh_slot = (jnp.arange(self.K) == slot).astype(I32)
        fam = (oh_slot * self._slot_family_dev).sum(dtype=I32)
        coords = (oh_slot[:, None] * self._slot_coords_dev).sum(0, dtype=I32)

        def mk(fn):
            def branch(args):
                st1, cw = args
                _valid, _mult, child, added, _abort = fn(st1, cw)
                # set the added-message bits (SendMsg union, Raft.tla:43-45)
                msgs = child.msgs

                def set_bit(m, mid):
                    live = mid >= 0
                    w = jnp.clip(mid, 0, None) >> 5
                    bit = jnp.where(live, U32(1) << (mid & 31).astype(U32), U32(0))
                    word_hit = jnp.arange(m.shape[0], dtype=I32) == w
                    return jnp.where(word_hit, m | bit, m)

                for a in range(self.A):
                    msgs = set_bit(msgs, added[a])
                return child._replace(msgs=msgs), added

            return branch

        branches = [mk(fn) for _, fn, _ in self.families]
        return jax.lax.switch(fam, branches, (st, coords))

    def _materialize(self, parents: RaftState, slots: jnp.ndarray) -> RaftState:
        """parents: leaves with leading dim G (already gathered); slots i32[G]."""
        return jax.vmap(self._materialize_one)(parents, slots)[0]

    def _materialize_added(self, parents: RaftState, slots: jnp.ndarray):
        """As ``materialize``, but also returns the sent message ids
        (``added`` i32[G, A], -1-padded) so callers holding the parents'
        sparse msg-id lists can build the children's lists by sorted
        insertion instead of recovering them from the packed bitmask with
        a per-row top_k over the whole message universe (the measured
        dominator of the materialize pass, docs/PERF.md round 5)."""
        return jax.vmap(self._materialize_one)(parents, slots)


def _kernel_program(kern: SuccessorKernel, name: str, fn_name: str):
    """The kernel ``name`` as a program over the kernel's tables."""

    def run(tables, *args):
        return getattr(kern.bind(tables), name)(*args)

    run.__name__ = run.__qualname__ = fn_name
    return _tables.TabledProgram(run, kern.device_tables)


@functools.lru_cache(maxsize=8)
def _get_kernel_cached(cfg: RaftConfig, mxu: bool) -> SuccessorKernel:
    return SuccessorKernel(cfg, mxu=mxu)


def get_kernel(cfg: RaftConfig, mxu: bool | None = None) -> SuccessorKernel:
    """Kernel cache, keyed (cfg, mxu).  ``mxu=None`` resolves the env
    default HERE (not inside the cached call) so tests flipping
    TLA_RAFT_MXU never see a stale kernel."""
    if mxu is None:
        mxu = mxu_enabled_by_env()
    return _get_kernel_cached(cfg, bool(mxu))
