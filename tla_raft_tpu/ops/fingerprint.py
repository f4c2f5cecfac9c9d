"""Canonical state fingerprints as MXU matmuls.

TLC fingerprints each state with a 64-bit hash of the ``VIEW`` projection
(Raft.cfg:26 -> Raft.tla:38), canonicalized under ``SYMMETRY symmServers``
(Raft.cfg:24 -> Raft.tla:21) by taking the minimum fingerprint over all
|Servers|! server permutations.  This module re-derives that capability as
a TPU-native computation:

* The state is flattened to a small integer **feature vector** (the 8 view
  variables, plus the 4 aux variables for the full-state channel;
  ``votedFor`` is one-hot expanded because its *values* are server-valued
  and permute with the symmetry group).
* The hash is **multilinear**: ``h = sum_e feat[e] * C[e] (mod 2^32)`` with
  random 32-bit coefficients — a classic universal hash family, so any two
  distinct feature vectors collide with probability 2^-32 per channel
  (2^-64 over the paired channels that form the u64 fingerprint).
* Applying a server permutation to the state permutes feature *positions*
  (the one-hot trick linearizes the votedFor value remap), so the permuted
  hash is the same matmul against **permutation-folded coefficient
  tables** — no per-permutation gather of the data, just extra columns.
* The message set's contribution is a set-hash ``sum_{m in msgs} G[p][m]``
  where ``G[p]`` is the coefficient table pre-composed with the message-ID
  permutation (ops/msg_universe.py ``perm_table``).  For a frontier state
  this is one ``bits @ G`` matmul; for a successor it is the parent's sum
  plus the few added-message coefficients (messages are only ever *added*:
  SendMsg/SendMultiMsgs are set union, Raft.tla:43-45).
* Coefficients are decomposed into 4 signed-byte planes so the whole hash
  runs as int8 matmuls with int32 accumulation (the MXU-native integer
  path); the signed-byte reinterpretation is a fixed linear transform of
  the coefficient table, so the result is still an exact multilinear hash,
  and the numpy reference path below reproduces it bit-for-bit.

Two fingerprint channels are produced per state:

* ``fp_view``  — hash of the VIEW projection (dedup key, TLC semantics),
* ``fp_full``  — hash of all 12 variables (aux included).  Used as the
  deterministic tiebreak when several same-view successors are generated
  in one BFS level: the representative kept for expansion is the one with
  the minimal ``fp_full``.  TLC leaves this choice to thread timing; we
  make it canonical so runs (and the Python oracle) are reproducible.
"""

from __future__ import annotations

import functools

import jax

# 64-bit fingerprints (the TLC FPSet analog) flow through sort/searchsorted/
# all_to_all as single u64 lanes; enable x64 before any kernel is traced.
# All kernel dtypes are explicit, so default-dtype widening does not apply.
jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp
import numpy as np

from ..config import RaftConfig
from .msg_universe import MsgUniverse, get_universe
from . import tables as _tables

_SEED = 0x7C3A_11E5
FP_SENTINEL = np.uint64(0xFFFFFFFFFFFFFFFF)


class FeatureSpec:
    """Flattening of the 12 state variables into one small-int vector.

    Layout (all slices static per config): currentTerm[S], role[S],
    logTerm[S*L], logVal[S*L], logLen[S], matchIndex[S*S], nextIndex[S*S],
    commitIndex[S], votedFor one-hot [S*(S+1)]  — the VIEW prefix — then
    electionCount[1], restartCount[1], pendingResponse[S*S], valSent[V].
    """

    def __init__(self, cfg: RaftConfig):
        self.cfg = cfg
        S, L, V = cfg.S, cfg.L, cfg.V
        off = 0

        def take(n: int) -> slice:
            nonlocal off
            sl = slice(off, off + n)
            off += n
            return sl

        self.ct = take(S)
        self.role = take(S)
        self.lt = take(S * L)
        self.lv = take(S * L)
        self.ll = take(S)
        self.mi = take(S * S)
        self.ni = take(S * S)
        self.ci = take(S)
        self.vf_oh = take(S * (S + 1))
        self.F_view = off
        self.ec = take(1)
        self.rc = take(1)
        self.pend = take(S * S)
        self.vs = take(V)
        self.F = off

    # -- extraction (jnp; works for any leading batch dims) ----------------

    def features(self, st) -> jnp.ndarray:
        """RaftState (arbitrary leading dims on each leaf) -> i8[..., F]."""
        S, L, V = self.cfg.S, self.cfg.L, self.cfg.V
        lead = st.voted_for.shape[:-1]
        flat = lambda x, n: x.reshape(*lead, n).astype(jnp.int8)
        oh = (st.voted_for[..., :, None] == jnp.arange(S + 1, dtype=st.voted_for.dtype)).astype(
            jnp.int8
        )
        return jnp.concatenate(
            [
                flat(st.current_term, S),
                flat(st.role, S),
                flat(st.log_term, S * L),
                flat(st.log_val, S * L),
                flat(st.log_len, S),
                flat(st.match_index, S * S),
                flat(st.next_index, S * S),
                flat(st.commit_index, S),
                oh.reshape(*lead, S * (S + 1)),
                flat(st.election_count[..., None], 1),
                flat(st.restart_count[..., None], 1),
                flat(st.pending, S * S),
                flat(st.val_sent, V),
            ],
            axis=-1,
        )

    def features_np(self, arrs: dict) -> np.ndarray:
        """numpy variant over a dict of per-field arrays (oracle bridge)."""
        S, L, V = self.cfg.S, self.cfg.L, self.cfg.V
        lead = arrs["voted_for"].shape[:-1]
        flat = lambda x, n: np.asarray(x).reshape(*lead, n).astype(np.int64)
        oh = (np.asarray(arrs["voted_for"])[..., :, None] == np.arange(S + 1)).astype(np.int64)
        return np.concatenate(
            [
                flat(arrs["current_term"], S),
                flat(arrs["role"], S),
                flat(arrs["log_term"], S * L),
                flat(arrs["log_val"], S * L),
                flat(arrs["log_len"], S),
                flat(arrs["match_index"], S * S),
                flat(arrs["next_index"], S * S),
                flat(arrs["commit_index"], S),
                oh.reshape(*lead, S * (S + 1)),
                flat(np.asarray(arrs["election_count"])[..., None], 1),
                flat(np.asarray(arrs["restart_count"])[..., None], 1),
                flat(arrs["pending"], S * S),
                flat(arrs["val_sent"], V),
            ],
            axis=-1,
        )

    # -- symmetry: feature-position permutation ----------------------------

    def perm_source_indices(self, p: tuple[int, ...]) -> np.ndarray:
        """pi[d] = source feature index that lands at position d under perm p.

        p maps server s -> p[s-1] (1-based images, Raft.tla:21).  Per-server
        structures move to permuted slots; matrix fields permute both axes;
        the votedFor one-hot columns permute through p as well (the one-hot
        trick that keeps the value remap linear).
        """
        cfg = self.cfg
        S, L, V = cfg.S, cfg.L, cfg.V
        inv = np.empty(S, np.int64)  # inv[i] = 0-based preimage of server i+1
        for s0 in range(S):
            inv[p[s0] - 1] = s0
        src = np.empty(self.F, np.int64)
        ar = np.arange
        for sl in (self.ct, self.role, self.ll, self.ci):
            src[sl] = sl.start + inv
        for sl in (self.lt, self.lv):
            src[sl] = sl.start + (inv[:, None] * L + ar(L)[None, :]).ravel()
        for sl in (self.mi, self.ni, self.pend):
            src[sl] = sl.start + (inv[:, None] * S + inv[None, :]).ravel()
        # target one-hot (i, w) <- source (inv[i], 0 if w==0 else inv[w-1]+1)
        wmap = np.concatenate([[0], inv + 1])
        src[self.vf_oh] = self.vf_oh.start + (inv[:, None] * (S + 1) + wmap[None, :]).ravel()
        src[self.ec] = self.ec.start
        src[self.rc] = self.rc.start
        src[self.vs] = self.vs.start + ar(V)
        return src


def _u32_to_i8_planes(c: np.ndarray) -> np.ndarray:
    """u32[..., n] -> i8[..., n, 4] signed byte planes (LSB first)."""
    b = np.stack([(c >> (8 * k)) & 0xFF for k in range(4)], axis=-1)
    return b.astype(np.uint8).astype(np.int8)


# -- computed message coefficients -----------------------------------------
# The message-set hash coefficient for (channel c, message id m) is a
# *computed* u32, not a stored table: G[c, m] = mix32(m*PHI + c*PHI2 + seed).
# The full-state path still materializes G as a host-built matrix for the
# bits @ G matmul, but the successor path evaluates the coefficient
# arithmetically per added message — a handful of VPU ops instead of a
# row gather from a [M+1, P, chan] table, which XLA:TPU lowers to
# full-table scans per lane (measured ~500KB of reads per fan-out lane,
# ~750GB per chunk; see docs/PERF.md).

_PHI = 0x9E3779B9
_PHI2 = 0x85EBCA6B


def _mix32(x):
    """splitmix32-style finalizer; identical semantics for np and jnp."""
    xp = jnp if isinstance(x, jnp.ndarray) else np
    u = xp.uint32
    x = x.astype(u)
    x = x ^ (x >> u(16))
    x = x * u(0x7FEB352D)
    x = x ^ (x >> u(15))
    x = x * u(0x846CA68B)
    x = x ^ (x >> u(16))
    return x


def _eff_u32(x):
    """The signed-byte-plane linearization of a u32 coefficient.

    Equals _effective_u32 (byte k >= 128 shifts the coefficient by
    -2^(8k+8); the k=3 term wraps to zero mod 2^32) but computable
    in-kernel without a table.
    """
    xp = jnp if isinstance(x, jnp.ndarray) else np
    u = xp.uint32
    return (
        x
        - (((x >> u(7)) & u(1)) << u(8))
        - (((x >> u(15)) & u(1)) << u(16))
        - (((x >> u(23)) & u(1)) << u(24))
    )


def _combine_planes_u32(planes) -> "jnp.ndarray | np.ndarray":
    """i32[..., 4] plane sums -> u32[...] hash (shared jnp/np semantics)."""
    xp = jnp if isinstance(planes, jnp.ndarray) else np
    h = planes[..., 0].astype(xp.uint32)
    for k in range(1, 4):
        h = h + (planes[..., k].astype(xp.uint32) << xp.uint32(8 * k))
    return h


def _effective_u32(c: np.ndarray) -> np.ndarray:
    """The coefficient the signed-byte-plane matmul *actually* applies.

    Reinterpreting each byte plane as int8 shifts coefficients by fixed
    multiples of 256 per plane; the hash stays multilinear but with this
    transformed table. Delta-gather paths must use the same effective
    values to stay bit-compatible with the matmul path.
    """
    planes = _u32_to_i8_planes(c).astype(np.int64)
    return _combine_planes_u32(planes)


class Fingerprinter:
    """Permutation-folded hash tables + the fingerprint kernels for one cfg.

    Channels 0,1 -> fp_view (aux-variable coefficients zeroed, matching the
    VIEW projection Raft.tla:38); channels 2,3 -> fp_full (all 12 vars).
    When ``cfg.use_view`` is False the view channels still hash the full
    vector (TLC without VIEW fingerprints the complete state).
    """

    N_CHAN = 4

    def __init__(
        self, cfg: RaftConfig, seed: int = _SEED, force_factored: bool | None = None
    ):
        self._force_factored = force_factored
        self.cfg = cfg
        self.uni: MsgUniverse = get_universe(cfg)
        self.spec = FeatureSpec(cfg)
        F, M = self.spec.F, self.uni.M
        self.perms = cfg.server_perms()
        P = len(self.perms)
        self.P = P

        rng = np.random.default_rng(seed)
        self.seed = np.uint32(seed)
        C = rng.integers(0, 1 << 32, size=(self.N_CHAN, F), dtype=np.uint32)
        if cfg.use_view:
            C[0:2, self.spec.F_view :] = 0  # aux vars excluded from view hash

        # Fold every permutation into the feature-coefficient table
        # (Cp is [P, chan, F] — 22 MB even at S=7, always affordable).
        Cp = np.empty((P, self.N_CHAN, F), np.uint32)
        for pi, p in enumerate(self.perms):
            pi_src = self.spec.perm_source_indices(p)
            # h_p(v) = sum_d C[d] v[pi_src[d]] = sum_e Cp[e] v[e]
            Cp[pi][:, pi_src] = C

        # Device tables. Plane matmul layout: columns = (P, chan, byte).
        self.C_planes = jnp.asarray(
            _u32_to_i8_planes(Cp).transpose(2, 0, 1, 3).reshape(F, P * self.N_CHAN * 4)
        )

        # Message-set hash: the permutation-folded table Gp is [P, chan, M]
        # u32 — fine at small symmetry groups (S=3: 0.5 MB, S=5: 30 MB) but
        # 2.7 GB at S=7 (P=5040).  Above a budget, switch to the pair-block
        # factorization (docs/SCALING.md): a server permutation moves ONLY
        # the (src,dst)-pair digit of a message id, so the per-permutation
        # set hash factors through per-type [stride, NP, chan] tables plus
        # one exact one-hot P-fold matmul — nothing P-sized ever crosses M.
        self.factored_msgs = P * self.N_CHAN * M * 4 > (64 << 20)
        if self._force_factored is not None:
            self.factored_msgs = self._force_factored
        if not self.factored_msgs:
            # message coefficients are COMPUTED (see _mix32 above) so
            # successor kernels can evaluate them arithmetically;
            # materialize the matrix host-side for the full-state matmul
            # path.  raw_msg_coef is the single definition both paths share.
            G = np.moveaxis(self.raw_msg_coef(np.arange(M, dtype=np.uint32)), -1, 0)
            Gp = np.empty((P, self.N_CHAN, M), np.uint32)
            pt = self.uni.perm_table  # int32[P, M]: message id under each perm
            for pi in range(P):
                Gp[pi] = G[:, pt[pi]]
            self.G_planes = jnp.asarray(
                _u32_to_i8_planes(Gp).transpose(2, 0, 1, 3).reshape(M, P * self.N_CHAN * 4)
            )
            self._Gp_np = Gp
        else:
            self._build_pair_block_tables()
        # tiny constants for the arithmetic delta path
        self._pair_perm = jnp.asarray(self.uni.pair_perm_table)  # [P, S(S-1)]
        self._type_offsets = self.uni.type_offsets
        self._type_strides = self.uni.type_strides
        # Host copies for the numpy reference path.
        self._Cp_np = Cp

    def _build_pair_block_tables(self):
        """Per-type pair-block coefficient tables + the P-fold one-hot.

        For type t, every id is ``off_t + q*stride_t + rest`` and a server
        permutation p maps it to ``off_t + PPERM[p,q]*stride_t + rest``.
        ``Gt[t][rest, q'*chan*4 + ...]`` holds the i8 planes of the
        coefficient at pair digit q'; the state's per-(q,q') partial sums
        R then fold over permutations with a [P, NP*NP] one-hot matmul
        whose integer values stay < 2^24, so it runs exactly in f32 on
        the MXU (see _msg_hash_factored)."""
        uni = self.uni
        # Exactness precondition of the f32 fold: every folded partial is a
        # sum of at most M plane bytes (|plane| <= 127), so it stays exact
        # in f32 only while 127*M < 2^24.  Current universes are far below
        # (S=7 full M=33,768 -> 4.3M) but a future scale dial must fail
        # loudly here, not round silently into wrong canonical fingerprints.
        if 127 * uni.M >= (1 << 24):
            raise ValueError(
                f"factored message hash exactness bound violated: "
                f"127*M = {127 * uni.M} >= 2^24; use the monolithic path "
                f"(force_factored=False) or add an int fold for this size"
            )
        NP = uni.S * (uni.S - 1)
        self._NP = NP
        self._Gt_planes = []
        for off, stride in zip(uni.type_offsets, uni.type_strides):
            q = np.arange(NP, dtype=np.uint32)[:, None]
            r = np.arange(stride, dtype=np.uint32)[None, :]
            ids = np.uint32(off) + q * np.uint32(stride) + r  # [NP, stride]
            coef = self.raw_msg_coef(ids)  # u32 [NP, stride, chan]
            planes = _u32_to_i8_planes(coef)  # i8 [NP, stride, chan, 4]
            self._Gt_planes.append(
                jnp.asarray(
                    planes.transpose(1, 0, 2, 3).reshape(
                        stride, NP * self.N_CHAN * 4
                    )
                )
            )
        pp = self.uni.pair_perm_table  # [P, NP]
        oh = np.zeros((self.P, NP * NP), np.float32)
        rows = np.repeat(np.arange(self.P), NP)
        cols = (np.tile(np.arange(NP), self.P) * NP + pp.ravel())
        oh[rows, cols] = 1.0
        self._ppfold = jnp.asarray(oh)  # f32 [P, NP*NP]

    def bind(self, arrays: dict, orbit: dict | None = None) -> "Fingerprinter":
        """A view reading ``arrays`` (and ``orbit``'s canonical-relabel
        tables) in place of its own device tables (``ops/tables.py``)."""
        extra = {} if orbit is None else {"orbit_tables": orbit}
        return _tables.view(self, arrays, **extra)

    # -- the ONE definition of the computed message coefficient ------------

    def raw_msg_coef(self, ids):
        """Message id(s) -> raw u32 coefficient per channel [..., chan].

        ``G[c, m] = mix32(m*PHI + c*PHI2 + seed)`` — identical semantics
        for numpy (host matrix build) and jnp (kernel arithmetic) inputs.
        """
        xp = jnp if isinstance(ids, jnp.ndarray) else np
        chan_c = (
            xp.arange(self.N_CHAN, dtype=xp.uint32) * xp.uint32(_PHI2)
            + xp.uint32(self.seed)
        )
        return _mix32(ids.astype(xp.uint32)[..., None] * xp.uint32(_PHI) + chan_c)

    def msg_coef_eff(self, ids):
        """Byte-plane-linearized coefficient (what the delta paths add)."""
        return _eff_u32(self.raw_msg_coef(ids))

    # -- jnp kernels -------------------------------------------------------

    def _plane_matmul(self, x_i8: jnp.ndarray, table: jnp.ndarray) -> jnp.ndarray:
        if jax.default_backend() == "cpu":
            # XLA:CPU miscompiles the fused int8-dot -> byte-combine ->
            # reduce chain (invalid LLVM IR "add i32, i8"); an i32 dot is
            # bit-identical and sidesteps it.  TPU keeps the int8 MXU path.
            out = jnp.dot(x_i8.astype(jnp.int32), table.astype(jnp.int32))
        else:
            out = jnp.dot(x_i8, table, preferred_element_type=jnp.int32)
        return _combine_planes_u32(out.reshape(*x_i8.shape[:-1], self.P, self.N_CHAN, 4))

    def feat_hash(self, feats: jnp.ndarray) -> jnp.ndarray:
        """i8[..., F] -> u32[..., P, chan]."""
        return self._plane_matmul(feats, self.C_planes)

    def unpack_bits(self, packed: jnp.ndarray) -> jnp.ndarray:
        """u32[..., n_words] -> i8[..., M]."""
        uni = self.uni
        bits = (packed[..., :, None] >> jnp.arange(32, dtype=jnp.uint32)) & jnp.uint32(1)
        return bits.reshape(*packed.shape[:-1], uni.n_words * 32)[..., : uni.M].astype(jnp.int8)

    def msg_hash(self, packed: jnp.ndarray) -> jnp.ndarray:
        """packed u32[..., n_words] -> set-hash u32[..., P, chan]."""
        if self.factored_msgs:
            return self._msg_hash_factored(packed)
        return self._plane_matmul(self.unpack_bits(packed), self.G_planes)

    def _msg_hash_factored(self, packed: jnp.ndarray) -> jnp.ndarray:
        """Pair-block set hash: per-type partial sums + one P-fold matmul.

        Bit-identical to the monolithic ``bits @ G_planes`` path (the
        plane combine is linear mod 2^32 and commutes with the fold; the
        f32 fold matmul is exact because every partial sum and every
        folded sum stays below 2^24 — |plane| <= 127, sum of strides
        <= ~10^3, NP <= 42 terms per output)."""
        uni, NP, NC = self.uni, self._NP, self.N_CHAN
        bits = self.unpack_bits(packed)  # i8 [..., M]
        lead = bits.shape[:-1]
        R = None
        for (off, stride), Gt in zip(
            zip(uni.type_offsets, uni.type_strides), self._Gt_planes
        ):
            bt = bits[..., off : off + NP * stride].reshape(*lead, NP, stride)
            if jax.default_backend() == "cpu":
                Rt = jnp.dot(bt.astype(jnp.int32), Gt.astype(jnp.int32))
            else:
                Rt = jnp.dot(bt, Gt, preferred_element_type=jnp.int32)
            R = Rt if R is None else R + Rt  # [..., NP(q), NP(q')*chan*4]
        A = R.reshape(*lead, NP * NP, NC * 4).astype(jnp.float32)
        # precision=HIGHEST: the exactness argument needs true f32
        # accumulation — default matmul precision on TPU is bf16 passes,
        # which would silently round the >2^8 partial sums
        folded = jnp.einsum(
            "...mx,pm->...px", A, self._ppfold,
            precision=jax.lax.Precision.HIGHEST,
        )
        planes = jnp.round(folded).astype(jnp.int32)
        return _combine_planes_u32(planes.reshape(*lead, self.P, NC, 4))

    def delta_hash(self, ids: jnp.ndarray, live: jnp.ndarray) -> jnp.ndarray:
        """Added-message contribution: ids i32[..., A], live bool[..., A].

        Dead slots (live=False) contribute zero — used both for -1 padding
        and for re-sent messages already present in the parent set (set
        union adds nothing; see FollowerAcceptEntry, Raft.tla:292-295).

        Entirely arithmetic: the permuted message id is reconstructed from
        the mixed-radix layout (only the (src, dst) pair digit moves under
        a server permutation) and the coefficient is the computed
        ``mix32`` hash — no table gathers on the per-lane hot path.
        """
        i32, u32 = jnp.int32, jnp.uint32
        id0 = jnp.clip(ids, 0, self.uni.M - 1).astype(i32)  # [..., A]
        # message type from the offset ranges (branchless)
        offs = self._type_offsets
        t = (
            (id0 >= offs[1]).astype(i32)
            + (id0 >= offs[2]).astype(i32)
            + (id0 >= offs[3]).astype(i32)
        )
        # per-type decode with constant divisors, then select
        pair = jnp.zeros_like(id0)
        rest = jnp.zeros_like(id0)
        off = jnp.zeros_like(id0)
        for k, (o, s) in enumerate(zip(offs, self._type_strides)):
            qk = id0 - i32(o)
            pk = qk // i32(s)
            sel = t == k
            pair = jnp.where(sel, pk, pair)
            rest = jnp.where(sel, qk - pk * i32(s), rest)
            off = jnp.where(sel, i32(o), off)
        # permuted pair digit via a one-hot contraction with the tiny
        # [P, S(S-1)] map (NP <= 42 even at 7 servers)
        NP = self._pair_perm.shape[1]
        onehot = (pair[..., None] == jnp.arange(NP, dtype=i32)).astype(i32)
        pair_p = jnp.einsum(
            "...n,pn->...p", onehot, self._pair_perm
        )  # [..., A, P]
        stride = jnp.zeros_like(id0)
        for k, s in enumerate(self._type_strides):
            stride = jnp.where(t == k, i32(s), stride)
        id_p = off[..., None] + pair_p * stride[..., None] + rest[..., None]
        g = self.msg_coef_eff(id_p)  # [..., A, P, chan]
        return jnp.where(
            live[..., None, None], g, u32(0)
        ).sum(axis=-3, dtype=jnp.uint32)

    @staticmethod
    def finalize(h: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
        """u32[..., P, chan] -> (fp_view u64[...], fp_full u64[...]).

        Each fingerprint is the minimum over the symmetry group of the
        64-bit pair formed by its two hash channels — TLC's min-fingerprint
        symmetry normalization re-expressed on the hash itself.
        """
        h64 = h.astype(jnp.uint64)
        view = (h64[..., 0] << jnp.uint64(32)) | h64[..., 1]
        full = (h64[..., 2] << jnp.uint64(32)) | h64[..., 3]
        return view.min(axis=-1), full.min(axis=-1)

    def state_fingerprints(self, st) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
        """Full-state path: (fp_view u64[N], fp_full u64[N], msum u32[N,P,chan]).

        ``msum`` (the message-set hash partial) is returned so successor
        fingerprints can be computed incrementally from it.
        """
        # the name groups the P-fold's device ops in a profile
        with jax.named_scope("raft.fp.fold"):
            feats = self.spec.features(st)
            msum = self.msg_hash(st.msgs)
            fp_view, fp_full = self.finalize(self.feat_hash(feats) + msum)
            return fp_view, fp_full, msum

    def child_fingerprints(
        self, feats: jnp.ndarray, parent_msum: jnp.ndarray, ids: jnp.ndarray, live: jnp.ndarray
    ) -> tuple[jnp.ndarray, jnp.ndarray]:
        """Successor path: features are fresh, message hash is incremental.

        feats i8[..., F]; parent_msum u32[..., P, chan] (broadcastable);
        ids/live [..., A] added-message ids and liveness.
        """
        h = self.feat_hash(feats) + parent_msum + self.delta_hash(ids, live)
        return self.finalize(h)

    # -- orbit pruning (canonical-relabel fast path) -----------------------
    #
    # The P-folded min-fingerprint costs O(P) matmul columns per state —
    # fine at S=3 (P=6) but the dominant compute at S=7 (P=5040, the
    # north-star config 5).  Most non-trivial states are ASYMMETRIC: a
    # cheap Weisfeiler–Leman-style per-server coloring from view-covariant
    # data (currentTerm, role, log, match/nextIndex, votedFor, per-pair
    # message multisets) distinguishes all S servers, which pins a unique
    # canonical relabeling σ (sort by color).  For such "discrete" states
    # the orbit-invariant fingerprint is the hash at that ONE permutation
    # — computed with base (identity) coefficient tables after permuting
    # the feature vector and message bitmask by σ, ~P× less work than the
    # fold.  States with color ties (symmetric early states, or color
    # collisions) fall back to the exact min-over-P path; both routes are
    # orbit-invariant and orbit-mates always take the same route (the
    # color multiset is itself orbit-invariant), so distinct-state counts
    # are unchanged.  NOTE the fingerprint VALUES differ from the
    # min-over-P definition, so runs must not mix the two definitions in
    # one visited store (engine flag TLA_RAFT_ORBIT, default off).
    #
    # σ is derived from VIEW variables only, so view-equal states get the
    # same σ and fp_view stays a pure function of the VIEW projection
    # (the Raft.cfg:26 contract); fp_full then hashes the full state at
    # that same σ, which is still orbit-invariant because σ is a
    # covariant function of the view projection.

    @functools.cached_property
    def orbit_tables(self):
        """Device tables for the canonical-relabel path (built on demand)."""
        from .msg_universe import _dst_idx

        uni, S, P = self.uni, self.cfg.S, self.P
        NP = S * (S - 1)
        # feature-permutation rows for every perm: [P, F] i32
        psi = np.stack(
            [self.spec.perm_source_indices(p) for p in self.perms]
        ).astype(np.int32)
        # inverse pair-digit permutation: ppinv[p, q'] = q with pp[p,q]=q'
        pp = self.uni.pair_perm_table
        ppinv = np.empty_like(pp)
        rows = np.arange(P)[:, None]
        ppinv[rows, pp] = np.arange(NP)[None, :].astype(pp.dtype)
        # (src, dst) -> pair digit (1-based servers; diagonal unused)
        qidx = np.zeros((S, S), np.int32)
        for src in range(1, S + 1):
            for dst in range(1, S + 1):
                if src != dst:
                    qidx[src - 1, dst - 1] = (src - 1) * (S - 1) + _dst_idx(
                        src, dst
                    )
        # per-type random coefficients for the per-pair message multiset
        # hash (i32 wraparound arithmetic = mod 2^32 hashing)
        rng = np.random.default_rng(self.seed ^ 0x0B17)
        W = [
            jnp.asarray(
                rng.integers(-(1 << 31), 1 << 31, size=(s,), dtype=np.int64
                             ).astype(np.int32)
            )
            for s in uni.type_strides
        ]
        # identity-permutation (base) coefficient planes
        C0 = jnp.asarray(
            np.asarray(self.C_planes).reshape(self.spec.F, P, self.N_CHAN * 4)[
                :, 0, :
            ]
        )
        G0 = jnp.asarray(
            _u32_to_i8_planes(
                self.raw_msg_coef(np.arange(uni.M, dtype=np.uint32))
            ).reshape(uni.M, self.N_CHAN * 4)
        )
        fact = np.ones(S, np.int64)
        for i in range(S - 2, -1, -1):
            fact[i] = fact[i + 1] * (S - 1 - i)
        return dict(
            psi=jnp.asarray(psi), ppinv=jnp.asarray(ppinv),
            qidx=jnp.asarray(qidx), W=W, C0=C0, G0=G0,
            fact=jnp.asarray(fact),
        )

    def _orbit_pairh(self, bits):
        """Per-(src,dst)-pair message multiset hash: i8[..., M] -> u32[..., NP]."""
        tb = self.orbit_tables
        NP = self.cfg.S * (self.cfg.S - 1)
        lead = bits.shape[:-1]
        acc = jnp.zeros((*lead, NP), jnp.int32)
        for (off, stride), W in zip(
            zip(self.uni.type_offsets, self.uni.type_strides), tb["W"]
        ):
            bt = jax.lax.slice_in_dim(
                bits, off, off + NP * stride, axis=-1
            ).reshape(*lead, NP, stride).astype(jnp.int32)
            acc = acc + jnp.einsum("...ns,s->...n", bt, W)
        return acc.astype(jnp.uint32)

    def _orbit_colors(self, st, pairh):
        """View-covariant WL colors u32[..., S] (3 refinement rounds)."""
        u32, S, L = jnp.uint32, self.cfg.S, self.cfg.L
        tb = self.orbit_tables
        ct = st.current_term.astype(u32)
        role = st.role.astype(u32)
        ll = st.log_len.astype(u32)
        ci = st.commit_index.astype(u32)
        lt = st.log_term.astype(u32)
        lv = st.log_val.astype(u32)
        mi = st.match_index.astype(u32)
        ni = st.next_index.astype(u32)
        vf = st.voted_for.astype(jnp.int32)
        lpos = jnp.arange(L, dtype=u32) * u32(0x9E3779B9)
        logh = _mix32(
            lt * u32(0x85EBCA6B) + lv * u32(0xC2B2AE35) + lpos
        ).sum(-1, dtype=u32)
        c = _mix32(
            ct * u32(0x8DA6B343) + role * u32(0xD8163841)
            + ll * u32(0xCB1AB31F) + ci * u32(0x165667B1) + logh
        )
        # directed-pair data (position-covariant under simultaneous row/
        # column permutation): per-pair msg hash + match/nextIndex entries
        ph_ij = pairh[..., tb["qidx"]]  # [..., S(i), S(j)] (diag garbage)
        ph_ji = pairh[..., tb["qidx"].T]
        offdiag = ~jnp.eye(S, dtype=bool)
        mi_d = jnp.diagonal(mi, axis1=-2, axis2=-1).astype(u32)
        ni_d = jnp.diagonal(ni, axis1=-2, axis2=-1).astype(u32)
        for _ in range(3):
            cj = c[..., None, :]  # [..., 1(i), S(j)]
            e_out = jnp.where(
                offdiag,
                _mix32(cj + ph_ij * u32(3) + mi * u32(0x27D4EB2F)
                       + ni * u32(0x9E3779B1)),
                u32(0),
            ).sum(-1, dtype=u32)
            mi_t = jnp.swapaxes(mi, -1, -2)
            ni_t = jnp.swapaxes(ni, -1, -2)
            e_in = jnp.where(
                offdiag,
                _mix32(cj + ph_ji * u32(5) + mi_t * u32(0x85EBCA77)
                       + ni_t * u32(0xC2B2AE3D)),
                u32(0),
            ).sum(-1, dtype=u32)
            cvf = jnp.take_along_axis(
                c, jnp.clip(vf - 1, 0, S - 1), axis=-1
            )
            vfh = jnp.where(
                vf == 0, u32(0x94D049BB), _mix32(cvf + u32(0xBF58476D))
            )
            c = _mix32(
                c * u32(0xFF51AFD7) + e_out + e_in + vfh
                + mi_d * u32(0xE6546B64) + ni_d * u32(0x2545F491)
            )
        return c

    def _orbit_rank(self, colors):
        """(lexicographic perm rank i64[...], discrete bool[...]).

        The canonical perm maps each server to 1 + (#servers with a
        smaller color) — i.e. sorts servers by color — and its index in
        ``server_perms()`` (itertools lexicographic order) is the Lehmer
        rank of the image sequence.  Only meaningful where ``discrete``.
        """
        tb = self.orbit_tables
        ci = colors[..., :, None]
        cj = colors[..., None, :]
        S = self.cfg.S
        p = (cj < ci).sum(-1).astype(jnp.int64)  # 0-based images
        eq = (ci == cj) & ~jnp.eye(S, dtype=bool)
        discrete = ~eq.any(axis=(-2, -1))
        after = jnp.triu(jnp.ones((S, S), bool), k=1)
        code = ((p[..., None, :] < p[..., :, None]) & after).sum(-1)
        rank = (code * tb["fact"]).sum(-1)
        return rank, discrete

    def _plane_matmul_flat(self, x_i8, table):
        """i8[..., D] x [D, NC*4] -> u32[..., NC] (same CPU guard as
        ``_plane_matmul``, single permutation column)."""
        if jax.default_backend() == "cpu":
            out = jnp.dot(x_i8.astype(jnp.int32), table.astype(jnp.int32))
        else:
            out = jnp.dot(x_i8, table, preferred_element_type=jnp.int32)
        return _combine_planes_u32(
            out.reshape(*x_i8.shape[:-1], self.N_CHAN, 4)
        )

    def state_fingerprints_orbit(self, st):
        """(fp_view u64[...], fp_full u64[...], discrete bool[...]).

        Fingerprints are EXACT canonical hashes only where ``discrete``;
        other rows need the min-over-P fallback (``state_fingerprints``).
        Where discrete, the value equals the standard per-permutation
        hash evaluated at the canonical perm (bit-identical to that
        column of the folded table path — asserted in tests/test_orbit).
        """
        tb = self.orbit_tables
        bits = self.unpack_bits(st.msgs)
        pairh = self._orbit_pairh(bits)
        colors = self._orbit_colors(st, pairh)
        rank, discrete = self._orbit_rank(colors)
        lead = bits.shape[:-1]
        # features permuted by the canonical perm, hashed at base coeffs
        feats = self.spec.features(st)
        psi = tb["psi"][rank]  # [..., F]
        fplanes = jnp.take_along_axis(feats, psi, axis=-1)
        # message bitmask permuted arithmetically: only the pair digit of
        # an id moves under a server perm, so permute the q axis of each
        # type block by the inverse pair map and hash against base coeffs
        ppinv_row = tb["ppinv"][rank]  # [..., NP]
        NP = self.cfg.S * (self.cfg.S - 1)
        parts = []
        for off, stride in zip(self.uni.type_offsets, self.uni.type_strides):
            bt = jax.lax.slice_in_dim(
                bits, off, off + NP * stride, axis=-1
            ).reshape(*lead, NP, stride)
            btp = jnp.take_along_axis(bt, ppinv_row[..., None], axis=-2)
            parts.append(btp.reshape(*lead, NP * stride))
        bits_perm = jnp.concatenate(parts, axis=-1)
        h = (
            self._plane_matmul_flat(fplanes, tb["C0"])
            + self._plane_matmul_flat(bits_perm, tb["G0"])
        )
        h64 = h.astype(jnp.uint64)
        view = (h64[..., 0] << jnp.uint64(32)) | h64[..., 1]
        full = (h64[..., 2] << jnp.uint64(32)) | h64[..., 3]
        return view, full, discrete

    # -- numpy reference path (oracle bridge, tests) -----------------------

    def fingerprints_np(self, arrs: dict, msgs_bits: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        """Bit-exact host-side reproduction of the device hash.

        arrs: per-field numpy arrays (models/raft.py layout) with one
        leading batch dim; msgs_bits: u8[N, M] unpacked message bitmask.
        """
        feats = self.spec.features_np(arrs)  # i64[N, F]
        # sum_e feat[e] * Cp  with the same signed-byte-plane linearization.
        cp = _u32_to_i8_planes(self._Cp_np).astype(np.int64)  # [P, chan, F, 4]
        planes = np.einsum("nf,pcfk->npck", feats, cp)
        if self.factored_msgs:
            planes = planes + self._msg_planes_factored_np(msgs_bits)
        else:
            gp = _u32_to_i8_planes(self._Gp_np).astype(np.int64)
            planes = planes + np.einsum(
                "nm,pcmk->npck", msgs_bits.astype(np.int64), gp
            )
        h = _combine_planes_u32(planes)  # u32[N, P, chan]
        h64 = h.astype(np.uint64)
        view = ((h64[..., 0] << np.uint64(32)) | h64[..., 1]).min(axis=-1)
        full = ((h64[..., 2] << np.uint64(32)) | h64[..., 3]).min(axis=-1)
        return view, full

    def _msg_planes_factored_np(self, msgs_bits: np.ndarray) -> np.ndarray:
        """Exact int64 twin of _msg_hash_factored -> planes i64[N, P, chan, 4]."""
        uni, NP, NC = self.uni, self._NP, self.N_CHAN
        bits = msgs_bits.astype(np.int64)
        R = None
        for (off, stride), Gt in zip(
            zip(uni.type_offsets, uni.type_strides), self._Gt_planes
        ):
            bt = bits[:, off : off + NP * stride].reshape(-1, NP, stride)
            Rt = bt @ np.asarray(Gt).astype(np.int64)  # [N, q, q'*chan*4]
            R = Rt if R is None else R + Rt
        A = R.reshape(R.shape[0], NP * NP, NC * 4)
        oh = np.asarray(self._ppfold).astype(np.int64)  # [P, NP*NP]
        folded = np.einsum("nmx,pm->npx", A, oh)
        return folded.reshape(-1, self.P, NC, 4)


@functools.lru_cache(maxsize=8)
def get_fingerprinter(cfg: RaftConfig) -> Fingerprinter:
    return Fingerprinter(cfg)
