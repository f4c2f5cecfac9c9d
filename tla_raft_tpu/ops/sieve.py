"""Device-resident spill sieve: a blocked bloom filter over spilled
fingerprints.

PR 12's tiered store made |visited| storage-bounded, but it cost the
superstep its 1/N dispatch amortization: once a generation exists the
resident loop stands down to span 1, because a mid-window level's
generation revisits cannot be host-corrected before the next level
expands them (engine/superstep.py).  This module restores span-N under
spill with the "Compression and Sieve" move (PAPERS.md) — filter before
exact membership:

* the host keeps ONE blocked bloom filter over EVERY fingerprint ever
  demoted (:class:`SpillSieve`, owned by the tiered store, fed at
  demote time).  Blooms have **no false negatives**, so a level whose
  device-side probe reports ZERO sieve hits provably contains no
  spilled revisits — it can commit inside the resident window without
  any host correction, bit-identical to the hot-only run;
* the device holds a read-only copy of the filter words
  (``u64[M]``, M a power of two), probed *inside* the fused
  megakernel/superstep body (:func:`probe_impl`) at ONE data-indexed
  gather per candidate lane — a definite-miss never leaves the device;
* a level with sieve hits > 0 STOPS the superstep BEFORE that level
  commits (``FLAG_TIER``); the host replays it through the per-level
  megakernel whose exact generation probe + one-gather-per-field
  compaction (store/tiered.py) already corrects it.  False positives
  therefore cost one per-level replay, never correctness.

**Layout.**  One u64 word per block: ``word = mix64(fp) & (M - 1)``,
``k = 4`` bit positions from disjoint 6-bit fields of a second mix —
one gather serves all k bits, the cache-line-local variant of a blocked
bloom (docs/PERF.md has the false-positive-rate math: at k = 4 within
one 64-bit word, rate ~= (1 - exp(-k n_blk / 64))^k for n_blk keys per
block).

The same construction backs the per-generation **side-car filters**
(``gen_*.sieve.npz``) the tiered store's compaction persists beside
each cold run, so level-tail probes touch disk only on likely hits —
and the native host store's per-run blooms (native/fpstore.cpp) are
its C++ twin.

Sizing: :func:`sieve_words_for` spends 1/8 of the hot-tier device
budget by default (``TLA_RAFT_SIEVE_BYTES`` overrides), allocated at
FULL size on first demotion and never rebuilt — growing a bloom needs
every spilled fingerprint re-hashed (cold-generation reloads), so the
filter trades graceful fp-rate degradation past its design load for
never touching disk.

Arming is a runtime policy (:class:`SieveGovernor`, driven by
:func:`mode_from_env`): the engine stands the sieve down when recent
superstep windows keep stopping on sieve hits, and re-arms it after a
probation of per-level progress.  Host-purity: building and the numpy mirror are
pure numpy (GL007-safe); the only device code is :func:`probe_impl`,
registered under the GL010 gather budget as ``ops.sieve_probe``.
"""

from __future__ import annotations

import os
from collections import deque

import numpy as np

from ..obs import telemetry as obs

SIEVE_VERSION = 1

# probe bits per key, all inside one u64 block word.  4 bits balances
# the per-key occupancy (4/64 of a block) against the miss-probability
# exponent; see docs/PERF.md for the rate curve
K_BITS = 4

# the second-mix salt decorrelates the bit-position hash from the
# block-index hash (both derive from mix64 chains of the fingerprint)
_SALT = np.uint64(0x9E3779B97F4A7C15)

_C1 = np.uint64(0xBF58476D1CE4E5B9)
_C2 = np.uint64(0x94D9ECA592EAF335)


def _mix(x, xp):
    u = xp.uint64
    x = x.astype(u)
    x = (x ^ (x >> u(30))) * u(_C1)
    x = (x ^ (x >> u(27))) * u(_C2)
    return x ^ (x >> u(31))


def _word_and_mask(fps, xp):
    """(word_index, bit_mask) per fingerprint — the ONE hash pipeline
    the host builder, the numpy mirror and the device probe all share
    (any drift between them would manufacture false negatives, the one
    thing a sieve must never have)."""
    u = xp.uint64
    h1 = _mix(fps, xp)
    h2 = _mix(fps ^ _SALT, xp)
    mask = xp.zeros_like(h2)
    one = u(1)
    for i in range(K_BITS):
        mask = mask | (one << ((h2 >> u(6 * i)) & u(63)))
    return h1, mask


def sieve_words_for(dev_bytes: int) -> int:
    """Filter words (u64, power of two) for a hot-tier device budget:
    1/8 of the budget by default — at 8 bits/spilled-key design load
    that covers a spill ~= the budget itself — floored at 8 KiB so tiny
    test budgets still filter.  ``TLA_RAFT_SIEVE_BYTES`` overrides the
    byte spend directly."""
    env = os.environ.get("TLA_RAFT_SIEVE_BYTES")
    if env:
        nbytes = int(float(env))
    else:
        nbytes = max(int(dev_bytes) >> 3, 1 << 13)
    words = max(nbytes // 8, 1)
    return 1 << max(words.bit_length() - 1, 0)


def words_for_keys(n: int) -> int:
    """Side-car sizing: the smallest power-of-two word count giving a
    per-generation filter >= 12 bits/key (fp rate ~0.5% at K_BITS=4),
    floored at 64 words so tiny runs stay cheap to validate."""
    bits = max(int(n) * 12, 1)
    words = 1 << max((bits // 64).bit_length(), 6)
    return words


class SpillSieve:
    """Host-side blocked bloom over spilled fingerprints.

    ``words`` is the device-uploadable filter image; ``version`` bumps
    on every add so the engine can refresh its device copy exactly when
    the host image changed (demotions are host events — the device copy
    is stale only between a demotion and the next loop top)."""

    __slots__ = ("words", "version", "n_added")

    def __init__(self, n_words: int):
        assert n_words & (n_words - 1) == 0, n_words
        self.words = np.zeros(n_words, np.uint64)
        self.version = 0
        self.n_added = 0

    @property
    def nbytes(self) -> int:
        return self.words.nbytes

    def add(self, fps: np.ndarray) -> None:
        fps = np.asarray(fps, np.uint64)
        if not len(fps):
            return
        w, m = _word_and_mask(fps, np)
        idx = (w & np.uint64(len(self.words) - 1)).astype(np.int64)
        np.bitwise_or.at(self.words, idx, m)
        self.n_added += len(fps)
        self.version += 1

    def contains(self, fps: np.ndarray) -> np.ndarray:
        """Numpy mirror of the device probe (side-car probes, tests,
        the no-false-negative validation)."""
        fps = np.asarray(fps, np.uint64)
        if not len(fps):
            return np.zeros(0, bool)
        w, m = _word_and_mask(fps, np)
        idx = (w & np.uint64(len(self.words) - 1)).astype(np.int64)
        return (self.words[idx] & m) == m

    def fp_rate(self) -> float:
        """Predicted false-positive rate at the current load.

        Blocked blooms pay for their one-gather probe with block-load
        variance: a block's keys are Poisson(n/M), and the rate is the
        Poisson MIXTURE of the per-block rate — roughly 2x the uniform
        single-bloom estimate at design load (docs/PERF.md)."""
        lam = self.n_added / max(len(self.words), 1)
        ks = np.arange(0, max(int(lam * 8), 16))
        pmf = np.exp(-lam + ks * np.log(max(lam, 1e-300))
                     - np.cumsum(np.log(np.maximum(ks, 1))))
        bits = 1.0 - (1.0 - 1.0 / 64.0) ** (K_BITS * ks)
        return float(np.sum(pmf * bits ** K_BITS))

    @classmethod
    def from_words(cls, words: np.ndarray, n_added: int = 0):
        words = np.ascontiguousarray(words, np.uint64)
        s = cls(len(words))
        s.words = words
        s.n_added = int(n_added)
        return s

    @classmethod
    def build(cls, fps: np.ndarray, n_words: int | None = None):
        fps = np.asarray(fps, np.uint64)
        s = cls(n_words or words_for_keys(len(fps)))
        s.add(fps)
        return s


def probe_impl(sieve, fps):
    """Device probe: hit bool[N] per fingerprint lane.

    ``sieve`` is ``u64[M]`` (M a power of two).  ONE data-indexed
    gather (the word fetch); everything else is lane-local bit algebra
    — the GL010-ledgered budget of ``ops.sieve_probe``.  The all-zero
    1-word sentinel the engine passes while tiering is off (or before
    the first demotion) makes every lane a definite miss, so ONE traced
    program serves both regimes."""
    import jax.numpy as jnp

    u = jnp.uint64
    w, m = _word_and_mask(fps, jnp)
    idx = w & u(sieve.shape[0] - 1)
    return (sieve[idx] & m) == m


def empty_device_sieve():
    """The 1-word all-miss sentinel (see probe_impl)."""
    import jax.numpy as jnp

    return jnp.zeros((1,), jnp.uint64)


def ledger_trace(cfg=None):
    """Closed jaxpr of the device probe at tiny reference shapes — the
    graftlint layer-2 (GL010) registration: the budget pins ONE
    data-indexed gather per probe (the block-word fetch), nothing else
    data-indexed."""
    import jax
    import jax.numpy as jnp

    sieve = jax.ShapeDtypeStruct((64,), jnp.uint64)
    fps = jax.ShapeDtypeStruct((256,), jnp.uint64)
    return jax.make_jaxpr(probe_impl)(sieve, fps)


# -- adaptive arm/stand-down policy ------------------------------------
#
# A round-20 CPU A/B measured both regimes (docs/PERF.md, round 20): in
# sieve-clean post-spill sweeps the armed sieve restores span-N
# residency (6 supersteps vs stand-down's 3 at the forced-spill
# reference), but in revisit-dense regimes every window stops on
# FLAG_TIER and replays per-level — the replays never amortize and cost
# ~14% wall over just standing down.  Which regime a run is in is a
# RUNTIME property (it shifts as generations accumulate), so the arm
# decision is driven by the measured signal, not a hand-set env: the
# governor watches the same per-window sieve-stop outcomes the telemetry
# hub records as ``sieve_stop`` events and
#
# * **stands down** when recent windows stop sieve-dirty at high density
#   (>= half of the last few windows): span drops to 1 — the tiered
#   store's sieve-less stand-down — and the replay tax stops accruing;
# * **re-arms** after a probation of per-level progress: revisit density
#   decays as the frontier outruns the demoted generations, and one
#   probing window is cheap against the span-N upside it may restore.
#
# ``TLA_RAFT_SIEVE=1`` / ``=0`` still force either mode unconditionally
# (mode ``on`` / ``off``); the governor only owns the unset (``auto``)
# default.  Arming is pure schedule: counts stay bit-identical in every
# mode (a stood-down run replays through the exact per-level tier probe
# — the parity tests in tests/test_sieve.py pin both arms).

# recent superstep windows consulted for the stand-down decision
WINDOW = 8
# stand down once this fraction of recent windows stopped sieve-dirty
# (at the measured ~14% per-replay tax, half-dirty windows already burn
# more than span residency saves)
STAND_DOWN_DENSITY = 0.5
# minimum windows observed before the density is trusted
MIN_WINDOWS = 4
# per-level probation while stood down before one re-arm probe
REARM_LEVELS = 16


def mode_from_env(explicit: bool | None = None) -> str:
    """``auto`` | ``on`` | ``off`` — the one TLA_RAFT_SIEVE parse.

    An explicit engine argument forces; else env ``0`` forces off, any
    other non-empty value forces on, unset/empty is the governed
    auto mode."""
    if explicit is not None:
        return "on" if explicit else "off"
    env = os.environ.get("TLA_RAFT_SIEVE")
    if env is None or env == "":
        return "auto"
    return "off" if env == "0" else "on"


class SieveGovernor:
    """Measured arm/stand-down state machine for the spill sieve."""

    __slots__ = ("mode", "_armed", "_recent", "_standdown_level", "stats")

    def __init__(self, mode: str = "auto"):
        assert mode in ("auto", "on", "off"), mode
        self.mode = mode
        self._armed = mode != "off"
        self._recent: deque = deque(maxlen=WINDOW)
        self._standdown_level: int | None = None
        self.stats = {"stand_downs": 0, "rearms": 0, "windows": 0}

    @property
    def armed(self) -> bool:
        return self._armed

    def note_window(self, *, sieve_stop: bool, level: int) -> None:
        """One superstep window's outcome (called once per window while
        armed): ``sieve_stop`` is whether it stopped on FLAG_TIER."""
        if self.mode != "auto" or not self._armed:
            return
        self.stats["windows"] += 1
        self._recent.append(bool(sieve_stop))
        n = len(self._recent)
        if n < MIN_WINDOWS:
            return
        density = sum(self._recent) / n
        if density >= STAND_DOWN_DENSITY:
            self._armed = False
            self._standdown_level = int(level)
            self._recent.clear()
            self.stats["stand_downs"] += 1
            obs.emit("sieve_standdown", level=int(level),
                     density=round(density, 3), windows=n)

    def note_level(self, level: int) -> None:
        """Per-level tick (the engine's loop top): drives the re-arm
        probation while stood down."""
        if self.mode != "auto" or self._armed:
            return
        if (self._standdown_level is not None
                and int(level) - self._standdown_level >= REARM_LEVELS):
            self._armed = True
            self._standdown_level = None
            self._recent.clear()
            self.stats["rearms"] += 1
            obs.emit("sieve_arm", level=int(level))

    def snapshot(self) -> dict:
        return dict(mode=self.mode, armed=self._armed, **self.stats)
