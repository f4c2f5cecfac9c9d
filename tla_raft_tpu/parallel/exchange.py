"""Sieve-and-compress fingerprint exchange: packing + byte accounting.

The deep-sweep mesh tier (parallel/sharded.py, ``deep=True``) moves only
FINGERPRINTS over the host link: each owner shard's level-unique unknown
candidates, sorted ascending, delta-encoded and packed into a variable-
width byte stream ON DEVICE, fetched as a quantized prefix, and answered
with one is-new bit per fingerprint.  This is the "compress" half of
arXiv:1208.5542's sieve-and-compress BFS exchange; the sieve half (drop
candidates already known visited before any routing) lives in the
phase-1 program of the sharded checker.

Why deltas help at all on 64-bit hashes: a sorted run of n pseudorandom
u64 fingerprints has consecutive gaps ~2^64/n, i.e. ~(64 - log2 n) bits
of real information per entry — at a 10^6-candidate shard that is ~6
bytes instead of 8, and the variable-width encoding additionally never
pays for the routing/padding lanes the fixed-shape u64 exchange ships.
The big multiplier is the sieve and the exact owner-side dedup in front
of this encoder: only never-seen-before candidates reach the stream.

Encoding: entry i stores delta_i = fp_i - fp_{i-1} (fp_{-1} = 0) as
1..8 little-endian bytes; per-entry byte lengths ride in a 4-bit nibble
array (entry 2k in the low nibble of byte k).  Both halves are built on
device with a cumsum + masked scatter-add (no data-dependent shapes);
the host decodes with eight vectorized numpy passes.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from ..ops import radix

U64 = jnp.uint64
I32 = jnp.int32
I64 = jnp.int64
SENT = np.uint64(0xFFFFFFFFFFFFFFFF)


def pack_fp_deltas(fps_sorted: jnp.ndarray, n: jnp.ndarray):
    """Delta-pack the ascending prefix ``fps_sorted[:n]`` (device-side).

    fps_sorted: u64[cap], strictly ascending real entries in the first
    ``n`` lanes (SENT-padded beyond).  Returns (stream u8[cap*8],
    nibbles u8[cap//2], total_bytes i64) — ``total_bytes`` is the live
    prefix of ``stream``; ``nibbles``' live prefix is ceil(n/2) bytes.
    Traceable under jit/shard_map (fixed shapes; only the host fetch
    slices the prefixes).
    """
    cap = fps_sorted.shape[0]
    assert cap % 2 == 0, "pack capacity must be even (nibble pairing)"
    live = jnp.arange(cap, dtype=I32) < n
    prev = jnp.concatenate([jnp.zeros((1,), U64), fps_sorted[:-1]])
    delta = jnp.where(live, fps_sorted - prev, jnp.uint64(0))
    # byte length of each delta: 1 + (#thresholds passed); exact, no clz.
    # Offsets accumulate in i64: an i32 cumsum would wrap once a shard's
    # packed stream passes 2 GB (~350M fps at ~6 B — inside the deep-
    # sweep target regime) and silently corrupt the stream.
    nb = jnp.ones((cap,), I64)
    for k in range(1, 8):
        nb = nb + (delta >= jnp.uint64(1 << (8 * k))).astype(I64)
    nb = jnp.where(live, nb, 0)
    off = radix.cumsum(nb) - nb
    total = off[-1] + nb[-1]
    # masked scatter-add builds the byte stream; dead lanes all land on
    # one trash slot past the live region with value 0
    j = jnp.arange(8, dtype=I64)[None, :]
    idx = off[:, None] + j
    val = (
        (delta[:, None] >> (8 * j).astype(jnp.uint64)) & jnp.uint64(0xFF)
    ).astype(jnp.uint32)
    mask = (j < nb[:, None]) & live[:, None]
    flat_idx = jnp.where(mask, idx, cap * 8).reshape(-1)
    flat_val = jnp.where(mask, val, 0).reshape(-1)
    stream = (
        jnp.zeros((cap * 8 + 1,), jnp.uint32)
        .at[flat_idx]
        .add(flat_val)[: cap * 8]
        .astype(jnp.uint8)
    )
    nbu = nb.astype(jnp.uint8)
    nibbles = nbu[0::2] | (nbu[1::2] << 4)
    return stream, nibbles, total


def unpack_fp_deltas(stream: np.ndarray, nibbles: np.ndarray,
                     count: int, verify: bool = False) -> np.ndarray:
    """Host-side inverse of :func:`pack_fp_deltas` -> u64[count].

    ``verify=True`` adds the exchange-stream integrity check the deep
    level tail runs before inserting into the owner stores: the packed
    form encodes a STRICTLY ASCENDING unique sequence, so the decoded
    output must be strictly increasing — a flipped bit in the stream,
    the nibble header or the prefix fetch almost surely produces a
    duplicate (zero delta), a wrapped cumsum or a garbage length, all
    of which break monotonicity.  One O(count) compare buys end-to-end
    detection on the host leg that the per-record digests cannot give
    (the fetch crosses the link AFTER any checksumming)."""
    if count == 0:
        return np.empty(0, np.uint64)
    nib = np.asarray(nibbles[: (count + 1) // 2], np.uint8)
    nb = np.empty(2 * len(nib), np.int64)
    nb[0::2] = nib & 0xF
    nb[1::2] = nib >> 4
    nb = nb[:count]
    off = np.cumsum(nb) - nb
    st = np.asarray(stream, np.uint8)
    delta = np.zeros(count, np.uint64)
    for b in range(8):
        m = nb > b
        if not m.any():
            break
        delta[m] |= st[off[m] + b].astype(np.uint64) << np.uint64(8 * b)
    out = np.cumsum(delta, dtype=np.uint64)
    if verify and count > 1 and not (out[1:] > out[:-1]).all():
        from ..resilience.integrity import IntegrityError

        bad = int(np.argmin(out[1:] > out[:-1]))
        raise IntegrityError(
            f"corrupt fingerprint exchange stream: decoded entry "
            f"{bad + 1} of {count} is not strictly greater than its "
            "predecessor (the packed form encodes a sorted unique "
            "sequence) — a bit flipped between the owner's finalize "
            "and the host fetch"
        )
    return out


def packed_quantum(nbytes: int) -> int:
    """Fetch-prefix quantization: smallest c >= nbytes with c in
    {2^k, 3*2^(k-1)} (the repo's half-step ladder), so the prefix-slice
    programs compile O(log) times per run, not once per level."""
    n = max(int(nbytes), 1)
    p = 1 << (n - 1).bit_length()
    half = 3 * (p >> 2)
    return half if half >= n and half > 0 else p


class ExchangeMeter:
    """Per-level byte accounting for the fingerprint exchange.

    Two ledgers: ``a2a`` (device-device collective bytes — the routing
    all_to_all tiles that actually cross a link, i.e. the off-diagonal
    (D-1)/D share — plus verdict return tiles) and ``host`` (host<->
    device bytes: candidate fetches and verdict puts — the host-link
    budget at deep levels).  ``raw`` mirrors what the
    uncompressed exchange would have moved for the same level so the
    run summary can report an honest reduction factor.
    """

    def __init__(self):
        self.levels: list[dict] = []
        self._cur: dict | None = None

    def begin_level(self, level: int):
        self._cur = dict(
            level=level, a2a_bytes=0, host_bytes=0,
            raw_a2a_bytes=0, raw_host_bytes=0,
            n_candidates=0, n_sieved=0, n_unique=0,
            # None = no packing decision was made this level (paths
            # that never delta-pack — all_gather, the plain hosted
            # exchange); the deep path records True/False explicitly
            packed=None,
        )

    def add(self, **kw):
        assert self._cur is not None
        for k, v in kw.items():
            self._cur[k] += int(v)

    def note_packed(self, packed: bool):
        """Record whether the level's fp stream went out delta-packed.

        ``packed=False`` means the packing fallback fired: the packed
        form (plus header) was NOT smaller, so the raw u64 stream was
        sent instead.  The level's host leg then has no hypothetical
        uncompressed equivalent — what was sent IS the uncompressed
        form — so ``end_level`` floors the raw-host mirror at the
        actual host bytes and per-level reduction can never read < 1
        (the BENCH_r06 levels 1-2 "reduction 0.21-0.56" artifact was
        exactly quantum padding billed against a live-lane mirror)."""
        assert self._cur is not None
        self._cur["packed"] = bool(packed)

    def end_level(self) -> dict:
        cur, self._cur = self._cur, None
        if cur["packed"] is False:  # None = packing never considered
            cur["raw_host_bytes"] = max(
                cur["raw_host_bytes"], cur["host_bytes"]
            )
        exchanged = cur["a2a_bytes"] + cur["host_bytes"]
        raw = cur["raw_a2a_bytes"] + cur["raw_host_bytes"]
        cur["exchanged_bytes"] = exchanged
        cur["raw_bytes"] = raw
        cur["reduction"] = round(raw / exchanged, 2) if exchanged else None
        self.levels.append(cur)
        # per-level exchange bytes into the telemetry hub (the flight
        # recorder is the unified sink; summary() keeps the CLI view)
        from ..obs import telemetry as _obs

        _obs.exchange(
            cur["level"], exchanged, raw,
            candidates=cur["n_candidates"], sieved=cur["n_sieved"],
        )
        return cur

    def summary(self) -> dict:
        tot = sum(lv["exchanged_bytes"] for lv in self.levels)
        raw = sum(lv["raw_bytes"] for lv in self.levels)
        return dict(
            levels=len(self.levels),
            exchanged_bytes=tot,
            raw_bytes=raw,
            reduction=round(raw / tot, 2) if tot else None,
            sieved=sum(lv["n_sieved"] for lv in self.levels),
            candidates=sum(lv["n_candidates"] for lv in self.levels),
            per_level=[
                {k: lv[k] for k in (
                    "level", "exchanged_bytes", "raw_bytes", "reduction",
                    "n_candidates", "n_sieved", "n_unique", "packed",
                )}
                for lv in self.levels
            ],
        )
