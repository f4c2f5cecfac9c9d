"""Sorts and prefix sums that compile in seconds for a TPU.

XLA:TPU's compile time for ``lax.sort`` and for a 1-D ``cumsum`` grows
steeply with the array length.  Compiled for a v5e (PR 21): a stable
sort on two u64 keys took 2 s at 4,096 lanes and 332 s at 65,536; an
i32 cumsum over 2^20 lanes 40 s.  The mesh deep sweep does both over up
to a few million lanes per owner.  Here an LSD radix sort is one loop
of identical O(N) passes (a digit histogram, a prefix sum, one
scatter), 5.7 s to compile at 2^20 lanes, and a prefix sum runs over
1024-lane rows, 1.0 s at 2^20.

The results are exactly those of the sorts and sums they replace (a
stable order is unique; integer sums are exact in any order), so
counts and traces stay bit-identical.

The price is run time.  On one v5e (PR 21) a two-u64-key order takes
5.1 ms at 4,096 lanes, 190 ms at 2^18 and 758 ms at 2^20, where
``jnp.lexsort`` takes 0.7 ms at 16,384 lanes (after 37 s of compile);
the mesh deep sweep's three orders cost ~1.7 s of every level.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# u64 keys (same declaration as ops/hashstore.py; jax.config is
# GL001-safe — no backend touch at import)
jax.config.update("jax_enable_x64", True)

DIGIT_BITS = 4  # 16 buckets: the [16, N] rank table stays small
_NB = 1 << DIGIT_BITS


def stable_order(keys, bits=None):
    """The permutation that stably sorts rows ascending by ``keys``,
    most significant first: ``jnp.lexsort(keys[::-1])``.

    ``keys`` are non-negative integer arrays of one length; ``bits``
    gives each key's significant width (default: its dtype's), so a
    small key such as an owner index costs one pass."""
    n = keys[0].shape[0]
    if bits is None:
        bits = [jnp.dtype(k.dtype).itemsize * 8 for k in keys]
    words, sched = [], []
    for k, b in zip(reversed(keys), reversed(bits)):  # least significant first
        k = k.astype(jnp.uint64)
        for lo in range(0, b, 32):
            words.append((k >> np.uint64(lo)).astype(jnp.uint32))
            wb = min(32, b - lo)
            sched += [(len(words) - 1, s) for s in range(0, wb, DIGIT_BITS)]
    w_tab = jnp.stack(words)
    p_word = jnp.asarray([w for w, _ in sched], jnp.int32)
    p_shift = jnp.asarray([s for _, s in sched], jnp.uint32)
    buckets = jnp.arange(_NB, dtype=jnp.uint32)[:, None]

    def one_pass(p, perm):
        w = jax.lax.dynamic_index_in_dim(w_tab, p_word[p], 0, keepdims=False)
        d = (w[perm] >> p_shift[p]) & jnp.uint32(_NB - 1)
        hot = (d[None, :] == buckets).astype(jnp.int32)  # [16, N]
        count = hot.sum(1)
        start = jnp.cumsum(count) - count
        rank = jnp.take_along_axis(
            jnp.cumsum(hot, axis=1), d[None, :].astype(jnp.int32), 0
        )[0]
        pos = start[d] + rank - 1
        return jnp.zeros_like(perm).at[pos].set(perm, unique_indices=True)

    return jax.lax.fori_loop(
        0, len(sched), one_pass, jnp.arange(n, dtype=jnp.int32)
    )


def true_first(mask):
    """``jnp.argsort(~mask, stable=True)``: the rows where ``mask``
    holds, in order, then the rest, in order."""
    return stable_order([(~mask).astype(jnp.uint32)], bits=[1])


def cumsum(x, block: int = 1024):
    """``jnp.cumsum`` of a 1-D array, summed over ``block``-lane rows
    and then over the row totals."""
    n = x.shape[0]
    if n <= block:
        return jnp.cumsum(x)
    rows = jnp.cumsum(jnp.pad(x, (0, -n % block)).reshape(-1, block), axis=1)
    lead = jnp.cumsum(rows[:, -1]) - rows[:, -1]
    return (rows + lead[:, None]).reshape(-1)[:n]
