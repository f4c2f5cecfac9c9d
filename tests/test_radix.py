"""ops/radix.py: the radix orders equal the stable sorts they replace."""

import jax
import numpy as np
import pytest

from tla_raft_tpu.ops.radix import cumsum, stable_order, true_first

SENT = np.uint64(0xFFFFFFFFFFFFFFFF)


@pytest.mark.parametrize("n", [1, 7, 4099])
def test_stable_order_equals_lexsort(n):
    """Two u64 keys with heavy ties and SENT padding, as the owner dedup
    sees them: the order is lexsort's, ties in input order."""
    rng = np.random.default_rng(n)
    a = rng.integers(0, 5, n).astype(np.uint64) * np.uint64(
        0xFFFFFFFF00000001
    )
    a[: n // 3] = SENT
    b = rng.integers(0, 3, n).astype(np.uint64) << np.uint64(40)
    got = jax.jit(lambda a, b: stable_order((a, b)))(a, b)
    np.testing.assert_array_equal(np.asarray(got), np.lexsort((b, a)))


def test_narrow_key_and_true_first():
    """An owner key (0..D) in a few bits, and the stable compaction."""
    rng = np.random.default_rng(0)
    owner = rng.integers(0, 5, 3000)
    got = jax.jit(lambda x: stable_order((x,), bits=(3,)))(owner)
    np.testing.assert_array_equal(
        np.asarray(got), np.argsort(owner, kind="stable")
    )
    mask = rng.random(3000) < 0.3
    np.testing.assert_array_equal(
        np.asarray(jax.jit(true_first)(mask)),
        np.argsort(~mask, kind="stable"),
    )


@pytest.mark.parametrize("n", [5, 1024, 3000])
def test_blocked_cumsum(n):
    x = np.random.default_rng(n).integers(0, 9, n).astype(np.int32)
    np.testing.assert_array_equal(
        np.asarray(jax.jit(cumsum)(x)), np.cumsum(x)
    )
