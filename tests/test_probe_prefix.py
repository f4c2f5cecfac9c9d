"""The fused level's probe-and-insert over the live lane prefix.

``megakernel.probe_live_prefix`` runs the hash store's probe-and-insert
and the survivor compaction over the smallest static width that holds
the level's live candidate lanes.  It must return bit for bit what the
whole lane buffer returns: the slab layout, the fresh lanes, n_new, the
probe-window overflow and the compacted fingerprints and payloads, for
every rung of the width ladder.  The engine rows run the reference
config's golden prefix through the superstep and the per-level path.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tla_raft_tpu.config import RaftConfig
from tla_raft_tpu.engine import megakernel
from tla_raft_tpu.ops import hashstore

SENT = hashstore.SENT
CHUNK = 4
CAP_X = 8
N_CHUNKS = 12
N = N_CHUNKS * CAP_X  # widths 8, 16, 32, 64, 96
SLAB = hashstore.MIN_CAP


@functools.partial(jax.jit, static_argnames=("cap_out",))
def _full_width(slab, cv, cf, cp, cap_out):
    """The whole-buffer probe-and-insert and compaction, as the level
    program ran them before the prefix bound."""
    slab2, fresh, n_new, ovf = hashstore.probe_and_insert_impl(
        slab, cv, cf, cp
    )
    fps, pay = hashstore.compact_fresh(fresh, cv, cp, N)
    if cap_out > N:
        fps = jnp.concatenate(
            [fps, jnp.full((cap_out - N,), SENT, jnp.uint64)]
        )
        pay = jnp.concatenate(
            [pay, jnp.full((cap_out - N,), -1, jnp.int64)]
        )
    return slab2, fresh, n_new, ovf, fps[:cap_out], pay[:cap_out]


@functools.partial(jax.jit, static_argnames=("cap_out",))
def _prefix(slab, cv, cf, cp, n_f, cap_out):
    return megakernel.probe_live_prefix(
        slab, cv, cf, cp, n_f, CHUNK, CAP_X, cap_out
    )


def _lanes(rng, live_chunks, n_fps):
    """A level's candidate buffers: live chunks hold a random subset of
    their cap_x lanes, drawn from ``n_fps`` view fingerprints (so groups
    repeat within and across chunks) with two fp_full keys each (key
    ties break on the payload); every lane past the live chunks is
    SENT, as the chunked expand leaves it."""
    pool = rng.integers(0, 1 << 63, size=n_fps, dtype=np.uint64)
    keys = rng.integers(0, 1 << 63, size=(n_fps, 2), dtype=np.uint64)
    cv = np.full(N, SENT, np.uint64)
    cf = np.full(N, SENT, np.uint64)
    cp = np.full(N, -1, np.int64)
    for i in range(live_chunks):
        for lane in range(i * CAP_X, (i + 1) * CAP_X):
            if rng.random() < 0.8:
                g = rng.integers(n_fps)
                cv[lane] = pool[g]
                cf[lane] = keys[g, rng.integers(2)]
                cp[lane] = 3 * lane + 1  # unique, lane-ascending
    n_f = (live_chunks - 1) * CHUNK + int(rng.integers(1, CHUNK + 1))
    return pool, cv, cf, cp, n_f


def _slab(rng, pool, case):
    slab = np.full(SLAB, SENT, np.uint64)
    if case == "seeded":
        # a third of the level's fingerprints are already visited
        hashstore.insert_np(slab, pool[: len(pool) // 3])
    elif case == "overflow":
        # every slot taken: fresh lanes find no empty slot within the
        # probe depth, while fingerprints stored at their home still hit
        slab[:] = np.random.default_rng(7).integers(
            0, 1 << 63, size=SLAB, dtype=np.uint64
        )
        homes = hashstore.mix64(pool[::2]) & np.uint64(SLAB - 1)
        slab[homes.astype(np.int64)] = pool[::2]
    return slab


@pytest.mark.parametrize("cap_out", [64, 128])
@pytest.mark.parametrize("case", ["dups", "seeded", "overflow"])
@pytest.mark.parametrize("live_chunks", [1, 2, 3, N_CHUNKS - 1, N_CHUNKS])
def test_prefix_probe_is_bit_identical(live_chunks, case, cap_out):
    rng = np.random.default_rng(1000 * live_chunks + len(case) + cap_out)
    pool, cv, cf, cp, n_f = _lanes(rng, live_chunks, 2 + 3 * live_chunks)
    slab = _slab(rng, pool, case)
    want = jax.device_get(_full_width(slab, cv, cf, cp, cap_out=cap_out))
    got = jax.device_get(_prefix(slab, cv, cf, cp, jnp.int64(n_f),
                                 cap_out=cap_out))
    w_slab, w_fresh, w_n, w_ovf, w_fps, w_pay = want
    g_slab, g_n, g_ovf, g_fps, g_pay, g_lanes = got
    np.testing.assert_array_equal(g_slab, w_slab)
    assert int(g_n) == int(w_n)
    assert bool(g_ovf) == bool(w_ovf) == (case == "overflow")
    np.testing.assert_array_equal(g_fps, w_fps)
    np.testing.assert_array_equal(g_pay, w_pay)
    # the fresh set: the representative payload of every new group
    n = min(int(g_n), cap_out)
    assert list(g_pay[:n]) == list(cp[w_fresh][:n])
    if case != "overflow":
        assert 0 < int(g_n) < (cv != SENT).sum()  # duplicates collapsed
    assert int(g_lanes) == live_chunks * CAP_X  # the live blocks alone


def test_prefix_probe_of_an_empty_level():
    cv = np.full(N, SENT, np.uint64)
    cp = np.full(N, -1, np.int64)
    slab = np.full(SLAB, SENT, np.uint64)
    g_slab, g_n, g_ovf, g_fps, _pay, g_lanes = jax.device_get(
        _prefix(slab, cv, cv, cp, jnp.int64(0), cap_out=64)
    )
    assert int(g_n) == 0 and not bool(g_ovf)
    assert (g_slab == SENT).all() and (g_fps == SENT).all()
    assert int(g_lanes) == 0


def _eqns(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs nested in it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for p in eqn.params.values():
            for sub in p if isinstance(p, (list, tuple)) else (p,):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner)


@pytest.mark.parametrize("n_chunks,cap_x", [(N_CHUNKS, CAP_X), (192, 64)])
def test_no_gather_or_scatter_spans_the_lane_budget(n_chunks, cap_x):
    """The data-indexed work runs one lane block at a time: no gather
    or scatter takes more than a probe window of one block, so neither
    the work nor the program's code grows with the lane budget."""
    n = n_chunks * cap_x
    u64 = jax.ShapeDtypeStruct((n,), jnp.uint64)
    jaxpr = jax.make_jaxpr(
        lambda s, v, f, p, k: megakernel.probe_live_prefix(
            s, v, f, p, k, CHUNK, cap_x, 64
        )
    )(
        jax.ShapeDtypeStruct((SLAB,), jnp.uint64), u64, u64,
        jax.ShapeDtypeStruct((n,), jnp.int64),
        jax.ShapeDtypeStruct((), jnp.int64),
    ).jaxpr
    sizes = {}
    for eqn in _eqns(jaxpr):
        name = eqn.primitive.name
        if name == "gather" or name.startswith("scatter"):
            lanes = eqn.invars[1].aval.size
            sizes[name] = max(sizes.get(name, 0), lanes)
    assert {"gather", "scatter", "scatter-min"} <= set(sizes)
    assert max(sizes.values()) <= hashstore.PROBE_WINDOW * cap_x, sizes


# -- the engine: S=3 golden prefix through both fused paths ---------------

# each path's dispatch and ledgered-fetch counts on this prefix, as the
# full-width probe gave them (chunk 64: cap_x grows twice, and those
# levels replay per level)
LEDGER = {
    4: dict(per_superstep_dispatches=[1, 1, 1],
            per_superstep_fetches=[1, 1, 1],
            per_level_dispatches=[1, 0, 0, 0, 1, 1, 1, 1],
            per_level_fetches=[4, 0, 0, 0, 2, 1, 1, 2]),
    1: dict(per_superstep_dispatches=[], per_superstep_fetches=[],
            per_level_dispatches=[1, 1, 1, 1, 1, 2, 1, 2],
            per_level_fetches=[4, 1, 1, 1, 1, 2, 2, 3]),
}


@pytest.mark.parametrize("span,block", [
    pytest.param(4, "superstep_stats", id="superstep"),
    pytest.param(1, "megakernel_stats", id="per-level"),
])
def test_golden_prefix_probes_live_lanes(span, block):
    """Raft.cfg's golden per-level counts to depth 8 are unchanged, the
    summary shows fewer lanes probed than the buffers hold, and the
    dispatches and ledgered fetches are the full-width probe's: one of
    each per superstep, one of each per level and per redo."""
    from bench import GOLDEN_LEVELS
    from tla_raft_tpu.analysis import sanitize
    from tla_raft_tpu.check import run_check, summary_public

    with sanitize.Sanitizer(warmup_levels=0, strict=False) as san:
        s = summary_public(run_check(
            RaftConfig(), chunk=64, max_depth=8, superstep=span,
            telemetry=False,
        ))
    assert s["level_sizes"] == GOLDEN_LEVELS[(3, 2, 3, 3)][:9]
    stats = s[block]
    assert 0 < stats["probe_lanes"] < stats["lane_budget"], stats
    r = san.report()
    assert {k: r[k] for k in LEDGER[span]} == LEDGER[span]
