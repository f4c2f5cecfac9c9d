"""On-device open-addressing fingerprint store (ops/hashstore.py).

Unit level: probe/insert semantics under duplicate-heavy batches,
forced collision chains, growth/rehash, the numpy mirror's layout
parity, slab checkpoint round-trips.  Engine level: the hash-store
visited path must be bit-identical (distinct/generated/depth and
per-level counts) to the sort-based path — on quick-tier fixpoints and
prefixes here, and on the (3,1,2,1) GOLDEN_FULL fixpoint in the slow
tier.  Mesh level: the deep sweep's golden depth-8 prefix (1505
distinct / 3044 generated) with the hash sieve live, and the plain
all_to_all mesh with hash-slab owner shards vs the sorted-shard path.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tla_raft_tpu.config import RaftConfig
from tla_raft_tpu.engine import JaxChecker
from tla_raft_tpu.ops import hashstore as hs

SENT = np.uint64(0xFFFFFFFFFFFFFFFF)
S2 = RaftConfig(n_servers=2, n_vals=1, max_election=1, max_restart=1)
S3V1 = RaftConfig(n_vals=1, max_election=1, max_restart=1)
REF = RaftConfig()  # the reference Raft.cfg constants


# -- kernel unit tests ----------------------------------------------------

def _insert(slab, fps, keys, pays):
    out = jax.jit(hs.probe_and_insert_impl)(
        slab, jnp.asarray(fps), jnp.asarray(keys), jnp.asarray(pays)
    )
    slab2, fresh, n_new, ovf = out
    return slab2, np.asarray(fresh), int(n_new), bool(ovf)


def test_fresh_mask_parity_duplicate_heavy():
    """Duplicate-heavy batches: exactly ONE fresh lane per new
    fingerprint, and it is the min-(key, payload) lane of its group —
    the lexsort path's representative choice."""
    rng = np.random.default_rng(7)
    base = rng.integers(1, 2**63, 500, dtype=np.uint64)
    # every fp appears 1-6 times, with distinct keys/payloads per lane
    reps = rng.integers(1, 7, len(base))
    fps = np.repeat(base, reps)
    perm = rng.permutation(len(fps))
    fps = fps[perm]
    keys = rng.integers(1, 2**63, len(fps), dtype=np.uint64)
    pays = np.arange(len(fps), dtype=np.int64)
    slab2, fresh, n_new, ovf = _insert(
        hs.make_slab(1 << 12), fps, keys, pays
    )
    uniq = np.unique(base)
    assert not ovf
    assert n_new == len(uniq)
    assert set(fps[fresh]) == set(uniq)
    for fp in uniq:
        lanes = np.nonzero(fps == fp)[0]
        best = min((int(keys[i]), int(pays[i]), i) for i in lanes)[2]
        assert fresh[best] and fresh[lanes].sum() == 1
    # second pass: nothing fresh (all duplicates of the store now)
    _s3, fresh2, n2, _ = _insert(slab2, fps, keys, pays)
    assert n2 == 0 and not fresh2.any()


def test_probe_membership_exact():
    rng = np.random.default_rng(3)
    fps = np.unique(rng.integers(1, 2**63, 3000, dtype=np.uint64))
    slab2, _f, _n, ovf = _insert(
        hs.make_slab(1 << 13), fps, fps, np.arange(len(fps), dtype=np.int64)
    )
    assert not ovf
    assert np.asarray(hs.probe(slab2, jnp.asarray(fps))).all()
    absent = np.setdiff1d(
        rng.integers(1, 2**63, 3000, dtype=np.uint64), fps
    )
    assert not np.asarray(hs.probe(slab2, jnp.asarray(absent))).any()
    # SENT lanes are dead: never hits, never inserts
    assert not np.asarray(
        hs.probe(slab2, jnp.full((16,), SENT, jnp.uint64))
    ).any()


def test_collision_chain_within_probe_depth():
    """Craft fingerprints sharing ONE probe home: the linear chain must
    resolve every insert, probe must find them all, and the numpy
    mirror must reproduce the slab bit for bit."""
    cap = 1 << 12
    h = hs.mix64(np.arange(1, 200_000, dtype=np.uint64)) & np.uint64(cap - 1)
    same = (np.nonzero(h == h[0])[0][:32] + 1).astype(np.uint64)
    assert len(same) >= 8, "need a real chain for the test to bite"
    slab2, fresh, n_new, ovf = _insert(
        hs.make_slab(cap), same, same, np.arange(len(same), dtype=np.int64)
    )
    assert not ovf and n_new == len(same) and fresh.all()
    assert np.asarray(hs.probe(slab2, jnp.asarray(same))).all()
    arr = np.full(cap, SENT, np.uint64)
    hs.insert_np(arr, same)
    assert (arr == np.asarray(slab2)).all()


def test_probe_overflow_reports_and_preserves_input():
    """Past the probe window the kernel must REPORT overflow (the
    grow/redo trigger), and the input slab must be untouched (the
    kernels are functional — redo runs against the original)."""
    rng = np.random.default_rng(11)
    tiny = hs.make_slab(1 << 10)
    fps = np.unique(rng.integers(1, 2**63, 1024, dtype=np.uint64))
    _s2, _f, _n, ovf = _insert(
        tiny, fps, fps, np.arange(len(fps), dtype=np.int64)
    )
    assert ovf  # ~100% load cannot fit the probe window
    assert (np.asarray(tiny) == SENT).all()


def test_growth_rehash_preserves_set():
    rng = np.random.default_rng(5)
    fps = np.unique(rng.integers(1, 2**63, 2000, dtype=np.uint64))
    st = hs.DeviceHashStore.from_fps(fps)
    cap0 = st.cap
    assert st.count == len(fps)
    st.grow()
    assert st.cap == 2 * cap0 and st.count == len(fps)
    live = np.asarray(st.slab)
    live = live[live != SENT]
    assert len(live) == len(fps) and set(live) == set(fps)
    assert np.asarray(hs.probe(st.slab, jnp.asarray(fps))).all()
    # reserve() ratchets up, never down
    st.reserve(10)
    assert st.cap == 2 * cap0
    st.reserve(4 * cap0)
    assert st.cap >= 8 * cap0


def test_slab_checkpoint_roundtrip(tmp_path):
    rng = np.random.default_rng(9)
    fps = np.unique(rng.integers(1, 2**63, 1000, dtype=np.uint64))
    st = hs.DeviceHashStore.from_fps(fps)
    path = str(tmp_path / "hslab.npz")
    st.dump(path, depth=5, fp_def=0)
    back = hs.DeviceHashStore.load(path, depth=5, count=st.count, fp_def=0)
    assert back is not None
    assert back.cap == st.cap and back.count == st.count
    assert (np.asarray(back.slab) == np.asarray(st.slab)).all()
    # any mismatch falls back to a rebuild (load returns None)
    assert hs.DeviceHashStore.load(path, depth=6, count=st.count) is None
    assert hs.DeviceHashStore.load(path, depth=5, count=st.count + 1) is None
    assert hs.DeviceHashStore.load(path, 5, st.count, fp_def=1) is None


def test_slab_rows_quantized_load():
    assert hs.slab_rows(0) == hs.MIN_CAP
    for n in (100, 10_000, 1_000_000):
        cap = hs.slab_rows(n)
        assert cap & (cap - 1) == 0
        assert n * 2 <= cap < n * 4 or cap == hs.MIN_CAP


# -- engine parity: hash-store path vs sort path --------------------------

def _triple(res):
    return (res.distinct, res.generated, res.depth, tuple(res.level_sizes))


def test_engine_parity_s2_fixpoint():
    a = JaxChecker(S2, chunk=256, use_hashstore=False).run()
    b = JaxChecker(S2, chunk=256, use_hashstore=True).run()
    assert _triple(a) == _triple(b)
    assert a.action_counts == b.action_counts


@pytest.mark.slow  # tier-1 budget (PR 20): the S2 fixpoint + 3121
# prefix rows above/below keep hash-vs-sort engine parity fast; the
# 545-state S3V1 pin rides with the heavy rows
def test_engine_parity_s3v1_fixpoint():
    a = JaxChecker(S3V1, chunk=256, use_hashstore=False).run()
    b = JaxChecker(S3V1, chunk=256, use_hashstore=True).run()
    assert _triple(a) == _triple(b)
    assert b.distinct == 545  # the S3V1 fixpoint the deep suite pins


def test_engine_parity_3121_prefix():
    """Quick-tier prefix of the GOLDEN_FULL (3,1,2,1) config; the full
    180,582-state fixpoint runs in the slow tier below."""
    cfg = RaftConfig(n_vals=1, max_election=2, max_restart=1)
    a = JaxChecker(cfg, chunk=256, use_hashstore=False).run(max_depth=9)
    b = JaxChecker(cfg, chunk=256, use_hashstore=True).run(max_depth=9)
    assert _triple(a) == _triple(b)


@pytest.mark.slow
def test_engine_parity_golden_full_3121():
    """GOLDEN_FULL acceptance: the hash-store path lands exactly on the
    dual-verified (3,1,2,1) fixpoint totals (oracle/golden.py GOLDEN_FULL)."""
    cfg = RaftConfig(n_vals=1, max_election=2, max_restart=1)
    res = JaxChecker(cfg, chunk=1024, use_hashstore=True).run()
    assert (res.distinct, res.generated, res.depth) == (180_582, 747_500, 35)


def test_engine_resume_through_slab_dump(tmp_path):
    """Checkpoint/resume through a slab dump: the resumed run must land
    on the uninterrupted run's numbers, with the slab fast path AND the
    rebuild-from-deltas fallback (slab removed) both exercised."""
    td = str(tmp_path / "ck")
    want = JaxChecker(S3V1, chunk=256, use_hashstore=True).run(max_depth=12)
    JaxChecker(S3V1, chunk=256, use_hashstore=True).run(
        max_depth=8, checkpoint_dir=td
    )
    assert os.path.exists(os.path.join(td, "hslab.npz"))
    got = JaxChecker(S3V1, chunk=256, use_hashstore=True).run(
        max_depth=12, resume_from=td, checkpoint_every=0
    )
    assert _triple(got) == _triple(want)
    os.unlink(os.path.join(td, "hslab.npz"))  # force the rebuild path
    got2 = JaxChecker(S3V1, chunk=256, use_hashstore=True).run(
        max_depth=12, resume_from=td, checkpoint_every=0
    )
    assert _triple(got2) == _triple(want)


# -- mesh: hash-slab owner shards + hash sieve ----------------------------

@pytest.mark.slow  # tier-1 budget (PR 15): the deep-mode hash-vs-
# sorted parity row (test_mesh_deep_hash_sieve_matches_sorted_sieve)
# stays fast and covers mesh hash-slab parity incl. resume
def test_mesh_a2a_hash_shards_match_sorted(tmp_path):
    """Plain all_to_all mesh: hash-slab owner shards vs sorted shards,
    identical counts and coverage on the S2 fixpoint."""
    if len(jax.devices()) < 4:
        pytest.skip("not enough virtual devices")
    from tla_raft_tpu.parallel import ShardedChecker, make_mesh

    mesh = make_mesh(4)
    a = ShardedChecker(S2, mesh, cap_x=256, use_hashstore=False).run()
    b = ShardedChecker(S2, mesh, cap_x=256, use_hashstore=True).run()
    assert _triple(a) == _triple(b)
    assert a.action_counts == b.action_counts


@pytest.mark.slow  # tier-1 budget (PR 20): the S2 deep row below
# (test_mesh_deep_hash_sieve_matches_sorted_sieve) keeps deep-mode
# hash-sieve parity + slab serialize/resume fast; the 8-dev golden
# reference prefix rides with the heavy rows
def test_mesh_deep_golden_prefix_hash_sieve(tmp_path):
    """The deep-sweep acceptance prefix with the hash sieve live: the
    reference constants to depth 8 must land on 1505 distinct / 3044
    generated (BASELINE.md golden prefix), the sieve must fire, and the
    checkpoint must serialize the sieve slab (resume-through-slab runs
    at S2 scale below)."""
    if len(jax.devices()) < 8:
        pytest.skip("not enough virtual devices")
    from tla_raft_tpu.parallel import ShardedChecker, make_mesh

    td = str(tmp_path / "ck")
    chk = ShardedChecker(
        REF, make_mesh(8), cap_x=512, deep=True, seg_rows=128,
        host_store_dir=str(tmp_path / "fps"), use_hashstore=True,
    )
    got = chk.run(max_depth=8, checkpoint_dir=td)
    assert (got.distinct, got.generated, got.depth) == (1505, 3044, 8)
    assert list(got.level_sizes) == [1, 1, 3, 9, 22, 57, 136, 345, 931]
    s = chk.meter.summary()
    assert s["sieved"] > 0, "the hash sieve never fired"
    assert os.path.exists(os.path.join(td, "sieve_slab.npz"))


def test_mesh_deep_hash_sieve_matches_sorted_sieve(tmp_path):
    """Deep mode: hash sieve vs sorted sieve, identical counts and
    store contents on the S2 fixpoint — plus checkpoint/resume through
    the serialized sieve slab."""
    if len(jax.devices()) < 4:
        pytest.skip("not enough virtual devices")
    from tla_raft_tpu.parallel import ShardedChecker, make_mesh

    mesh = make_mesh(4)
    a = ShardedChecker(
        S2, mesh, cap_x=256, deep=True, seg_rows=8,
        host_store_dir=str(tmp_path / "a"), use_hashstore=False,
    )
    ra = a.run()
    td = str(tmp_path / "ck")
    b = ShardedChecker(
        S2, mesh, cap_x=256, deep=True, seg_rows=8,
        host_store_dir=str(tmp_path / "b"), use_hashstore=True,
    )
    rb = b.run(max_depth=8, checkpoint_dir=td)
    assert os.path.exists(os.path.join(td, "sieve_slab.npz"))
    c = ShardedChecker(
        S2, mesh, cap_x=256, deep=True, seg_rows=8,
        host_store_dir=str(tmp_path / "b"), use_hashstore=True,
    )
    rc = c.run(checkpoint_dir=td, resume_from=td)
    assert _triple(ra) == _triple(rc)
    assert sum(len(s) for s in a.host_stores) == ra.distinct
    assert sum(len(s) for s in c.host_stores) == rc.distinct
