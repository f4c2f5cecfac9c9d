"""Sharded deep-sweep (1/D frontier segments + sieve-and-compress) parity.

Tier-1 coverage for the deep mesh tier: on the 8-device virtual CPU
mesh the deep path must reproduce the single-device engine's per-level
distinct/generated counts EXACTLY on an S=3 config to depth >= 8, its
per-owner stores must jointly hold exactly the engine's fingerprint
set, the measured exchange bytes must undercut the uncompressed
exchange (whose live-lane ledger the deep path's 'raw' mirror must
reproduce to the byte), and a checkpoint/resume cycle must land on
identical numbers.

Config sizing: the reference-constants acceptance run (RaftConfig()
defaults == Raft.cfg, depth 8, ~26 s on the 8-device virtual mesh)
and a deeper S=3 V=1 full fixpoint (depth 19 — more sieve exposure at
a quarter of the kernel size) both stay in the quick tier; multi-
segment machinery (R > 1 rounds per level, multi-segment repack) is
exercised with tiny seg_rows so real segment counts appear at test
scale.
"""

import glob

import jax
import numpy as np
import pytest

from tla_raft_tpu.config import RaftConfig
from tla_raft_tpu.engine import JaxChecker
from tla_raft_tpu.models.raft import init_batch
from tla_raft_tpu.oracle import OracleChecker
from tla_raft_tpu.parallel import ShardedChecker, make_mesh

REF = RaftConfig()  # the reference Raft.cfg constants (S=3, V=2)
GOLDEN_REF = [1, 1, 3, 9, 22, 57, 136, 345, 931]  # BASELINE.md prefix
S3V1 = RaftConfig(n_vals=1, max_election=1, max_restart=1)  # S=3, K=165
S2 = RaftConfig(n_servers=2, n_vals=1, max_election=1, max_restart=1)


def _engine_with_fps(cfg, ckdir, max_depth=None, chunk=256):
    """Single-device engine run + its final fingerprint set (via the
    delta log — the engines have no store-dump API, but every level's
    new fingerprints ride in the checkpoint records)."""
    res = JaxChecker(cfg, chunk=chunk).run(
        max_depth=max_depth, checkpoint_dir=ckdir
    )
    fps = [
        np.load(f)["fps"] for f in sorted(glob.glob(ckdir + "/delta_*.npz"))
    ]
    fv0, _ff = JaxChecker(cfg, chunk=chunk)._fp_states(init_batch(cfg, 1))
    all_fps = np.unique(
        np.concatenate([np.asarray(fv0).astype(np.uint64)] + fps)
    )
    assert len(all_fps) == res.distinct
    return res, all_fps


def _assert_deep_matches(chk, got, eng, eng_fps):
    assert got.ok == eng.ok
    assert list(got.level_sizes) == list(eng.level_sizes)
    assert got.distinct == eng.distinct
    assert got.generated == eng.generated
    # final fingerprint SET equality: every engine fp sits in its
    # owner's store, and total cardinality matches — subset + equal
    # size == set equality
    D = chk.D
    assert sum(len(s) for s in chk.host_stores) == eng.distinct
    for o, s in enumerate(chk.host_stores):
        own = eng_fps[eng_fps % np.uint64(D) == o]
        assert s.contains(own).all(), f"owner {o} is missing engine fps"


@pytest.mark.slow  # tier-1 budget (PR 15): deep-vs-engine parity
# stays fast via test_deep_matches_uncompressed_exchange (4-dev) +
# test_deep_multisegment_and_oracle_parity; this is the 8-dev scale-up
def test_deep_parity_8dev_s3_vs_engine(tmp_path):
    """Tier-1 gate: 8-device sieve+compress deep sweep == single-device
    engine on an S=3 config, full fixpoint (depth >= 8), counts AND
    final fingerprint sets, with the sieve live and the exchange
    undercutting the uncompressed bytes."""
    if len(jax.devices()) < 8:
        pytest.skip("not enough virtual devices")
    eng, eng_fps = _engine_with_fps(S3V1, str(tmp_path / "eng"))
    assert eng.depth >= 8
    chk = ShardedChecker(
        S3V1, make_mesh(8), cap_x=512, deep=True, seg_rows=16,
        host_store_dir=str(tmp_path / "fps"),
    )
    got = chk.run()
    _assert_deep_matches(chk, got, eng, eng_fps)
    s = chk.meter.summary()
    assert s["sieved"] > 0, "the sieve never fired"
    assert s["exchanged_bytes"] < s["raw_bytes"]
    # per-device peak frontier rows stay well under the single-device
    # frontier (1/D sharding), even with segment quantization
    peak_level = max(eng.level_sizes)
    assert chk.peak_dev_rows < peak_level


@pytest.mark.slow  # tier-1 budget (PR 12): the 8-dev S3-vs-engine
# parity row keeps the deep path fast; this reference-constants
# depth-8 anchor is the chip-campaign acceptance row
def test_deep_parity_reference_depth8(tmp_path):
    """The acceptance run: the reference Raft.cfg constants on the
    8-device mesh to depth 8, bit-identical per-level distinct/
    generated counts vs the single-device engine, fingerprint sets
    equal, per-device peak frontier ~1/D of the resident design."""
    if len(jax.devices()) < 8:
        pytest.skip("not enough virtual devices")
    eng, eng_fps = _engine_with_fps(REF, str(tmp_path / "eng"), max_depth=8)
    assert list(eng.level_sizes) == GOLDEN_REF
    chk = ShardedChecker(
        REF, make_mesh(8), cap_x=512, deep=True, seg_rows=128,
        host_store_dir=str(tmp_path / "fps"),
    )
    got = chk.run(max_depth=8)
    _assert_deep_matches(chk, got, eng, eng_fps)
    # level 8's frontier needs 931 rows resident on ONE device in the
    # single-device engine; the deep mesh peaked at 128 rows/device
    assert chk.peak_dev_rows * 4 <= max(eng.level_sizes)
    s = chk.meter.summary()
    assert s["sieved"] > 0
    # the byte ledger is deterministic (live lane counts + quantized
    # prefixes); measured: 2.13x / 2.36x at levels 7 / 8, climbing to
    # 2.46x by level 10 (BENCH_r06.json)
    deep_lvls = [lv for lv in s["per_level"] if lv["level"] >= 7]
    assert all(lv["reduction"] >= 2 for lv in deep_lvls), deep_lvls


@pytest.mark.slow  # tier-1 budget (PR 20): deep-vs-plain counts stay
# fast via test_deep_multisegment_and_oracle_parity; the per-level
# raw-byte-ledger cross-check rides with the heavy rows
def test_deep_matches_uncompressed_exchange(tmp_path):
    """Byte-ledger cross-check: the deep path's 'raw' (uncompressed-
    equivalent) ledger must equal what the plain host-store mesh
    actually measures on the same run, and the parity triple + action
    coverage must match."""
    if len(jax.devices()) < 4:
        pytest.skip("not enough virtual devices")
    mesh = make_mesh(4)
    plain = ShardedChecker(
        S2, mesh, cap_x=256, host_store_dir=str(tmp_path / "plain"),
    )
    want = plain.run()
    deep = ShardedChecker(
        S2, mesh, cap_x=256, deep=True, seg_rows=8,
        host_store_dir=str(tmp_path / "deep"),
    )
    got = deep.run()
    assert (got.distinct, got.generated, got.depth) == (
        want.distinct, want.generated, want.depth
    )
    assert got.level_sizes == want.level_sizes
    assert got.action_counts == want.action_counts
    # same local pre-dedup => same routed candidates => the deep raw
    # ledger reproduces the plain path's measured live-lane bytes on
    # every level whose stream went out delta-packed.  Levels where the
    # packing FALLBACK fired (packed=False — the raw u64 prefix was
    # smaller than packed+header, typical for tiny early levels) have
    # no hypothetical uncompressed equivalent: what was sent IS the raw
    # form, so their raw mirror is floored at the actual bytes and the
    # per-level reduction must never read < 1 (the BENCH_r06 levels-1-2
    # inflation artifact).
    ps = plain.meter.summary()
    ds = deep.meter.summary()
    plain_by_level = {lv["level"]: lv for lv in ps["per_level"]}
    saw_fallback = False
    for lv in ds["per_level"]:
        if lv["packed"]:
            assert lv["raw_bytes"] == (
                plain_by_level[lv["level"]]["exchanged_bytes"]
            ), lv
        else:
            saw_fallback = True
            assert lv["reduction"] >= 1, lv
    assert saw_fallback, "tiny early levels should trip the fallback"
    assert ds["exchanged_bytes"] < ds["raw_bytes"]


def test_deep_multisegment_and_oracle_parity(tmp_path):
    """Tiny seg_rows forces multi-round levels (R > 1) and multi-segment
    repack (n_out > 1); counts must still match the oracle exactly."""
    if len(jax.devices()) < 2:
        pytest.skip("not enough virtual devices")
    want = OracleChecker(S2).run()
    chk = ShardedChecker(
        S2, make_mesh(2), cap_x=256, deep=True, seg_rows=2,
        host_store_dir=str(tmp_path / "fps"),
    )
    got = chk.run()
    assert got.ok == want.ok
    assert got.level_sizes == want.level_sizes
    assert got.generated == want.generated
    assert got.action_counts == want.action_counts
    # 9-state levels on 2 devices at seg_rows=2 needed > 1 segment
    assert chk.peak_dev_rows > 2


def test_deep_checkpoint_resume(tmp_path):
    """Kill/resume on the sharded-frontier path: the mdelta chain replay
    rebuilds segments and stores, and the resumed run lands on the
    uninterrupted run's exact numbers."""
    if len(jax.devices()) < 4:
        pytest.skip("not enough virtual devices")
    want = OracleChecker(S2).run()
    mesh = make_mesh(4)
    ck = str(tmp_path / "ck")
    half = ShardedChecker(
        S2, mesh, cap_x=256, deep=True, seg_rows=8,
        host_store_dir=str(tmp_path / "fps1"),
    ).run(max_depth=5, checkpoint_dir=ck)
    assert half.depth == 5
    assert len(glob.glob(ck + "/mdelta_*.npz")) == 5
    res = ShardedChecker(
        S2, mesh, cap_x=256, deep=True, seg_rows=8,
        host_store_dir=str(tmp_path / "fps2"),
    ).run(resume_from=ck, checkpoint_dir=ck)
    assert res.ok == want.ok
    assert res.distinct == want.distinct
    assert res.generated == want.generated
    assert res.level_sizes == want.level_sizes
    # the appended chain replays cleanly end to end
    res2 = ShardedChecker(
        S2, mesh, cap_x=256, deep=True, seg_rows=8,
        host_store_dir=str(tmp_path / "fps3"),
    ).run(resume_from=ck)
    assert res2.distinct == want.distinct
    assert res2.level_sizes == want.level_sizes


def test_deep_requires_host_store():
    with pytest.raises(ValueError, match="host_store_dir"):
        ShardedChecker(S2, make_mesh(2), deep=True)


def test_deep_capacities_sized_from_seg_rows(tmp_path):
    """Deep mode sizes cap_x and the sieve slab once, from seg_rows, so
    no capacity rung recompiles its programs; plain mode keeps 4096."""
    chk = ShardedChecker(
        S2, make_mesh(2), deep=True, seg_rows=1024,
        host_store_dir=str(tmp_path),
    )
    assert (chk.cap_x, chk.scap) == (8192, 131072)
    assert ShardedChecker(S2, make_mesh(2)).cap_x == 4096
