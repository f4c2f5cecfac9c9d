"""Native CPU baseline checker (native/cpubase.cpp) — differential tests.

The baseline must reproduce the oracle's per-level counts exactly: it is
an independent third implementation re-verifying the golden record
(BASELINE.md, oracle/golden.py).
"""

import json
import subprocess

import pytest

from refenv import requires_reference

from tla_raft_tpu.native import build_cpubase


@pytest.fixture(scope="module")
def binary():
    return build_cpubase()


def run_native(binary, S, V, maxE, maxR, depth, threads=2):
    out = subprocess.run(
        [binary, str(S), str(V), str(maxE), str(maxR), str(depth),
         str(threads)],
        capture_output=True, text=True, timeout=600, check=True,
    )
    return json.loads(out.stdout)


@requires_reference
def test_reference_config_matches_oracle(binary):
    from tla_raft_tpu.cfgparse import load_raft_config
    from tla_raft_tpu.oracle import OracleChecker

    cfg = load_raft_config("/root/reference/Raft.cfg")
    want = OracleChecker(cfg).run(max_depth=10)
    got = run_native(binary, cfg.S, cfg.V, cfg.max_election,
                     cfg.max_restart, 10)
    assert got["level_sizes"] == list(want.level_sizes)
    assert got["distinct"] == want.distinct
    assert got["generated"] == want.generated


@pytest.mark.slow
def test_small_configs_match_oracle(binary):
    from tla_raft_tpu.config import RaftConfig
    from tla_raft_tpu.oracle import OracleChecker

    for S, V, me, mr in ((2, 1, 1, 1), (2, 2, 2, 1), (3, 1, 2, 0)):
        cfg = RaftConfig(n_servers=S, n_vals=V, max_election=me,
                         max_restart=mr)
        want = OracleChecker(cfg).run()
        got = run_native(binary, S, V, me, mr, -1)
        assert got["level_sizes"] == list(want.level_sizes), (S, V, me, mr)
        assert got["distinct"] == want.distinct
        assert got["generated"] == want.generated
        assert got["depth"] == want.depth


def test_thread_count_invariance(binary):
    """Distinct counts are deterministic across worker counts (the
    min-canonical-full-encoding representative makes the level dedup
    thread-schedule-independent, unlike TLC's first-writer-wins)."""
    a = run_native(binary, 3, 2, 3, 3, 9, threads=1)
    b = run_native(binary, 3, 2, 3, 3, 9, threads=4)
    assert a["level_sizes"] == b["level_sizes"]
    assert a["generated"] == b["generated"]
