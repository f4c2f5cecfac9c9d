"""Scanned G-chunk span programs vs the per-chunk path.

At real chunk sizes the engine expands full chunk groups with ONE
lax.scan program per G chunks instead of ~13 host dispatches per chunk
(eager per-field slices + the program) — where dispatch latency, not
compute, dominates warm levels.
These tests lower ``span_min_chunk`` so spans engage at test scale and
assert exact parity with the oracle on both the device-store and the
external-store (segmented, host-paged) paths.
"""

import pytest

import tla_raft_tpu.engine.bfs as bfs
from tla_raft_tpu.config import RaftConfig
from tla_raft_tpu.engine import JaxChecker
from tla_raft_tpu.native import HostFPStore
from tla_raft_tpu.oracle import OracleChecker

pytestmark = pytest.mark.slow

# level 11 has 2,925 states -> 92 chunks at chunk=32 > 4*G, so grouping
# (and with it the span path) engages on the deepest levels
CFG = RaftConfig(n_servers=3, n_vals=2, max_election=2, max_restart=2)


def test_device_store_span_parity():
    want = OracleChecker(CFG).run(max_depth=12)
    chk = JaxChecker(CFG, chunk=32)
    chk.span_min_chunk = 32
    got = chk.run(max_depth=12)
    assert got.ok == want.ok
    assert got.distinct == want.distinct
    assert got.generated == want.generated
    assert got.level_sizes == want.level_sizes


def test_host_store_span_parity(tmp_path, monkeypatch):
    """Spans over uniform segments: G*chunk == SEG_ROWS here, so every
    full group is exactly one segment (the deep-sweep shape)."""
    monkeypatch.setattr(bfs, "SEG_ROWS", 512)
    want = OracleChecker(CFG).run(max_depth=12)
    chk = JaxChecker(
        CFG, chunk=32, host_store=HostFPStore(str(tmp_path / "fp"))
    )
    chk.span_min_chunk = 32
    got = chk.run(max_depth=12)
    assert got.ok == want.ok
    assert got.distinct == want.distinct
    assert got.generated == want.generated
    assert got.level_sizes == want.level_sizes
