"""The kernel's device tables are program operands, never literals.

Every program that reads the successor kernel's tables takes them as
its first operand (``ops/tables.py``).  A program that closed over them
instead would carry them into its lowered module as dense
``stablehlo.constant`` literals: 38.6 MB at S=5.  Each program below is
lowered at small shapes on the CPU, at S=3 and at S=5, and its module
text is searched for a dense literal over 64 KiB.
"""

import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import pytest

from tla_raft_tpu import check
from tla_raft_tpu.cfgparse import load_raft_config
from tla_raft_tpu.config import RaftConfig
from tla_raft_tpu.engine import JaxChecker
from tla_raft_tpu.engine import megakernel, superstep
from tla_raft_tpu.models.raft import init_batch
from tla_raft_tpu.ops import hashstore
from tla_raft_tpu.ops import tables

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LIMIT = 64 << 10
CHUNK = 64

_ELEM_BYTES = {
    "i1": 1, "i8": 1, "ui8": 1, "i16": 2, "ui16": 2, "bf16": 2, "f16": 2,
    "i32": 4, "ui32": 4, "f32": 4, "i64": 8, "ui64": 8, "f64": 8,
}
_CONSTANT = re.compile(
    r"stablehlo\.constant dense<([^>]*)>\s*:\s*tensor<([^>]*)>"
)


def dense_literal_bytes(module_text: str) -> list:
    """Bytes of each dense (non-splat) constant in a lowered module."""
    out = []
    for m in _CONSTANT.finditer(module_text):
        body, ty = m.groups()
        if not body.startswith(('"0x', "[")):
            continue  # a splat: one value, however wide the tensor
        *dims, dtype = ty.split("x")
        n = 1
        for d in dims:
            n *= int(d)
        out.append(n * _ELEM_BYTES[dtype])
    return out


def test_literal_scan_sees_a_captured_table():
    big = jnp.arange(LIMIT, dtype=jnp.int32)  # 256 KiB
    text = jax.jit(lambda x: x + big).lower(big).as_text()
    assert max(dense_literal_bytes(text)) == 4 * LIMIT
    splat = jax.jit(lambda x: x + jnp.zeros(LIMIT, jnp.int32)).lower(big)
    assert dense_literal_bytes(splat.as_text()) == []


def _cfg(servers: int) -> RaftConfig:
    cfg = load_raft_config(os.path.join(ROOT, "Raft.cfg"))
    return dataclasses.replace(cfg, n_servers=servers)


_ENGINES: dict = {}


def _engine(servers: int) -> JaxChecker:
    if servers not in _ENGINES:
        _ENGINES[servers] = JaxChecker(_cfg(servers), chunk=CHUNK)
    return _ENGINES[servers]


def _lowered(eng: JaxChecker, program: str):
    sd = jax.ShapeDtypeStruct
    i64 = sd((), jnp.int64)
    fr0, _ovf = eng._deflate(init_batch(eng.cfg, 1))
    fr = eng._frontier_struct(fr0, CHUNK)
    span = eng._frontier_struct(fr0, eng.G * CHUNK)
    states = jax.tree.map(lambda x: sd((CHUNK,) + x.shape[1:], x.dtype),
                          init_batch(eng.cfg, 1))
    slab = sd((hashstore.MIN_CAP,), jnp.uint64)
    sieve = sd((1,), jnp.uint64)
    pay = sd((CHUNK,), jnp.int64)
    rows = sd((8,), jnp.int64)
    spec = eng.level_spec()
    lower = {
        "megakernel": lambda: megakernel.build_level_program(
            spec, False).lower(fr, slab, i64, sieve, cap_out=CHUNK),
        "superstep": lambda: superstep.build_superstep_program(
            spec, 2, False).lower(fr, slab, i64, i64, sieve, cap_f=CHUNK,
                                  ring=2 * CHUNK),
        "expand_chunk": lambda: eng._expand_chunk.lower(fr, i64, i64),
        "expand_span": lambda: eng._expand_span.lower(span, i64, i64, i64),
        "expand_group_fused": lambda: eng._expand_group_fused.lower(
            span, i64, i64, i64),
        "expand_group_gfused": lambda: eng._expand_group_gfused.lower(
            span, i64, i64, i64, slab, cap_g=4 * CHUNK),
        "mat_slice": lambda: eng._mat_slice.lower(fr, pay, i64),
        "mat_slice_seg": lambda: eng._mat_slice_seg.lower(
            fr, fr, i64, pay, i64),
        "inv_scan": lambda: eng._inv_scan.lower(states, i64),
        "deflate": lambda: eng._program(eng._deflate).lower(states),
        "audit": lambda: eng._audit_prog.lower(fr, fr, rows, rows, rows),
        "mesh_level": lambda: _lowered_mesh_level(eng.cfg),
    }
    return lower[program]()


def _lowered_mesh_level(cfg):
    """The 2-device mesh's level program (``parallel/sharded.py``), its
    operands sharded as the run places them."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tla_raft_tpu.parallel.sharded import ShardedChecker, make_mesh

    D = 2
    chk = ShardedChecker(cfg, make_mesh(D), cap_x=CHUNK)
    shard = NamedSharding(chk.mesh, P("d"))

    def sd(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=shard)

    frontier = jax.tree.map(lambda x: sd((D,) + x.shape[1:], x.dtype),
                            init_batch(cfg, 1))
    return chk.level_step.lower(
        frontier, sd((D, 1, 1), jnp.uint32), sd((D,), jnp.int64),
        sd((D * hashstore.MIN_CAP,), jnp.uint64),
    )


PROGRAMS = [
    "megakernel", "superstep", "expand_chunk", "expand_span",
    "expand_group_fused", "expand_group_gfused", "mat_slice",
    "mat_slice_seg", "inv_scan", "deflate", "audit", "mesh_level",
]


@pytest.mark.parametrize("program", PROGRAMS)
@pytest.mark.parametrize("servers", [3, 5], ids=["s3", "s5"])
def test_no_program_carries_a_table_literal(servers, program):
    text = _lowered(_engine(servers), program).as_text()
    assert max(dense_literal_bytes(text), default=0) <= LIMIT


@pytest.mark.parametrize("servers,mbytes", [(3, 1.46), (5, 38.57)],
                         ids=["s3", "s5"])
def test_tables_operand_size(servers, mbytes):
    """The operand is every table of the kernel: about 38.6 MB at S=5
    (the 120-permutation message-set hash planes are 30.9 MB of it)."""
    kern = _engine(servers).kern
    assert tables.nbytes(kern.device_tables) == pytest.approx(
        mbytes * 1e6, rel=0.01)
    if servers == 5:
        assert kern.device_tables.fpr["G_planes"].shape == (16080, 1920)


@pytest.mark.parametrize("superstep_span,stats", [
    (1, "megakernel_stats"), (4, "superstep_stats"),
], ids=["megakernel", "superstep"])
def test_summary_reports_table_bytes(superstep_span, stats):
    cfg = RaftConfig(n_servers=2, n_vals=1, max_election=1, max_restart=1)
    summary = check.run_check(cfg, chunk=CHUNK, superstep=superstep_span)
    want = tables.nbytes(JaxChecker(cfg, chunk=CHUNK).kern.device_tables)
    assert summary[stats]["table_bytes"] == want > 0


@pytest.mark.parametrize("program", ["megakernel", "superstep"])
def test_level_program_names_its_phases(program):
    """The guard matmul and the full-state P-fold carry their named
    scopes into the level programs' op metadata, where a device profile
    can split the level's time by phase."""
    text = _lowered(_engine(3), program).as_text(debug_info=True)
    assert "raft.guards" in text
    assert "raft.fp.fold" in text
