"""TPU compiles of the main path's device programs at real widths.

No chip is attached here: JAX's TPU compiler compiles for a described
v5e and reports what the chip's compiler would refuse (a program over
HBM, an unpartitionable op).  The shapes are those of the reference
config's depth-19 run (chunk 8192, the slab sized for ~3.4M
fingerprints, the level 18 -> 19 frontier capacities, and the superstep
window the first chip run tried: cap_f 2M rows at cap_x 49152).

Code that asks ``jax.default_backend()`` still sees the CPU here and
would take its CPU branch, so each test steers it to ``"tpu"`` while
tracing: that compiles the int8 MXU fingerprint dot that only runs on a
chip.
"""

import re

import jax
import jax.numpy as jnp
import pytest

from tla_raft_tpu.config import RaftConfig
from tla_raft_tpu.models.raft import init_batch
from tla_raft_tpu.ops import hashstore, radix

GIB = 1 << 30
HBM = 15.75 * GIB  # what XLA:TPU lets one v5e program use
CHUNK = 8192
SLAB_ROWS = hashstore.slab_rows(3_400_000)


@pytest.fixture(scope="module")
def topo():
    import os

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a TPU executable written to the persistent cache cannot be read
    # back without a chip: keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    # a failure to describe the topology fails every test here: these
    # compiles are the only HBM-fit and int8-dot gate
    try:
        yield topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def on_tpu(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


@pytest.fixture(scope="module")
def engine():
    from tla_raft_tpu.engine import JaxChecker

    cfg = RaftConfig()
    eng = JaxChecker(cfg, chunk=CHUNK)
    fr0, _ovf = eng._deflate(init_batch(cfg, 1))
    return eng, fr0


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _frontier(fr0, cap, sharding):
    return jax.tree.map(
        lambda x: _sds((cap,) + x.shape[1:], x.dtype, sharding), fr0
    )


def _hbm_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


def test_probe_and_insert_window_major(one_chip):
    """The superstep's probe over 12.6M lanes: the [W, N] probe window
    keeps lanes on the minor axis (lane-major [N, 8] padded to 128-wide
    tiles: 13.7 GiB of temp here, 26.8 GiB in the chip's superstep)."""
    n = (2_097_152 // CHUNK) * 49152
    u64 = _sds((n,), jnp.uint64, one_chip)
    compiled = jax.jit(hashstore.probe_and_insert_impl).lower(
        _sds((SLAB_ROWS,), jnp.uint64, one_chip), u64, u64,
        _sds((n,), jnp.int64, one_chip),
    ).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * GIB


def test_int8_fingerprint_dot(engine, one_chip, on_tpu):
    """The MXU fingerprint's int8 x int8 -> int32 dot (ops/fingerprint.py
    ``_plane_matmul``), which the CPU branch replaces with an i32 dot."""
    eng, fr0 = engine
    lowered = jax.jit(
        lambda f: eng.fpr.state_fingerprints(eng._inflate(f))[:2]
    ).lower(_frontier(fr0, CHUNK, one_chip))
    assert re.search(
        r"dot_general.*xi8>, tensor<[^>]*xi8>\) -> tensor<[^>]*xi32>",
        lowered.as_text(),
    )
    lowered.compile()


def test_megakernel_level_depth19(engine, one_chip, on_tpu):
    """The fused level program, level 18 (cap 1M rows) -> 19 (1.5M)."""
    from tla_raft_tpu.engine import megakernel

    eng, fr0 = engine
    prog = megakernel.build_level_program(eng.level_spec(), donate=True)
    compiled = prog.lower(
        _frontier(fr0, 1_048_576, one_chip),
        _sds((SLAB_ROWS,), jnp.uint64, one_chip),
        _sds((), jnp.int64, one_chip),
        _sds((1,), jnp.uint64, one_chip),
        cap_out=1_572_864,
    ).compile()
    assert _hbm_bytes(compiled) < HBM


def test_superstep_depth19(engine, one_chip, on_tpu):
    """The span-4 superstep over levels 17-19 at the window the first
    chip run compiled (cap_f 2M rows, cap_x grown to 49152)."""
    from tla_raft_tpu.engine import superstep

    eng, fr0 = engine
    cap_x = eng.cap_x
    eng.cap_x = 49152
    eng._jit_expand_programs()
    try:
        prog = superstep.build_superstep_program(
            eng.level_spec(), 4, donate=True
        )
        compiled = prog.lower(
            _frontier(fr0, 393_216, one_chip),
            _sds((SLAB_ROWS,), jnp.uint64, one_chip),
            _sds((), jnp.int64, one_chip),
            _sds((), jnp.int64, one_chip),
            _sds((1,), jnp.uint64, one_chip),
            cap_f=2_097_152, ring=4_194_304,
        ).compile()
    finally:
        eng.cap_x = cap_x
        eng._jit_expand_programs()
    assert _hbm_bytes(compiled) < HBM


def test_owner_dedup_order_radix(one_chip):
    """The mesh owner dedup's stable (fp_view, fp_full) order over 2^20
    lanes (ops/radix.py): no sort op, which XLA:TPU takes minutes to
    compile at this length (332 s at 2^16 lanes for a v5e)."""
    u64 = _sds((1 << 20,), jnp.uint64, one_chip)
    lowered = jax.jit(lambda q, f: radix.stable_order((q, f))).lower(u64, u64)
    assert "stablehlo.sort" not in lowered.as_text()
    assert _hbm_bytes(lowered.compile()) < GIB
