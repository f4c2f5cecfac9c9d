"""Pre-jax-import environment bootstrap for virtual CPU meshes.

Must run BEFORE jax (or anything that imports it) initialises a backend:
``XLA_FLAGS`` are read at backend init.  This module deliberately does
not import jax — callers do, afterwards.

Shared by tests/conftest.py, __graft_entry__.dryrun_multichip and
scripts/mesh_deep_parity.py so the flag set cannot drift between entry
points.
"""

from __future__ import annotations

import os

# Wall-clock guards, not correctness knobs: virtual devices timeshare the
# host CPU, and XLA aborts the whole process when a collective's
# participant threads miss its hard rendezvous window (hit at ~100k-state
# virtual-mesh levels on a 1-core host).  The installed jaxlib (0.9.0)
# knows both flags; XLA aborts on unknown ones.
_COLL_FLAGS = (
    " --xla_cpu_collective_call_terminate_timeout_seconds=3600"
    " --xla_cpu_collective_call_warn_stuck_timeout_seconds=600"
)


def ensure_virtual_cpu_mesh(n_devices: int = 8) -> None:
    """Point JAX at N virtual CPU devices with sane collective timeouts."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    xla = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in xla:
        xla = (
            xla + f" --xla_force_host_platform_device_count={n_devices}"
        ).strip()
    if "collective_call_terminate" not in xla:
        xla += _COLL_FLAGS
    os.environ["XLA_FLAGS"] = xla
