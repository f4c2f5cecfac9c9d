"""Test configuration: force an 8-device virtual CPU mesh.

Multi-chip TPU hardware is not available in CI; sharding paths are tested on
a virtual CPU mesh per the project environment contract. Must run before any
jax import.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tla_raft_tpu.xla_env import ensure_virtual_cpu_mesh  # noqa: E402

ensure_virtual_cpu_mesh(8)
