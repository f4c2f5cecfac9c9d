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

# Pin the suite to the hand-set performance defaults: with the committed
# autotuned plan cache (tla_raft_tpu/tune/plans.json) active, every
# run_check would resolve tuned spans/windows for matching regimes and
# the suite's dispatch-budget assertions would measure the plan, not the
# engine.  Counts are bit-identical either way (tests/test_tune.py pins
# that); the plan-on path is exercised by the tune tests' explicit plan
# paths and the CI autotune job.
os.environ.setdefault("TLA_RAFT_PLAN", "0")
