"""Run the pure-Python oracle to the FULL fixpoint of a config and dump
the totals as JSON — the second-engine cross-check for GOLDEN_FULL rows
pinned from cpubase alone (ADVICE r4 #1 / VERDICT r4 weak #3).

Usage: python scripts/oracle_fixpoint.py S V MAX_ELECTION MAX_RESTART out.json
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")  # the oracle is pure Python; it needs no chip

import dataclasses

from tla_raft_tpu.cfgparse import REPO_CFG, load_raft_config
from tla_raft_tpu.oracle import OracleChecker

S, V, ME, MR = (int(a) for a in sys.argv[1:5])
out_path = sys.argv[5]
cfg = dataclasses.replace(
    load_raft_config(REPO_CFG),
    n_servers=S, n_vals=V, max_election=ME, max_restart=MR,
)
t0 = time.monotonic()
res = OracleChecker(cfg).run(max_depth=None)
dt = time.monotonic() - t0
out = {
    "config": [S, V, ME, MR],
    "distinct": res.distinct,
    "generated": res.generated,
    "depth": res.depth,
    "ok": res.ok,
    "level_sizes": list(res.level_sizes),
    "wall_s": round(dt, 1),
    "impl": "python_oracle",
}
with open(out_path, "w") as f:
    json.dump(out, f)
print(json.dumps(out))
