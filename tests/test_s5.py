"""Scale config: 5-server model (the s4/s5 dial, Raft.cfg:16-17).

The reference pre-declares ``s4, s5`` as the scale-up path (BASELINE.md
configs 3-5).  These tests prove the whole stack — message universe,
guard tables, successor kernel, fingerprints (120 server permutations),
engine — is correct at S=5, not just built:

* sampled expand/materialize differential vs the oracle on reachable
  states at reference-like bounds,
* full engine-vs-oracle BFS parity on a bounded 5-server space,
* the engine at the cfg's own bounds against the independent C++
  reference's committed answers (benchmark/reference/answers).

The 5-server cfg is the repo's ``Raft.cfg`` with its declared s4/s5
servers switched in, as the benchmark's ``raft-s5`` configuration is.
"""

import collections
import dataclasses
import json
import os

import numpy as np
import pytest

from tla_raft_tpu.cfgparse import load_raft_config
from tla_raft_tpu.config import RaftConfig
from tla_raft_tpu.engine import JaxChecker
from tla_raft_tpu.models.raft import from_oracle
from tla_raft_tpu.ops.successor import get_kernel
from tla_raft_tpu.oracle import OracleChecker
from tla_raft_tpu.oracle.explicit import init_state, successors

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ANSWERS = os.path.join(ROOT, "benchmark", "reference", "answers",
                       "raft-s5.json")


@pytest.fixture(scope="module")
def cfg5():
    cfg = load_raft_config(os.path.join(ROOT, "Raft.cfg"))
    return dataclasses.replace(cfg, n_servers=5)


def collect(cfg, n):
    seen, order, frontier = {init_state(cfg)}, [init_state(cfg)], [init_state(cfg)]
    while frontier and len(order) < n:
        nxt = []
        for st in frontier:
            for _a, _s, _d, ch in successors(cfg, st):
                if ch not in seen:
                    seen.add(ch)
                    order.append(ch)
                    nxt.append(ch)
        frontier = nxt
    return order[:n]


def test_universe_dimensions(cfg5):
    kern = get_kernel(cfg5)
    assert kern.fpr.P == 120  # 5! server permutations folded into the hash
    assert kern.uni.M == 16080
    assert kern.K == 1900


def test_expand_matches_oracle_s5(cfg5):
    """Sampled differential at full reference bounds (S=5, V=2, E=R=3)."""
    kern = get_kernel(cfg5)
    fpr = kern.fpr
    states = collect(cfg5, 48)
    batch = from_oracle(cfg5, states)
    _, _, msum = fpr.state_fingerprints(batch)
    exp = kern.expand(batch, msum)
    valid = np.asarray(exp.valid)
    mult = np.asarray(exp.mult)
    fpv = np.asarray(exp.fp_view)
    assert not np.asarray(exp.abort).any()

    all_succs = [successors(cfg5, st) for st in states]
    flat = [ch for ss in all_succs for _a, _s, _d, ch in ss]
    ev, _, _ = fpr.state_fingerprints(from_oracle(cfg5, flat))
    ev = np.asarray(ev)
    off = 0
    for i, succs in enumerate(all_succs):
        assert int(mult[i][valid[i]].sum()) == len(succs), f"state {i}"
        want = collections.Counter(ev[off : off + len(succs)].tolist())
        off += len(succs)
        got = collections.Counter()
        for k in np.nonzero(valid[i])[0]:
            got[int(fpv[i, k])] += int(mult[i, k])
        assert got == want, f"state {i}"


def _s5_small(cfg5):
    return dataclasses.replace(cfg5, max_election=1, max_restart=0, n_vals=1)


def _reference_s3(_cfg5):
    return RaftConfig(n_servers=3, n_vals=2, max_election=3, max_restart=3)


@pytest.mark.parametrize("make,depth", [
    (_s5_small, 9),
    (_reference_s3, 6),
], ids=["s5-small-oracle", "s3-golden"])
def test_engine_parity_s5(cfg5, make, depth):
    """Full BFS parity of the engine (programs over the kernel's tables
    operand) with the oracle on a bounded 5-server space, and with the
    pinned golden prefix of the 3-server reference config."""
    from bench import GOLDEN_LEVELS

    cfg = make(cfg5)
    e = JaxChecker(cfg, chunk=64).run(max_depth=depth)
    assert e.ok
    if cfg.S == 3:
        assert list(e.level_sizes) == GOLDEN_LEVELS[(3, 2, 3, 3)][: depth + 1]
        return
    o = OracleChecker(cfg).run(max_depth=depth)
    assert o.ok
    assert e.level_sizes == o.level_sizes == (1, 1, 1, 2, 2, 3, 3, 6, 15, 36)
    assert e.generated == o.generated
    assert e.action_counts == o.action_counts


def test_engine_matches_reference_answers_s5(cfg5):
    """The engine at the 5-server cfg's own bounds equals the C++
    reference's committed answer at depth 5: verdict, depth, generated
    count and every level's new states."""
    with open(ANSWERS) as f:
        want = json.load(f)["depths"]["5"]
    r = JaxChecker(cfg5, chunk=64).run(max_depth=5)
    assert (r.ok, r.depth, r.generated) == (want["ok"], want["depth"], 388)
    assert r.generated == want["generated"]
    assert list(r.level_sizes) == want["level_sizes"]
