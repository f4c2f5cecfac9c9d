"""The two golden sources of the reference config agree.

``oracle/golden.py`` holds the per-level counts of Raft.cfg pinned from
the oracle and the engine; ``benchmark/reference/answers/raft-ref.json``
holds the counts the benchmark's independent C++ reference computed.
The benchmark judges the chip's answers by the second; the tests and
``chip_smoke.py`` by the first.
"""

import json
import os

from tla_raft_tpu.oracle.golden import GOLDEN_LEVELS

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ANSWERS = os.path.join(REPO, "benchmark", "reference", "answers",
                       "raft-ref.json")


def test_golden_levels_match_benchmark_answers():
    with open(ANSWERS) as f:
        doc = json.load(f)
    c = doc["constants"]
    key = (len(c["Servers"]), len(c["Vals"]), c["MaxElection"],
           c["MaxRestart"])
    assert key == (3, 2, 3, 3)
    golden = GOLDEN_LEVELS[key]
    assert doc["depths"]
    for depth, ans in doc["depths"].items():
        assert ans["level_sizes"] == golden[: int(depth) + 1], depth
        assert ans["distinct"] == sum(golden[: int(depth) + 1]), depth
