"""The control must come out as not correct.

The control is the plain reference (``reference/raftref.cpp``) put in the
checker's place, with one guarantee of the configuration broken, driving
the same checks a run makes: the warm-up block and then the window's
checks in the seed's order.  Its answers go through the same comparison
as the checker's.

* ``fold_stab1`` (the control): the SYMMETRY reduction folds only the
  permutations that fix s1 (2 of 6 at S=3, 24 of 120 at S=5), as a
  cheaper canonical form that is not an orbit invariant would.
* ``fp32``: the canonical fingerprint cut to 32 bits with a salt drawn
  per check, the narrower fingerprint a later change might take.  At the
  deep cells' sizes it merges a few states per check; at the quick
  cell's sizes (under 10^4 states a check) it is exact in practice, and
  its readings are only recorded.

By default the test runs the two 3-server cells with two window checks;
``BENCH_CONTROL_FULL=1`` runs every cell of BENCHMARK.json with as many
window checks as a run makes (``BENCH_CONTROL_CHECKS``, a JSON object of
cell -> count) on the seeds of ``BENCH_CONTROL_SEEDS``, and prints each
reading.
"""

import json
import os
import random

import pytest

from harness import compare, spec, traffic
from reference import ref

from conftest import ROOT

FULL = os.environ.get("BENCH_CONTROL_FULL") == "1"


def _seeds():
    raw = os.environ.get("BENCH_CONTROL_SEEDS", "11,2147483651,4093")
    return [int(s) for s in raw.split(",") if s]


def _cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        names = [w["name"] for w in json.load(f)["workloads"]]
    if FULL:
        return names
    return [n for n in names
            if len(spec.load_cell(n).config["constants"]["Servers"]) == 3]


def _n_checks(cell: str) -> int:
    if not FULL:
        return 2
    counts = json.loads(os.environ.get("BENCH_CONTROL_CHECKS", "{}"))
    return int(counts.get(cell, 3))


def _control_run(cell, seed: int, n: int, **control) -> dict:
    depths = traffic.warmup(cell.traffic)
    gen = traffic.checks(cell.traffic, seed)
    depths += [next(gen) for _ in range(n)]
    rng = random.Random(seed)
    results = []
    for d in depths:
        out = ref.run(cell.config["constants"], d,
                      salt=rng.getrandbits(64), **control)
        results.append(compare.CheckResult(
            max_depth=d, ok=out["ok"], depth=out["depth"],
            generated=out["generated"],
            level_sizes=tuple(out["level_sizes"]),
        ))
    return compare.compare(results, cell.answers)


def _report(tag, cell, seed, compared):
    print(f"control {tag} {cell} seed {seed}: " + ", ".join(
        f"{k} {v['value']} (limit {v['limit']})" for k, v in compared.items()
    ))


@pytest.mark.parametrize("name", _cells())
def test_control_fold_stab1_is_not_correct(name):
    cell = spec.load_cell(name)
    for seed in _seeds():
        compared = _control_run(cell, seed, _n_checks(name), fold="stab1")
        _report("fold_stab1", name, seed, compared)
        assert not compare.holds(compared)


@pytest.mark.parametrize("name", _cells())
def test_control_fp32(name):
    cell = spec.load_cell(name)
    deep = min(cell.traffic["depths"]) >= 13
    for seed in _seeds():
        compared = _control_run(cell, seed, _n_checks(name), fp_bits=32)
        _report("fp32", name, seed, compared)
        if deep and FULL:
            assert not compare.holds(compared)


@pytest.mark.parametrize("name", _cells())
def test_exact_reference_is_correct(name):
    """The same drive with the exact reference passes: the comparison
    rejects the controls for what they broke, not for the drive."""
    cell = spec.load_cell(name)
    compared = _control_run(cell, _seeds()[0], 1)
    assert compare.holds(compared)
