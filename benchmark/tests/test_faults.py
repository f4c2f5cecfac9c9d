"""A whole run on the CPU, past the harness's look for a chip: sound, it
is correct; with the timed path broken underneath, it is not.

Each fault is planted where the answer is produced, in what
``run_check`` returns, so the window's own checks carry it:

* an answer altered: one level's count of new states off by one;
* a step that returns its state unchanged: the last level gives back
  the frontier it was handed (its count repeats the level before);
* half of the batch left out: every level keeps half of its states;
* the verdict flipped.

The cells run on one chip, so there is no exchange between chips to
leave out.
"""

import json

import pytest

from harness import device, main

from tla_raft_tpu import check


def _flip(s):
    s["ok"] = not s["ok"]


def _alter(s):
    s["level_sizes"][-1] += 1


def _unchanged(s):
    s["level_sizes"][-1] = s["level_sizes"][-2]


def _half(s):
    s["level_sizes"] = [s["level_sizes"][0]] + [
        x // 2 for x in s["level_sizes"][1:]
    ]
    s["generated"] //= 2


FAULTS = {"answer_altered": _alter, "state_unchanged": _unchanged,
          "half_batch": _half, "verdict_flipped": _flip}


def _run(tiny_root, monkeypatch, capsys, seed=5):
    import jax

    monkeypatch.setattr(device, "require", lambda chips: jax.devices()[:1])
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                       str(tiny_root / ".jax_cache"))
    rc = main.main(["--workload", "ref-tiny", "--seed", str(seed),
                    "--seconds", "0.5", "--trace", "0"],
                   root=str(tiny_root), bench_dir=str(tiny_root / "benchmark"))
    assert rc == 0
    captured = capsys.readouterr()
    out = json.loads(captured.out.strip().splitlines()[-1])
    assert list(out)[-1] == "compared"
    # the numbers compared are the last lines on standard error
    tail = captured.err.strip().splitlines()[-len(out["compared"]):]
    assert all(line.startswith("compared ") for line in tail)
    return out


def test_sound_run_is_correct(tiny_root, monkeypatch, capsys):
    out = _run(tiny_root, monkeypatch, capsys)
    assert out["correct"] is True
    assert out["failed"] == 0 and out["attempted"] >= 1
    assert out["device"]["platform"] == "cpu"
    assert set(out["metrics"]) == {"states_per_s", "setup_s"}
    assert out["metrics"]["states_per_s"]["value"] > 0


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_planted_fault_is_not_correct(fault, tiny_root, monkeypatch, capsys):
    real = check.run_check

    def broken(cfg, **kw):
        s = real(cfg, **kw)
        FAULTS[fault](s)
        return s

    monkeypatch.setattr(check, "run_check", broken)
    out = _run(tiny_root, monkeypatch, capsys)
    assert out["correct"] is False
    assert out["failed"] == out["attempted"]


def test_no_tpu_means_no_result(capsys):
    # JAX_PLATFORMS=cpu here: the harness must refuse, not fall back
    rc = main.main(["--workload", "ref-deep", "--seed", "1",
                    "--seconds", "1", "--trace", "0"])
    assert rc == 2
    assert capsys.readouterr().out.strip() == ""
