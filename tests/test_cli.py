"""CLI smoke tests: the L4 launcher surface (check.py), driven the way a
user drives it (``python -m tla_raft_tpu.check ...`` — the ``-backend=jax``
leg of myrun.sh, /root/reference/myrun.sh:3).

These run in-process via ``main(argv)`` (a subprocess would re-pay jax
startup per case) on tiny configs, and assert on the TLC-shaped output
contract: the "Model checking completed" / "N states generated, M distinct"
lines, the raft.log tee, the --json summary, and the exit-code convention
(0 = clean sweep, 1 = violation found, 2 = usage error).
"""

import json

import pytest

from refenv import requires_reference

from tla_raft_tpu.check import main

TINY = ["--servers", "2", "--vals", "1", "--max-election", "1",
        "--max-restart", "1"]


def run_cli(tmp_path, *args):
    log = tmp_path / "raft.log"
    out = tmp_path / "stdout.txt"
    import contextlib
    import io

    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = main(list(args) + ["--log", str(log)])
    out.write_text(buf.getvalue())
    return rc, buf.getvalue(), log


@requires_reference
def test_clean_sweep_exit_zero_and_log_tee(tmp_path):
    rc, out, log = run_cli(tmp_path, *TINY, "--backend", "oracle")
    assert rc == 0
    assert "Model checking completed. No error has been found." in out
    assert "97 states generated, 50 distinct states found, depth 12." in out
    assert "fingerprint collision" in out
    # the tee contract: everything printed also lands in the log file
    assert log.read_text() == out


@requires_reference
def test_jax_backend_matches_oracle_counts(tmp_path):
    rc, out, _ = run_cli(tmp_path, *TINY, "--chunk", "64")
    assert rc == 0
    assert "97 states generated, 50 distinct states found, depth 12." in out


@requires_reference
def test_violation_exit_one_with_trace(tmp_path):
    # ~RaftCanCommt is a reachability probe: checking its negation MUST
    # find a violation with a replayable trace (SURVEY.md §4.3)
    rc, out, _ = run_cli(
        tmp_path, "--servers", "3", "--vals", "1", "--max-election", "1",
        "--max-restart", "0", "--backend", "oracle",
        "--invariant", "~RaftCanCommt",
    )
    assert rc == 1
    assert "Invariant" in out and "violated" in out
    assert "STATE 1" in out  # TLC-shaped numbered trace from Init


@requires_reference
def test_json_summary_line(tmp_path):
    rc, out, _ = run_cli(tmp_path, *TINY, "--backend", "oracle", "--json")
    assert rc == 0
    last = [l for l in out.strip().splitlines() if l.startswith("{")][-1]
    summary = json.loads(last)
    assert summary["distinct"] == 50
    assert summary["generated"] == 97
    assert summary["ok"] is True


def test_usage_error_exit_two(tmp_path):
    with pytest.raises(SystemExit) as ei:
        main(["--backend", "nonesuch"])
    assert ei.value.code == 2


@requires_reference
def test_mutation_is_caught_with_counterexample(tmp_path):
    # the planted FindMedian ÷2 bug (Raft.tla:65-66) must produce a
    # genuine Inv violation when compiled in (SURVEY.md §4.4)
    rc, out, _ = run_cli(
        tmp_path, "--servers", "3", "--vals", "1", "--max-election", "2",
        "--max-restart", "0", "--backend", "oracle",
        "--mutate", "median-bug",
    )
    assert rc == 1
    assert "violated" in out


@pytest.mark.parametrize("platforms", ["", "tpu", "tpu,cpu"])
def test_setup_jax_refuses_a_non_tpu_backend(monkeypatch, platforms):
    """Unless JAX_PLATFORMS puts the CPU first, a run that lands on the
    CPU (this process's backend) is an error, not a silent fallback."""
    from tla_raft_tpu.platform import setup_jax

    monkeypatch.setenv("JAX_PLATFORMS", platforms)
    with pytest.raises(RuntimeError, match="no TPU"):
        setup_jax()
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert setup_jax().default_backend() == "cpu"


def test_compile_cache_dir(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR wins untouched; unset, the cache sits at
    one fixed path inside the checkout."""
    import jax

    from tla_raft_tpu.platform import CACHE_DIR, setup_jax

    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        jax.config.update("jax_compilation_cache_dir", "/elsewhere")
        setup_jax()
        assert jax.config.jax_compilation_cache_dir == "/elsewhere"
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        setup_jax()
        assert jax.config.jax_compilation_cache_dir == CACHE_DIR
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def _run_py(code_or_args, cwd, **env):
    import os
    import subprocess
    import sys

    args = ["-c", code_or_args] if isinstance(code_or_args, str) else (
        code_or_args
    )
    return subprocess.run(
        [sys.executable, *args], cwd=cwd, capture_output=True, text=True,
        timeout=300, env=dict(os.environ, **env),
    )


def test_supervise_parent_stays_off_jax(tmp_path):
    """--supervise only relaunches children: the parent never imports
    JAX, so the chip stays free for the child."""
    import os

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    p = _run_py(
        "import subprocess, sys\n"
        "subprocess.call = lambda cmd, *a, **k: 0\n"
        "from tla_raft_tpu import check\n"
        f"rc = check.main(['--supervise', '1', '--checkpoint-dir', "
        f"{str(tmp_path)!r}, '--log', '-'])\n"
        "print('RC', rc, 'jax' in sys.modules)\n",
        repo, JAX_PLATFORMS="no_such_platform",
    )
    assert "RC 0 False" in p.stdout, p.stderr[-2000:]


def test_chip_smoke_alone_fails(tmp_path):
    """chip_smoke.py without the repo around it exits non-zero and prints
    no result line."""
    import os
    import shutil

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    shutil.copy(os.path.join(repo, "chip_smoke.py"), tmp_path)
    p = _run_py(["chip_smoke.py"], tmp_path)
    assert p.returncode != 0
    assert '"ok"' not in p.stdout


def test_chip_workers_capped_at_one(monkeypatch):
    """Pool/chaos/bench refuse a second chip-using worker (each JAX
    process claims every chip of the host); CPU-pinned workers are not
    capped."""
    from tla_raft_tpu import platform

    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    platform.check_chip_workers(1)
    with pytest.raises(ValueError, match="2 worker processes"):
        platform.check_chip_workers(2)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    platform.check_chip_workers(8)
