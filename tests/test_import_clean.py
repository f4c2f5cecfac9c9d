"""Importing the package must never initialize an XLA backend.

Module-scope ``jnp.uint64(...)`` constants used to force client creation
during pytest collection, aborting the whole tier-1 suite on hosts with
no usable backend.  The subprocess sets JAX_PLATFORMS to a nonexistent
platform: any import-time backend touch then fails loudly, while a
device-free import succeeds.
"""

import os
import subprocess
import sys

MODULES = [
    "tla_raft_tpu",
    "tla_raft_tpu.engine.bfs",
    "tla_raft_tpu.engine.megakernel",
    "tla_raft_tpu.analysis.dispatch_audit",
    "tla_raft_tpu.parallel.sharded",
    "tla_raft_tpu.parallel.exchange",
    "tla_raft_tpu.engine.forecast",
    "tla_raft_tpu.ops.fingerprint",
    "tla_raft_tpu.check",
    "tla_raft_tpu.xla_env",
    "tla_raft_tpu.analysis",
    "tla_raft_tpu.analysis.ast_lint",
    "tla_raft_tpu.analysis.sanitize",
    "tla_raft_tpu.service",
    "tla_raft_tpu.service.bucket",
    "tla_raft_tpu.service.queue",
    "tla_raft_tpu.service.daemon",
    "tla_raft_tpu.obs",
    "tla_raft_tpu.obs.telemetry",
    "tla_raft_tpu.obs.tracefile",
    "tla_raft_tpu.obs.progress",
    "tla_raft_tpu.obs.metrics",
    "tla_raft_tpu.oracle.golden",
    "tla_raft_tpu.ops.sieve",
    "tla_raft_tpu.store",
    "tla_raft_tpu.store.tiered",
]


def test_no_import_time_dispatch_static():
    """The graftlint GL001 rule is this test's static twin: the
    subprocess below proves today's imports are device-free; the rule
    keeps NEW module-scope jnp/jax calls from ever landing (the PR 1
    incident: a module-scope ``jnp.uint64(...)`` aborted collection of
    the whole tier-1 suite on XLA-less hosts)."""
    from tla_raft_tpu.analysis import ast_lint

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    findings = ast_lint.lint_paths(
        [os.path.join(repo, "tla_raft_tpu")], root=repo, select={"GL001"}
    )
    assert findings == [], "\n".join(f.format() for f in findings)


def test_imports_are_device_free():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "no_such_platform"
    env.pop("XLA_FLAGS", None)
    code = "import " + ", ".join(MODULES) + "\nprint('IMPORT_OK')"
    proc = subprocess.run(
        [sys.executable, "-c", code],
        env=env, capture_output=True, text=True, timeout=300,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "IMPORT_OK" in proc.stdout
