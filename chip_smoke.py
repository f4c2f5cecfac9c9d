"""Chip smoke: the checker's main path, end to end, on the TPU.

    python chip_smoke.py                 # one chip: ./myrun.sh -backend=jax
    python chip_smoke.py --four-chips    # four chips: --mesh 4 --mesh-deep

Runs the reference Raft.cfg (S=3, Vals=2, MaxElection=3, MaxRestart=3)
through the launcher a user calls — ``./myrun.sh -backend=jax``, which
execs ``python -m tla_raft_tpu.check`` — on the default engine (device
hash store, MXU expand, megakernel, superstep) to depth 19, and checks
the per-level counts against the pinned golden prefix
(``bench.GOLDEN_LEVELS``).  ``--four-chips`` runs the sharded deep sweep
(``--mesh 4 --mesh-deep``, its default capacities and sieve) to depth
16 instead, against the same golden, and nothing else.

This process never imports JAX: the chip belongs to the one child that
runs the check.  The child is pinned to ``JAX_PLATFORMS=tpu``, so it
fails where no TPU is attached.  Any failure exits non-zero without the
result line; the last line of a passing run is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
TIMEOUT_S = 1100  # inside the driver's 1200 s, compiles included


def fail(msg: str) -> int:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr)
    return 1


def run_child(cmd: list[str]) -> tuple[int, dict | None, float]:
    """Run the checker; stream its output; return (rc, --json, wall)."""
    env = dict(os.environ, JAX_PLATFORMS="tpu")
    print("chip_smoke: $ " + " ".join(cmd), flush=True)
    t0 = time.monotonic()
    p = subprocess.Popen(
        cmd, cwd=HERE, env=env, stdout=subprocess.PIPE, text=True,
    )
    summary = None
    try:
        for line in p.stdout:
            sys.stdout.write("  | " + line)
            if line.startswith("{"):
                summary = json.loads(line)
        rc = p.wait(timeout=TIMEOUT_S)
    except subprocess.TimeoutExpired:
        p.kill()
        p.wait()
        rc = 124
    finally:
        if p.poll() is None:
            p.kill()
            p.wait()
    return rc, summary, time.monotonic() - t0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded deep sweep on a 4-chip mesh")
    ap.add_argument("--depth", type=int, default=None,
                    help="BFS depth (default 19; 16 with --four-chips)")
    args = ap.parse_args()
    if not os.path.isdir(os.path.join(HERE, "tla_raft_tpu")):
        return fail(f"no tla_raft_tpu package next to {__file__}")
    sys.path.insert(0, HERE)
    from importlib.metadata import version

    from tla_raft_tpu.oracle.golden import GOLDEN_LEVELS
    from tla_raft_tpu.platform import CACHE_DIR

    n_chips = 4 if args.four_chips else 1
    depth = args.depth or (16 if args.four_chips else 19)
    golden = GOLDEN_LEVELS[(3, 2, 3, 3)][: depth + 1]
    print(f"chip_smoke: jax {version('jax')}, jaxlib {version('jaxlib')}, "
          f"libtpu {version('libtpu')}")
    print("chip_smoke: compile cache "
          + (os.environ.get("JAX_COMPILATION_CACHE_DIR") or CACHE_DIR))

    common = ["--max-depth", str(depth), "--chunk", "8192", "--json"]
    fps_dir = None
    if args.four_chips:
        fps_dir = tempfile.mkdtemp(prefix="chip_smoke_fps_")
        cmd = [sys.executable, "-m", "tla_raft_tpu.check", "--log", "-",
               "--mesh", "4", "--mesh-deep", "--fpstore-dir", fps_dir,
               *common]
    else:
        cmd = ["bash", "myrun.sh", "-backend=jax", "--log", "-", *common]
    try:
        rc, s, wall = run_child(cmd)
    finally:
        if fps_dir:
            shutil.rmtree(fps_dir, ignore_errors=True)
    if rc != 0:
        return fail(f"checker exited {rc}")
    if s is None:
        return fail("checker printed no --json summary")

    dev = s.get("device") or {}
    tel = s.get("telemetry") or {}
    print(f"chip_smoke: device {dev}")
    print(f"chip_smoke: plan {s.get('plan') or 'none'}")
    print(f"chip_smoke: levels {s['level_sizes']}")
    print(f"chip_smoke: golden {golden}")
    print(f"chip_smoke: distinct {s['distinct']} generated "
          f"{s['generated']} depth {s['depth']}")
    print(f"chip_smoke: megakernel {s.get('megakernel')} superstep "
          f"{s.get('superstep')} supersteps {tel.get('supersteps')} "
          f"hashstore_degraded {s.get('hashstore_degraded')}")
    print(f"chip_smoke: level_seconds {tel.get('level_seconds')}")
    print(f"chip_smoke: compile_s_per_level "
          f"{tel.get('compile_s_per_level')}")
    if args.four_chips:
        print(f"chip_smoke: mesh_deep {s.get('mesh_deep')}")
    print(f"chip_smoke: compile_s {tel.get('compile_s')} compiles "
          f"{tel.get('compiles')} cache_hits {tel.get('cache_hits')} "
          f"check_s {s.get('seconds')} wall_s {wall:.3f}")

    if dev.get("platform") != "tpu":
        return fail(f"platform {dev.get('platform')!r}, not tpu")
    if dev.get("count") != n_chips:
        return fail(f"{dev.get('count')} device(s), expected {n_chips}")
    if not s.get("ok"):
        return fail(f"checker reported a violation: {s.get('violation')}")
    if s["level_sizes"] != golden:
        return fail("level sizes differ from the golden prefix")
    if s["distinct"] != sum(golden):
        return fail(f"distinct {s['distinct']} != {sum(golden)}")
    if args.four_chips:
        md = s.get("mesh_deep") or {}
        if md.get("reactive_grows") != 0:
            return fail(f"deep mesh grew reactively: {md}")
        if not md.get("sieved"):
            return fail(f"the sieve dropped nothing: {md}")
    else:
        if s.get("hashstore_degraded"):
            return fail(f"hash store degraded: {s['hashstore_degraded']}")
        if not s.get("megakernel"):
            return fail("megakernel dropped")
        if s.get("superstep") != 4 or not tel.get("supersteps"):
            return fail("superstep span 4 not armed throughout")
    print(json.dumps({"ok": True, "device": {
        "platform": dev["platform"], "kind": dev["kind"],
        "count": dev["count"],
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
