"""Deep virtual-mesh parity run (VERDICT round 2, missing #3).

Runs the reference config on an 8-device virtual CPU mesh to a depth
where the mesh's capacity machinery (cap_r routing skew, vcap growth,
store trim) actually gets exercised (default depth 14, ~186k distinct
states — an hour-class single-CPU job), asserting EXACT per-level parity
with the pinned golden prefix, with mdelta checkpointing on and one
mid-flight kill/resume cycle.

Usage: python scripts/mesh_deep_parity.py [depth] [ckdir]
Writes a JSON result line to stdout and docs/MESH_DEEP.json.
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tla_raft_tpu.xla_env import ensure_virtual_cpu_mesh  # noqa: E402

ensure_virtual_cpu_mesh(8)

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

GOLDEN = [1, 1, 3, 9, 22, 57, 136, 345, 931, 2468, 5881, 12505, 24705,
          47599, 91014, 169607, 301664, 511609, 839797, 1353766]


def main():
    import time

    from tla_raft_tpu.cfgparse import REPO_CFG, load_raft_config
    from tla_raft_tpu.parallel import ShardedChecker, make_mesh

    import glob

    import numpy as np

    depth = int(sys.argv[1]) if len(sys.argv) > 1 else 14
    ckdir = sys.argv[2] if len(sys.argv) > 2 else "/tmp/mesh_deep_ck"
    os.makedirs(ckdir, exist_ok=True)
    resumable = sorted(glob.glob(os.path.join(ckdir, "mdelta_*.npz")))
    # a usable partial chain must (a) leave levels to run — a completed
    # chain would make "resume" a pure replay exercising no kill/resume
    # cycle — and (b) match the golden prefix level for level (a chain
    # left by a run that failed its golden assert must not eat another
    # hour-class phase 2 before failing again)
    if resumable and len(resumable) < depth:
        chain_sizes = [
            int(np.load(f)["meta"][1]) for f in resumable
        ]
        if chain_sizes != GOLDEN[1 : len(chain_sizes) + 1]:
            print(f"[mesh] existing chain diverges from golden "
                  f"({chain_sizes} vs {GOLDEN[1:len(chain_sizes)+1]}); "
                  "starting clean", file=sys.stderr, flush=True)
            resumable = []
    elif resumable:
        resumable = []
    if not resumable:
        for f in os.listdir(ckdir):
            os.unlink(os.path.join(ckdir, f))

    cfg = load_raft_config(REPO_CFG)
    mesh = make_mesh(8)
    # capacity sizing is now the ENGINE's job (run(presize=True) default,
    # engine/forecast.py): it forecasts cap_x/vcap for the whole run at
    # the first trustworthy level and resizes BEFORE compiling, so
    # growth-triggered recompiles of the 8-device collective program
    # (>1 h each on this 1-core host — the round-4 depth-14 killer)
    # never fire.  The script only supplies a measured candidate-peak
    # CEILING so a forecast overshoot can't inflate the one big compile.
    # Level 14 measured: pre-dedup candidates exceed 32k on the peak
    # device (the round-4 "20k/device" note undercounted duplicates) —
    # the engine's own unclamped forecast (65536) is the right size.
    cap_x = 8192
    cap_x_max = 8192 if depth <= 13 else 65536
    t0 = time.monotonic()
    levels = []

    def progress(s):
        levels.append((s["level"], s["frontier"], round(s["elapsed"], 1)))
        print(f"[mesh] level {s['level']}: frontier {s['frontier']}, "
              f"distinct {s['distinct']}, {s['elapsed']:.0f}s",
              file=sys.stderr, flush=True)

    if resumable:
        # an interrupted earlier run left a chain — resuming IT is the
        # kill/resume cycle; skip phase 1
        resumed_at = len(resumable)
        print(f"[mesh] resuming existing chain at depth {resumed_at}",
              file=sys.stderr, flush=True)
    else:
        # phase 1: run to depth-4 short of the target, checkpointing
        chk = ShardedChecker(cfg, mesh, cap_x=cap_x, vcap=1 << 16,
                             cap_x_max=cap_x_max, progress=progress)
        half = chk.run(max_depth=depth - 4, checkpoint_dir=ckdir)
        assert half.ok, half.violation
        assert list(half.level_sizes) == GOLDEN[: depth - 3], half.level_sizes
        resumed_at = depth - 4

    # phase 2: a FRESH checker resumes from the mdelta log (the kill/
    # resume cycle) and finishes the run
    chk2 = ShardedChecker(cfg, mesh, cap_x=cap_x, vcap=1 << 16,
                          cap_x_max=cap_x_max, progress=progress)
    res = chk2.run(max_depth=depth, checkpoint_dir=ckdir,
                   resume_from=ckdir)
    dt = time.monotonic() - t0
    ok = res.ok and list(res.level_sizes) == GOLDEN[: depth + 1]
    out = dict(
        ok=ok, depth=res.depth, distinct=res.distinct,
        generated=res.generated, level_sizes=list(res.level_sizes),
        golden_match=list(res.level_sizes) == GOLDEN[: depth + 1],
        seconds=round(dt, 1), devices=8, cap_x_final=chk2.cap_x,
        vcap_final=chk2.vcap, exchange="all_to_all",
        resumed_at_depth=resumed_at,
        # reactive growth events = presize forecast misses; the whole
        # point of predictive sizing is that this stays 0
        reactive_grows=chk2.reactive_grows,
    )
    print(json.dumps(out))
    with open("docs/MESH_DEEP.json", "w") as f:
        json.dump(out, f, indent=1)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
