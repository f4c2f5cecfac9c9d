"""Synthetic sweep-queue generator: the 1k-config production stand-in.

Production sweep traffic (ROADMAP item 2) is huge numbers of small
configs: CI matrices and parameter sweeps that vary one CONSTANT at a
time around a few base models.  This generator reproduces that shape
deterministically: a few (S, Vals, MaxElection) base keys, each swept
across a MaxRestart window (the service's free bucket axis) and a mix
of depth caps — so a synthetic queue of N jobs lands in a handful of
shape buckets with tens-to-hundreds of configs each, exactly the
distribution the config-batched scheduler exists to amortize.

Usage:
  python scripts/queue_synth.py --root /tmp/q --jobs 1000 [--seed 1] \
      [--mr-width 16] [--chunk 64] [--dry]

Importable: ``synth_jobs(n, seed, mr_width)`` returns the job list
(cfg, max_depth, options) without touching disk.
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tla_raft_tpu.config import RaftConfig  # noqa: E402

# base model keys (S, V, MaxElection), smallest first: the synthetic
# "per-user models".  All are seconds-class state spaces per config so
# a 1k-job queue stays a bench, not a campaign.
BASE_KEYS = [
    (2, 1, 1),
    (2, 1, 2),
    (3, 1, 1),
    (2, 2, 1),
]
# depth-cap mix: most sweeps run to fixpoint, some are shallow CI runs
DEPTH_CAPS = [None, None, None, 6, 9]


def synth_jobs(n: int, seed: int = 1, mr_width: int = 16,
               chunk: int = 64):
    """Deterministic job list: [(cfg, max_depth, options)] * n."""
    out = []
    x = seed & 0x7FFFFFFF
    for i in range(n):
        # xorshift steps keep the mix deterministic per (seed, i)
        x ^= (x << 13) & 0xFFFFFFFF
        x ^= x >> 17
        x ^= (x << 5) & 0xFFFFFFFF
        s, v, me = BASE_KEYS[i % len(BASE_KEYS)]
        mr = i // len(BASE_KEYS) % mr_width
        cap = DEPTH_CAPS[x % len(DEPTH_CAPS)]
        cfg = RaftConfig(
            n_servers=s, n_vals=v, max_election=me, max_restart=mr,
        )
        out.append((cfg, cap, dict(chunk=chunk)))
    return out


def main() -> int:
    p = argparse.ArgumentParser(prog="queue_synth")
    p.add_argument("--root", required=True)
    p.add_argument("--jobs", type=int, default=1000)
    p.add_argument("--seed", type=int, default=1)
    p.add_argument("--mr-width", type=int, default=16,
                   help="MaxRestart sweep window per base key (the "
                        "bucket width the scheduler can batch)")
    p.add_argument("--chunk", type=int, default=64)
    p.add_argument("--dry", action="store_true",
                   help="print the job mix without submitting")
    args = p.parse_args()
    jobs = synth_jobs(args.jobs, args.seed, args.mr_width, args.chunk)
    if args.dry:
        from collections import Counter

        mix = Counter(
            (c.S, c.V, c.max_election, c.max_restart, d)
            for c, d, _ in jobs
        )
        for k, cnt in sorted(mix.items()):
            print(f"S{k[0]} V{k[1]} ME{k[2]} MR{k[3]} depth{k[4]}: {cnt}")
        print(f"{len(jobs)} jobs over {len(set(k[:3] for k in mix))} "
              "shape keys")
        return 0
    from tla_raft_tpu.service.queue import JobQueue

    q = JobQueue(args.root)
    for cfg, cap, opt in jobs:
        jid = q.submit(cfg, max_depth=cap, options=opt)
        print(jid)
    return 0


if __name__ == "__main__":
    sys.exit(main())
