"""CLI: ``python -m tla_raft_tpu.tune`` — probe-search a regime and
commit the winner to the plan cache; ``show`` prints the cache.

    python -m tla_raft_tpu.tune --servers 2 --vals 1 \\
        --max-election 1 --max-restart 1 --max-depth 8 --out plans.json
    python -m tla_raft_tpu.tune show [--plan PATH]
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys

from ..cfgparse import REPO_CFG, load_raft_config
from . import plans, search


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="python -m tla_raft_tpu.tune")
    p.add_argument("cmd", nargs="?", default="tune",
                   choices=("tune", "show"))
    p.add_argument("--config", default=REPO_CFG)
    p.add_argument("--backend", default="jax")
    p.add_argument("--servers", type=int, default=None)
    p.add_argument("--vals", type=int, default=None)
    p.add_argument("--max-election", type=int, default=None)
    p.add_argument("--max-restart", type=int, default=None)
    p.add_argument("--max-depth", type=int, default=6,
                   help="probe depth cap (short prefixes; default 6)")
    p.add_argument("--repeats", type=int, default=1,
                   help="probes per candidate, best-of (default 1)")
    p.add_argument("--top-k", type=int, default=2,
                   help="measured candidates per knob after prior "
                        "ranking (default 2)")
    p.add_argument("--dev-bytes", type=float, default=None,
                   help="tiered hot budget the tuned regime targets "
                        "(feeds the HBM prune)")
    p.add_argument("--out", default=None, metavar="PATH",
                   help="plan cache to commit into (default: the "
                        "TLA_RAFT_PLAN-active cache)")
    p.add_argument("--dry-run", action="store_true",
                   help="search but do not commit")
    p.add_argument("--json", action="store_true")
    args = p.parse_args(argv)

    if args.cmd == "show":
        path = args.out or plans.plan_path()
        doc = plans.load_cache(path) if path else None
        if doc is None:
            print(f"no readable plan cache at {path}", file=sys.stderr)
            return 1
        print(json.dumps(doc, indent=1, sort_keys=True))
        return 0

    cfg = load_raft_config(args.config)
    overrides = {
        k: v for k, v in dict(
            n_servers=args.servers, n_vals=args.vals,
            max_election=args.max_election, max_restart=args.max_restart,
        ).items() if v is not None
    }
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)

    res = search.tune(
        cfg, backend=args.backend,
        path=args.out,
        commit=not args.dry_run,
        max_depth=args.max_depth, repeats=args.repeats,
        top_k=args.top_k,
        dev_bytes=int(args.dev_bytes) if args.dev_bytes else None,
        out=None if args.json else sys.stderr,
    )
    if args.json:
        res = dict(res)
        res.pop("ledger", None)
        print(json.dumps(res, sort_keys=True))
    else:
        committed = res.get("committed")
        print(
            f"{res['regime']}: winner committed to {committed}"
            if committed else f"{res['regime']}: dry run (no commit)"
        )
    return 0


if __name__ == "__main__":
    sys.exit(main())
