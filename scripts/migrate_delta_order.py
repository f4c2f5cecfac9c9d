"""One-off migration: rewrite a delta log into ascending-payload order.

Why: the engine's records were historically written in canonical-
fingerprint order; replaying them forces whole-frontier parent gathers,
whose XLA:TPU lowering materializes operand-sized temporaries (~4.3 GB
at a 16.8M-row frontier — measured via memory_analysis), which OOMs the
deep-sweep replay.  Ascending-payload records replay through the
segment-windowed gather instead (temp ~ 2 uniform segments).

The migration is pure bookkeeping: level k's rows are sorted by payload
(pidx*K + slot; unique, so deterministic), and level k+1's pidx values
— which index into level k's ROW ORDER — are remapped through the sort
permutation.  base.npz and the fps/mult content are untouched; only row
order and index values change, so the replayed state SET is identical.

In-flight partial_*.npz files are DELETED whenever any level was
rewritten: a partial is keyed by group index, and group gi covers parent
ROWS [gi*G*chunk, (gi+1)*G*chunk) of the frontier — permuting the parent
level's row order changes group membership, so a value-remap of the hp
payloads would leave the saved groups covering the OLD row ranges while
fresh expansion uses the NEW ones, silently dropping the successors of
any parent that moved across a saved-group boundary (advisor finding,
round 3).  Deleting costs re-expanding one level's saved groups on
resume; correctness is not negotiable.

Usage: python scripts/migrate_delta_order.py states_delta [K]
Idempotent (sorted levels produce identity permutations).
"""

import glob
import os
import sys

import numpy as np


def main():
    ckdir = sys.argv[1] if len(sys.argv) > 1 else "states_delta"
    if len(sys.argv) > 2:
        K = int(sys.argv[2])
    else:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        import jax

        jax.config.update("jax_platforms", "cpu")
        from tla_raft_tpu.cfgparse import REPO_CFG, load_raft_config
        from tla_raft_tpu.ops.successor import get_kernel

        K = get_kernel(load_raft_config(REPO_CFG)).K
    files = sorted(glob.glob(os.path.join(ckdir, "delta_*.npz")))
    if not files:
        print(f"no delta files under {ckdir}")
        return 0
    # rank[i] = new row of old row i in the PREVIOUS level (identity for
    # the first file's parent — the base frontier order is untouched)
    rank = None
    any_changed = False
    for f in files:
        z = np.load(f)
        pidx = z["pidx"].astype(np.int64)
        slot = z["slot"].astype(np.int64)
        if rank is not None:
            pidx = rank[pidx]
        pay = pidx * K + slot
        order = np.argsort(pay)  # unique keys -> deterministic
        inv = np.empty_like(order)
        inv[order] = np.arange(len(order))
        changed = not np.array_equal(order, np.arange(len(order)))
        any_changed = any_changed or changed
        meta = z["meta"]
        out = dict(
            pidx=pidx[order].astype(z["pidx"].dtype),
            slot=slot[order].astype(z["slot"].dtype),
            fps=z["fps"][order],
            mult=z["mult"],
            meta=meta,
        )
        tmp = f + ".tmp.npz"
        np.savez(tmp, **out)
        os.replace(tmp, f)
        print(f"{os.path.basename(f)}: {'rewritten' if changed else 'already sorted'}"
              f" ({len(order)} rows)")
        rank = inv
    # partials of the in-flight level are keyed by parent ROW RANGES
    # (group gi = rows [gi*G*chunk, (gi+1)*G*chunk)); a row-order rewrite
    # invalidates that keying, so they must go — see module docstring
    partials = sorted(glob.glob(os.path.join(ckdir, "partial_*.npz")))
    for f in partials:
        if any_changed:
            os.unlink(f)
            print(f"{os.path.basename(f)}: deleted (parent row order "
                  "changed; group membership is row-range-keyed)")
        else:
            print(f"{os.path.basename(f)}: kept (no level rewritten)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
