"""graftlint GL013: per-kernel XLA cost/memory budget audit.

GL010 froze the gather win at the jaxpr level and GL011 froze the
fusion win at the dispatch level; this rule freezes the COST level:
every registered hot kernel is lowered + compiled at the audit's tiny
reference shapes (the same S2V1E1R1 space the jaxpr audit traces), its
``cost_analysis()`` + ``memory_analysis()`` harvested
(analysis/devprof.py), and the result diffed against a committed
ledger (``cost_ledger.json``, beside ``golden_ledger.json``).  A hot
kernel whose FLOPs, bytes accessed or temp-HBM exceed the ledgered
budget (plus a small slack) is a HARD failure on the generating
backend+jax version: a regression in any of the three is exactly the
silent-perf-drift class docs/PERF.md fought one incident at a time —
the ~750 GB/chunk coefficient-gather reads (Round 1) were FOUND via
cost_analysis, and the 4.3 GB materialize temp blow-up (Finding 5) via
memory_analysis; this rule turns those one-off profiler sessions into
a committed, CI-diffed gate.

Cross-version/backend runs demote the diff to warnings (XLA's cost
model and lowering legitimately drift across releases and backends)
while keeping the harvest itself exercised.  Shrinking below budget
past the slack trips the "regenerate and bank the win" warning,
mirroring GL010/GL011.  Regenerate with
``python -m tla_raft_tpu.analysis --write-ledger`` and justify the
diff in the PR.
"""

from __future__ import annotations

import json
import os

from . import devprof

COST_LEDGER_PATH = os.path.join(
    os.path.dirname(__file__), "cost_ledger.json"
)

# the budget metrics and their relative slack: flops/bytes are
# deterministic for one backend+version (slack absorbs sub-% cost-model
# jitter); temp allocation depends on buffer-assignment heuristics and
# gets more headroom
BUDGETS = {
    "flops": 0.02,
    "bytes": 0.02,
    "tmp_b": 0.10,
}


def _tiny_cfg():
    from ..config import RaftConfig

    # the jaxpr/dispatch audits' reference space (50 states, depth 12)
    return RaftConfig(
        n_servers=2, n_vals=1, max_election=1, max_restart=1,
    )


def compiled_registry():
    """name -> zero-arg callable returning a COMPILED executable at the
    audit's tiny reference shapes.

    Covers the program-build sites the device-cost observatory
    harvests at runtime: the fused whole-level megakernel, the
    multi-level superstep driver, the hashstore probe kernels, the MXU
    expand pair (guards + materialize) with the dense-expand core, and
    the tiered store's compaction program."""
    import jax
    import jax.numpy as jnp

    from ..engine import megakernel as megakernel_mod
    from ..engine import superstep as superstep_mod
    from ..engine.bfs import JaxChecker
    from ..models.raft import init_batch
    from ..ops import hashstore
    from ..ops import sieve as sieve_mod
    from ..ops.successor import get_kernel
    from ..store import tiered as tiered_mod

    cfg = _tiny_cfg()
    kern = get_kernel(cfg, mxu=True)
    st = init_batch(cfg, 8)
    slots = jnp.zeros((8,), jnp.int64)
    fps = jnp.zeros((256,), jnp.uint64)
    slab = jnp.zeros((hashstore.MIN_CAP,), jnp.uint64)
    pays = jnp.zeros((256,), jnp.int64)
    msum = kern.fpr.msg_hash(st.msgs)

    def _compile(fn, *args, **statics):
        if not hasattr(fn, "lower"):  # a plain function, not a program
            fn = jax.jit(fn, static_argnames=tuple(statics) or None)
        return fn.lower(*args, **statics).compile()

    def _mega():
        eng = JaxChecker(cfg, chunk=64, use_hashstore=True,
                         megakernel=True)
        fr0, _ovf = eng._deflate(init_batch(cfg, 1))
        fr = eng._frontier_struct(fr0, 64)
        prog = megakernel_mod.build_level_program(
            eng.level_spec(), donate=False
        )
        return prog.lower(
            fr, jax.ShapeDtypeStruct((hashstore.MIN_CAP,), jnp.uint64),
            jax.ShapeDtypeStruct((), jnp.int64),
            jax.ShapeDtypeStruct((1,), jnp.uint64), cap_out=64,
        ).compile()

    def _sstep():
        eng = JaxChecker(cfg, chunk=64, use_hashstore=True,
                         megakernel=True)
        fr0, _ovf = eng._deflate(init_batch(cfg, 1))
        fr = eng._frontier_struct(fr0, 64)
        prog = superstep_mod.build_superstep_program(
            eng.level_spec(), span=2, donate=False
        )
        s_i64 = jax.ShapeDtypeStruct((), jnp.int64)
        return prog.lower(
            fr, jax.ShapeDtypeStruct((hashstore.MIN_CAP,), jnp.uint64),
            s_i64, s_i64, jax.ShapeDtypeStruct((1,), jnp.uint64),
            cap_f=64, ring=128,
        ).compile()

    def _tiered():
        eng = JaxChecker(cfg, chunk=64, use_hashstore=True)
        fr0, _ovf = eng._deflate(init_batch(cfg, 1))
        fr = eng._frontier_struct(fr0, 64)
        return jax.jit(tiered_mod.drop_rows_impl).lower(
            fr, jax.ShapeDtypeStruct((64,), jnp.bool_),
            jax.ShapeDtypeStruct((), jnp.int64),
        ).compile()

    return {
        "successor.expand_guards":
            lambda: _compile(kern.expand_guards, st),
        "successor.materialize":
            lambda: _compile(kern.materialize, st, slots),
        "dense.expand":
            lambda: _compile(kern.expand, st, msum),
        "hashstore.probe":
            lambda: _compile(hashstore.probe_impl, slab, fps),
        "hashstore.probe_and_insert":
            lambda: _compile(
                hashstore.probe_and_insert_impl, slab, fps, fps, pays
            ),
        "engine.megakernel_level": _mega,
        "engine.superstep": _sstep,
        "store.tiered_compact": _tiered,
        "ops.sieve_probe":
            lambda: _compile(
                sieve_mod.probe_impl, jnp.zeros((64,), jnp.uint64), fps
            ),
    }


def build_ledger() -> dict:
    import jax

    ledger = {
        "_meta": {
            "jax": jax.__version__,
            "backend": jax.default_backend(),
            "config": "S2V1E1R1",
            "metrics": list(devprof.METRIC_KEYS),
            "budgets": {k: f"+{int(v * 100)}%" for k, v in
                        BUDGETS.items()},
        }
    }
    for name, make in compiled_registry().items():
        metrics = devprof.harvest_compiled(make())
        if metrics is None:
            metrics = dict.fromkeys(devprof.METRIC_KEYS, 0)
        metrics["peak_b"] = devprof.peak_bytes(metrics)
        ledger[name] = metrics
    return ledger


def load_golden(path: str = COST_LEDGER_PATH) -> dict | None:
    if not os.path.exists(path):
        return None
    with open(path, encoding="utf-8") as fh:
        return json.load(fh)


def write_golden(ledger: dict, path: str = COST_LEDGER_PATH):
    with open(path, "w", encoding="utf-8") as fh:
        json.dump(ledger, fh, indent=1, sort_keys=True)
        fh.write("\n")


def diff_entry(name: str, gold: dict, cur: dict
               ) -> tuple[list[str], list[str]]:
    """(over-budget failures, bank-the-win warnings) for one kernel."""
    failures: list[str] = []
    warnings: list[str] = []
    for metric, slack in BUDGETS.items():
        g = float(gold.get(metric, 0) or 0)
        c = float(cur.get(metric, 0) or 0)
        if g <= 0:
            # a zero budget is exact: any appearance is a regression
            # (e.g. a temp-free kernel growing temps)
            if c > 0:
                failures.append(
                    f"[GL013] {name}: {metric} regressed 0 -> {c:,.0f}"
                    " — the kernel grew a cost class it did not have; "
                    "justify a new budget with --write-ledger"
                )
            continue
        if c > g * (1.0 + slack):
            failures.append(
                f"[GL013] {name}: {metric} {c:,.0f} exceeds the "
                f"ledgered budget {g:,.0f} (+{100 * slack:.0f}% slack)"
                " — the hot kernel's device cost regressed "
                "(docs/PERF.md); fix the kernel or justify a new "
                "budget with --write-ledger"
            )
        elif c < g * (1.0 - slack):
            warnings.append(
                f"[GL013] {name}: {metric} {c:,.0f} is under the "
                f"ledgered {g:,.0f} — regenerate with --write-ledger "
                "and bank the win"
            )
    return failures, warnings


def audit(golden=None, current: dict | None = None
          ) -> tuple[list[str], list[str]]:
    """Run the GL013 audit; returns (failures, warnings).

    Hard on the ledger's own backend + jax version; demoted to
    warnings when either differs (cost models drift across releases
    and backends, and a TPU ledger must not fail a CPU CI box)."""
    import jax

    failures: list[str] = []
    warnings: list[str] = []
    if golden is None:
        golden = load_golden()
    if golden is None:
        warnings.append(
            "[GL013] no cost ledger committed — run `python -m "
            "tla_raft_tpu.analysis --write-ledger` and commit "
            "cost_ledger.json"
        )
        return failures, warnings
    if current is None:
        current = build_ledger()
    meta = golden.get("_meta", {})
    same_env = (
        meta.get("jax") == jax.__version__
        and meta.get("backend") == jax.default_backend()
    )
    sink = failures if same_env else warnings
    for name, cur in current.items():
        if name == "_meta":
            continue
        gold = golden.get(name)
        if gold is None:
            sink.append(
                f"[GL013] {name}: kernel missing from the cost ledger "
                "— regenerate with --write-ledger"
            )
            continue
        f, w = diff_entry(name, gold, cur)
        sink.extend(f)
        warnings.extend(w)
    for name in golden:
        if name != "_meta" and name not in current:
            sink.append(
                f"[GL013] {name}: in the cost ledger but no longer "
                "registered"
            )
    if not same_env:
        warnings.append(
            f"[GL013] cost ledger was generated on "
            f"{meta.get('backend')}/jax {meta.get('jax')}, running "
            f"{jax.default_backend()}/jax {jax.__version__} — budget "
            "diff demoted to warnings"
        )
    return failures, warnings
