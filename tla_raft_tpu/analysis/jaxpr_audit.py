"""graftlint layer 2: primitive-level audit of the registered hot kernels.

The AST layer sees source; this layer sees what XLA will actually be
asked to run.  Each registered kernel (dense expand, fingerprint,
successor guards/materialize, exchange pack) is lowered to a closed
jaxpr on a tiny reference config and walked recursively:

* **hard failures** — primitives that must never appear in a
  single-device kernel regardless of ledger state: host callbacks
  (``pure_callback``/``io_callback``/... — a hidden per-dispatch host
  round-trip), cross-device collectives (these kernels are composed
  INSIDE shard_map bodies; a collective baked into one would nest
  axis semantics and deadlock the mesh), and any float64 value (the
  kernels are integer algebra end to end; an f64 appearing means an
  accidental promotion that doubles HBM traffic on the MXU path).
* **ledger diff** — the full per-kernel primitive histogram (plus a
  pseudo-entry counting 64->32-bit integer ``convert_element_type``
  narrowings — the PR 1 overflow class at the jaxpr level) is diffed
  against a committed golden ledger.  Any drift fails: a new gather in
  the fingerprint kernel or an extra convert in dense expand is exactly
  the silent-regression class that erases kernel wins one primitive at
  a time.

* **GL010 — gather/scatter budget** — the hot expand kernels (guards,
  materialize, dense expand, and their retained legacy A/B twins) each
  carry a *budget* of data-indexed ``gather`` and ``scatter*``
  primitives equal to their ledgered count.  Exceeding the budget is a
  HARD failure even across jax versions: the budget is semantic (the
  MXU-native expand exists precisely to kill this primitive class —
  the launch-cost cliff of docs/PERF.md), not a lowering artifact.
  Shrinking below budget only trips the ordinary ledger diff, which
  says "regenerate and bank the win".

The golden ledger records the jax version it was generated under; when
the running version differs, the diff degrades to a warning (jaxpr
lowering legitimately drifts across jax releases) while the hard
failures and the GL010 budget still apply.  Regenerate with
``python -m tla_raft_tpu.analysis --write-ledger`` and review the diff.
"""

from __future__ import annotations

import json
import os

LEDGER_PATH = os.path.join(os.path.dirname(__file__), "golden_ledger.json")

FORBIDDEN_PRIMITIVES = {
    "pure_callback", "io_callback", "debug_callback", "callback",
    "infeed", "outfeed",
}
COLLECTIVE_PRIMITIVES = {
    "psum", "pmin", "pmax", "ppermute", "pbroadcast", "all_gather",
    "all_to_all", "reduce_scatter", "pgather", "axis_index",
}

_NARROW_KEY = "convert_element_type[narrow64]"

# GL010: the kernels under the data-indexed gather/scatter budget —
# the per-level expand hot path (both MXU and legacy A/B variants),
# plus the fused whole-level program (engine/megakernel.py): its MXU
# expand/materialize stages contribute ZERO data-indexed gathers; the
# ledgered budget pins the residue (hashstore probe rounds + the
# materialize parent-row gathers) so fusion can never smuggle the
# gather storm back in
GL010_KERNELS = (
    "successor.expand_guards",
    "successor.materialize",
    "successor.expand_guards_legacy",
    "successor.materialize_legacy",
    "dense.expand",
    "engine.megakernel_level",
    "engine.superstep",
    "store.tiered_compact",
    "ops.sieve_probe",
)


def gather_scatter_count(prims: dict) -> int:
    """Data-indexed gather + scatter-class primitive count of a ledger
    histogram (the GL010 budget metric)."""
    return prims.get("gather", 0) + sum(
        v for k, v in prims.items() if k.startswith("scatter")
    )


def _tiny_cfg():
    from ..config import RaftConfig

    # the smallest config with a non-trivial reachable space (50 states,
    # depth 12 — the CLI smoke config): big enough that every kernel
    # branch lowers, small enough that tracing is milliseconds
    return RaftConfig(
        n_servers=2, n_vals=1, max_election=1, max_restart=1,
    )


def kernel_registry():
    """name -> zero-arg callable returning a ClosedJaxpr.

    Covers the four hot-kernel families the level loop dispatches:
    successor guards + materialize (ops/successor.py), the dense expand
    block algebra (ops/dense_expand.py), state fingerprints
    (ops/fingerprint.py), and the exchange delta packer
    (parallel/exchange.py)."""
    import jax
    import jax.numpy as jnp

    from ..engine import megakernel as megakernel_mod
    from ..engine import superstep as superstep_mod
    from ..store import tiered as tiered_mod
    from ..models.raft import init_batch
    from ..ops import hashstore
    from ..ops import sieve as sieve_mod
    from ..ops.successor import get_kernel
    from ..parallel.exchange import pack_fp_deltas

    cfg = _tiny_cfg()
    # mxu pinned ON so the audited successor.* entries are the shipped
    # default regardless of the caller's TLA_RAFT_MXU; the legacy A/B
    # kernels are registered from the same kernel's *_legacy bindings
    kern = get_kernel(cfg, mxu=True)
    fpr = kern.fpr
    st = init_batch(cfg, 8)
    msum = fpr.msg_hash(st.msgs)
    slots = jnp.zeros((8,), jnp.int64)
    fps = jnp.zeros((256,), jnp.uint64)
    n = jnp.asarray(0, jnp.int64)
    slab = jnp.zeros((hashstore.MIN_CAP,), jnp.uint64)
    pays = jnp.zeros((256,), jnp.int64)

    return {
        # the MXU-native hot path (ops/mxu_expand.py, the default):
        # guards = coefficient matmul + message terms, materialize =
        # select-matrix products — both at a ZERO gather/scatter budget
        "successor.expand_guards":
            lambda: jax.make_jaxpr(kern.expand_guards)(st),
        "successor.materialize":
            lambda: jax.make_jaxpr(kern.materialize)(st, slots),
        # the legacy per-lane kernels, retained for A/B: their ledger
        # entries pin the OLD gather/scatter budget so the comparison
        # baseline cannot silently drift either
        "successor.expand_guards_legacy":
            lambda: jax.make_jaxpr(kern.expand_guards_legacy)(st),
        "successor.materialize_legacy":
            lambda: jax.make_jaxpr(kern.materialize_legacy)(st, slots),
        "dense.expand":
            lambda: jax.make_jaxpr(kern.expand)(st, msum),
        "fingerprint.state_fingerprints":
            lambda: jax.make_jaxpr(fpr.state_fingerprints)(st),
        "exchange.pack_fp_deltas":
            lambda: jax.make_jaxpr(pack_fp_deltas)(fps, n),
        # the open-addressing visited store (ops/hashstore.py): the
        # probe hot path must stay at its pinned ONE gather per probe
        # round (plus the claim scatter-min / compaction scatters of
        # probe_and_insert) — any drift back toward the searchsorted
        # gather storm or a data-indexed sort fails the ledger diff
        "hashstore.probe":
            lambda: jax.make_jaxpr(hashstore.probe_impl)(slab, fps),
        "hashstore.probe_and_insert":
            lambda: jax.make_jaxpr(hashstore.probe_and_insert_impl)(
                slab, fps, fps, pays
            ),
        # the fused whole-level program (engine/megakernel.py): expand
        # while_loop + probe-and-insert + materialize scan + invariant
        # reduce as ONE jaxpr — registered so the fusion's primitive
        # mix is frozen like every other hot kernel's
        "engine.megakernel_level":
            lambda: megakernel_mod.ledger_trace(cfg),
        # the multi-level superstep driver (engine/superstep.py): the
        # while_loop wraps the megakernel's fused_level_core, so the
        # same gather budget pins its residue — plus the ring spool,
        # which must stay drop-mode scatters (no data-indexed gathers)
        "engine.superstep":
            lambda: superstep_mod.ledger_trace(cfg),
        # the tiered store's one device program (store/tiered.py):
        # compacting generation-revisit rows out of a materialized
        # frontier — the budget pins ONE data-indexed gather per
        # frontier field (the stable-argsort row permutation), so the
        # level-tail correction can never grow a gather storm
        "store.tiered_compact":
            lambda: tiered_mod.ledger_trace(cfg),
        # the device spill-sieve probe (ops/sieve.py): the in-kernel
        # filter over spilled generations — the budget pins ONE
        # data-indexed gather per probe (the blocked-bloom word fetch);
        # everything else is lane-local bit algebra
        "ops.sieve_probe":
            lambda: sieve_mod.ledger_trace(cfg),
    }


def _subjaxprs(params: dict):
    import jax.extend.core as jcore

    for v in params.values():
        if isinstance(v, jcore.ClosedJaxpr):
            yield v.jaxpr
        elif isinstance(v, jcore.Jaxpr):
            yield v
        elif isinstance(v, (tuple, list)):
            for x in v:
                if isinstance(x, jcore.ClosedJaxpr):
                    yield x.jaxpr
                elif isinstance(x, jcore.Jaxpr):
                    yield x


def primitive_ledger(closed) -> dict:
    """Recursive primitive histogram + dtype set of one closed jaxpr."""
    counts: dict[str, int] = {}
    dtypes: set[str] = set()

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            counts[name] = counts.get(name, 0) + 1
            for var in list(eqn.outvars) + list(eqn.invars):
                aval = getattr(var, "aval", None)
                dt = getattr(aval, "dtype", None)
                if dt is not None:
                    dtypes.add(str(dt))
            if name == "convert_element_type":
                new = str(eqn.params.get("new_dtype", ""))
                olds = {
                    str(getattr(getattr(v, "aval", None), "dtype", ""))
                    for v in eqn.invars
                }
                if new in ("int32", "uint32") and (
                    "int64" in olds or "uint64" in olds
                ):
                    counts[_NARROW_KEY] = counts.get(_NARROW_KEY, 0) + 1
            for sub in _subjaxprs(eqn.params):
                walk(sub)

    walk(closed.jaxpr)
    return {
        "primitives": dict(sorted(counts.items())),
        "dtypes": sorted(dtypes),
    }


def build_ledger() -> dict:
    import jax

    ledger = {"_meta": {"jax": jax.__version__, "config": "S2V1E1R1"}}
    for name, trace in kernel_registry().items():
        ledger[name] = primitive_ledger(trace())
    return ledger


def load_golden(path: str = LEDGER_PATH) -> dict | None:
    if not os.path.exists(path):
        return None
    with open(path, encoding="utf-8") as fh:
        return json.load(fh)


def write_golden(ledger: dict, path: str = LEDGER_PATH):
    with open(path, "w", encoding="utf-8") as fh:
        json.dump(ledger, fh, indent=1, sort_keys=True)
        fh.write("\n")


_DEFAULT_GOLDEN = object()  # sentinel: "load the committed ledger"


def audit(golden=_DEFAULT_GOLDEN) -> tuple[list[str], list[str]]:
    """Run the audit; returns (failures, warnings).

    Hard rules always apply; the ledger diff is a failure when the
    golden was generated under the running jax version, a warning
    otherwise (lowering drifts across releases).  ``golden=None``
    means "the caller's ledger is missing" and is reported as such —
    it does NOT silently fall back to the committed default."""
    import jax

    failures: list[str] = []
    warnings: list[str] = []
    current = build_ledger()
    for name, entry in current.items():
        if name == "_meta":
            continue
        prims = entry["primitives"]
        bad = sorted(set(prims) & FORBIDDEN_PRIMITIVES)
        if bad:
            failures.append(
                f"{name}: host-callback primitive(s) {bad} — a hidden "
                "host round-trip per dispatch"
            )
        coll = sorted(set(prims) & COLLECTIVE_PRIMITIVES)
        if coll:
            failures.append(
                f"{name}: collective primitive(s) {coll} outside any "
                "declared mesh axis — these kernels compose inside "
                "shard_map bodies; a baked-in collective nests axis "
                "semantics and deadlocks the rendezvous"
            )
        if any(d in ("float64", "complex128") for d in entry["dtypes"]):
            failures.append(
                f"{name}: float64 value in the lowered kernel — the "
                "checker is integer algebra end to end; f64 means an "
                "accidental promotion"
            )

    if golden is _DEFAULT_GOLDEN:
        golden = load_golden()
    if golden is None:
        warnings.append(
            "no golden ledger committed — run `python -m "
            "tla_raft_tpu.analysis --write-ledger` and commit "
            "golden_ledger.json"
        )
        return failures, warnings

    # GL010: gather/scatter budget for the hot expand kernels — a HARD
    # failure regardless of jax version (the budget is semantic; see
    # the module docstring).  Budgets come from the committed ledger.
    for name in GL010_KERNELS:
        entry, gold = current.get(name), golden.get(name)
        if entry is None or gold is None:
            continue  # missing-kernel drift is reported below
        cur_gs = gather_scatter_count(entry["primitives"])
        budget = gather_scatter_count(gold["primitives"])
        if cur_gs > budget:
            failures.append(
                f"[GL010] {name}: data-indexed gather/scatter count "
                f"{cur_gs} exceeds the ledgered budget {budget} — the "
                "expand hot path regressed onto the launch-cost cliff "
                "(docs/PERF.md); keep the kernel on the MXU-factored "
                "formulation or justify a new budget with --write-ledger"
            )

    same_version = golden.get("_meta", {}).get("jax") == jax.__version__
    sink = failures if same_version else warnings
    for name, entry in current.items():
        if name == "_meta":
            continue
        gold = golden.get(name)
        if gold is None:
            sink.append(f"{name}: kernel missing from the golden ledger")
            continue
        drift = _diff_counts(gold["primitives"], entry["primitives"])
        if drift:
            sink.append(
                f"{name}: primitive ledger drift vs golden "
                f"({'; '.join(drift)}) — if intended, regenerate with "
                "--write-ledger and justify in the PR"
            )
        if sorted(gold.get("dtypes", [])) != entry["dtypes"]:
            sink.append(
                f"{name}: dtype set drift vs golden "
                f"(golden {gold.get('dtypes')}, now {entry['dtypes']})"
            )
    for name in golden:
        if name != "_meta" and name not in current:
            sink.append(
                f"{name}: in the golden ledger but no longer registered"
            )
    if not same_version:
        warnings.append(
            f"golden ledger was generated under jax "
            f"{golden.get('_meta', {}).get('jax')}, running "
            f"{jax.__version__} — ledger diff demoted to warnings"
        )
    return failures, warnings


def _diff_counts(gold: dict, cur: dict) -> list[str]:
    out = []
    for k in sorted(set(gold) | set(cur)):
        g, c = gold.get(k, 0), cur.get(k, 0)
        if g != c:
            out.append(f"{k}: {g} -> {c}")
    return out
