"""The versioned plan cache: knob registry, regime keys, resolution.

A **plan** is a dict of performance-knob values tuned for one
*(backend, spec, shape-regime)*.  Plans live in ``plans.json``, written
through ``resilience.commit_json`` (atomic replace + digest + manifest
entry) and read through ``load_json_verified`` — a torn, corrupt or
schema-stale plan file is treated exactly like an absent one
(quarantined-and-ignored): every consumer falls back to the hand-set
defaults and the run proceeds; a bad plan must never crash a resume.

The **regime key** is one more dimension of the existing shape_plan
ladder: the forecast layer keys program shapes on capacity rungs, the
service buckets jobs on ``bucket_key`` shape identity — the tuner keys
its winners on ``backend|spec|S<n>V<n>|b<log2 budget-class>``.  Lookup
degrades gracefully: exact regime first, then the same backend+spec at
the nearest smaller budget class (a plan tuned on a smaller member of
the family transfers — the knobs scale with shape, and the parity gate
makes a transferred plan safe by construction), then defaults.

Precedence (highest wins) at every knob site:

1. explicit CLI flag / ``run_check`` argument,
2. explicit ``TLA_RAFT_*`` environment variable,
3. the installed plan (this module, via :mod:`.active`),
4. the hand-set default.

``TLA_RAFT_PLAN`` controls resolution: ``0`` disables plans entirely
(the pre-tuner repo, bit-for-bit), unset/``1`` reads the committed
default cache next to this module, any other value is a path to a
plan file to read instead.
"""

from __future__ import annotations

import os

from .. import resilience

SCHEMA = "tla-raft-plan/1"
PLAN_NAME = "plans.json"
PLAN_KIND = "tune_plan"

# the committed default cache (shipped with the package, tuned on the
# reference box; docs/PERF.md "Autotuned plans" records the A/B)
DEFAULT_PLAN_DIR = os.path.dirname(os.path.abspath(__file__))

# -- knob registry --------------------------------------------------------
# name -> (hand-set default, lo, hi, integer?).  Bounds clamp plan
# values at application time: a hand-edited (or detuned-on-purpose)
# plan can make a run SLOW but never hand a kernel a nonsense shape.
# Every knob changes shapes or schedules only — never semantics.
KNOBS: dict = {
    # expand chunk rows per device dispatch (run_check chunk=)
    "chunk": (1024, 128, 1 << 16, True),
    # resident superstep span, levels per dispatch (engine/superstep)
    "superstep_span": (4, 1, 16, True),
    # async in-flight window, groups (engine/pipeline)
    "pipeline_window": (2, 1, 16, True),
    # slots gathered per hashstore probe round (ops/hashstore)
    "probe_window": (8, 2, 64, True),
    # forecast/presize capacity inflation (engine/forecast shape_plan,
    # the superstep ring, the bfs presize floors)
    "cap_margin": (1.25, 1.05, 2.0, False),
    # scheduler batched-bucket minimum (service/daemon)
    "min_bucket": (2, 2, 64, True),
    # spill-sieve spend as a right-shift of the hot budget
    # (ops/sieve.sieve_words_for: bytes = dev_bytes >> sieve_shift)
    "sieve_shift": (3, 1, 8, True),
    # cold-run LSM compaction fanout (store/tiered)
    "compact_fanout": (8, 2, 64, True),
    # host-RAM frontier budget before warm-tier spill (store/tiered;
    # 0 keeps the hand-set off default)
    "fseg_bytes": (0, 0, 1 << 40, True),
    # host-warm generation budget (store/tiered; dev/warm split)
    "warm_bytes": (1 << 30, 1 << 20, 1 << 42, True),
}


def defaults() -> dict:
    """The hand-set defaults as a knob dict (the search's seed)."""
    return {k: v[0] for k, v in KNOBS.items()}


def clamp(knobs: dict) -> dict:
    """Registry-known knobs only, bounds-clamped and typed."""
    out = {}
    for k, v in (knobs or {}).items():
        spec = KNOBS.get(k)
        if spec is None:
            continue
        _d, lo, hi, is_int = spec
        try:
            v = int(v) if is_int else float(v)
        except (TypeError, ValueError):
            continue
        out[k] = min(hi, max(lo, v))
    return out


# -- regime keys ----------------------------------------------------------

def budget_class(cfg) -> int:
    """log2 size class of the config's action-budget product.

    ``(max_election+1)*(max_restart+1)`` tracks reachable-state volume
    across the Raft family far better than either bound alone (the
    golden ledger's fixpoints grow ~monotonically in it), and a log2
    class keeps neighbouring budgets in one regime so the cache stays
    small."""
    prod = (int(cfg.max_election) + 1) * (int(cfg.max_restart) + 1)
    return max(0, prod.bit_length() - 1)


def regime_key(cfg, backend: str, spec: str = "raft") -> str:
    """``platform|spec|S<n>V<n>|b<class>``.  For the engine backend
    (``"jax"``) the first field is the device platform JAX runs on, so
    a plan tuned on the CPU never steers a chip run (and vice versa)."""
    if backend == "jax":
        import jax

        backend = jax.default_backend()
    return (
        f"{backend}|{spec}|S{int(cfg.n_servers)}V{int(cfg.n_vals)}"
        f"|b{budget_class(cfg)}"
    )


def _fallback_keys(key: str) -> list:
    """Exact key, then same backend|spec|shape at smaller budget
    classes (nearest first) — a plan tuned on a smaller family member
    transfers; bigger-budget plans do NOT flow down (their capacity
    knobs were sized for more states than this run will see)."""
    head, _, b = key.rpartition("|b")
    try:
        cls = int(b)
    except ValueError:
        return [key]
    return [key] + [f"{head}|b{c}" for c in range(cls - 1, -1, -1)]


# -- cache I/O ------------------------------------------------------------

def plan_path() -> str | None:
    """The active plan file path per ``TLA_RAFT_PLAN`` (None = off)."""
    env = os.environ.get("TLA_RAFT_PLAN", "1")
    if env == "0":
        return None
    if env == "1" or env == "":
        return os.path.join(DEFAULT_PLAN_DIR, PLAN_NAME)
    return env


def load_cache(path: str | None = None) -> dict | None:
    """The plan-cache document, or None (missing/corrupt/stale ==
    quarantined-and-ignored; never raises)."""
    if path is None:
        path = plan_path()
        if path is None:
            return None
    ckdir, name = os.path.split(path)
    doc = resilience.load_json_verified(ckdir or ".", name)
    if not isinstance(doc, dict) or doc.get("schema") != SCHEMA:
        return None
    if not isinstance(doc.get("plans"), dict):
        return None
    return doc


def resolve(cfg, backend: str, *, spec: str = "raft",
            path: str | None = None) -> dict:
    """The clamped knob dict for this run's regime ({} = no plan).

    Degrades along :func:`_fallback_keys`; a resolved entry's knobs are
    bounds-clamped so even a hand-mangled cache cannot produce an
    out-of-range shape."""
    if path is None and os.environ.get("TLA_RAFT_PLAN", "1") == "0":
        return {}
    doc = load_cache(path)
    if doc is None:
        return {}
    plans = doc["plans"]
    for key in _fallback_keys(regime_key(cfg, backend, spec)):
        ent = plans.get(key)
        if isinstance(ent, dict) and isinstance(ent.get("knobs"), dict):
            return clamp(ent["knobs"])
    return {}


def commit(path: str, key: str, knobs: dict, *, probe: dict | None = None,
           source: str = "tuned") -> dict:
    """Fold one regime's winner into the cache at ``path`` atomically.

    Read-modify-write through the manifest layer: the existing cache
    (if readable) keeps its other regimes, the version bumps, and the
    whole document commits via ``resilience.commit_json`` — a crash
    mid-commit leaves the old cache intact."""
    doc = load_cache(path)
    if doc is None:
        doc = {"schema": SCHEMA, "version": 0, "plans": {}}
    doc["version"] = int(doc.get("version", 0)) + 1
    doc["plans"][key] = {
        "knobs": clamp(knobs),
        "source": source,
        **({"probe": probe} if probe else {}),
    }
    ckdir, name = os.path.split(path)
    resilience.commit_json(ckdir or ".", name, doc, kind=PLAN_KIND)
    return doc


# -- application ----------------------------------------------------------

def apply(cfg, backend: str, *, spec: str = "raft",
          path: str | None = None) -> dict:
    """Resolve this run's plan and publish it process-wide.

    Returns the installed knob dict ({} when plans are off or no regime
    matches — :mod:`.active` is then cleared so a previous run's plan
    cannot leak into this one).  Emits one ``plan_applied`` telemetry
    event when a plan lands, so the flight recorder pins exactly which
    knobs this run tuned."""
    from ..obs import telemetry as _obs
    from . import active

    knobs = resolve(cfg, backend, spec=spec, path=path)
    active.install(knobs or None)
    if knobs:
        _obs.emit("plan_applied", regime=regime_key(cfg, backend, spec),
                  knobs=dict(knobs))
    return knobs
