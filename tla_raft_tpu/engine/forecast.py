"""Predictive frontier-growth forecasting for capacity pre-sizing.

Growth-triggered capacity changes recompile the full level program —
minutes per shape on a real mesh, hours when they cascade (the round-4
depth-14 virtual-mesh attempt died on reactive cap_x doubling:
docs/MESH_DEEP.json).  This module turns the measured frontier-growth
model from BASELINE.md into a forecast the engines use to size
capacities ONCE for the whole run, so each program shape compiles once.

The model: on BFS level n the new-state count grows by a ratio r_n that
decays roughly linearly with depth (measured on the reference config:
r drops ~0.017-0.03 per level through the 10^8-state range, BASELINE.md
"golden counts").  Extrapolation marches the last observed ratio down by
the observed decay; errors land well inside the pow2 rounding the
capacity layer applies (from 20 observed levels the level-28 forecast is
within 5% of the measured 45.1M).

Reference analog: TLC sizes its fingerprint set and queue up front from
-Xmx heap flags (/root/reference/myrun.sh:3) rather than reallocating
mid-run; here the "heap flag" is derived from the spec's own measured
growth curve instead of hand tuning.
"""

from __future__ import annotations

# measured ratio decay per level on the reference sweep (BASELINE.md);
# used when fewer than 4 level ratios have been observed
DEFAULT_DECAY = 0.017
# forecasts from fewer observed levels than this are noise (early BFS
# ratios on the reference family swing 1.0-3.0)
MIN_LEVELS = 6
# capacity decisions trust the model at most this many levels ahead: a
# short noisy prefix extrapolates to nonsense at long range (a 14-state
# observed prefix once "forecast" a 3x10^10-state level and the presize
# tried to compile a 67M-lane program).  The per-level ratchet re-floors
# with ever-better forecasts as real levels land, so a long run pays a
# handful of planned resizes instead of one giant wrong one.
PRESIZE_HORIZON = 8

# capacity inflation over the raw forecast: the margin every presize
# floor, the prewarm ladder and the superstep ring share.  Hand-set at
# 1.25 (forecast error lands inside pow2 rounding at that inflation);
# TLA_RAFT_CAP_MARGIN overrides — one accessor so the three consumers
# cannot drift on the value.
DEFAULT_CAP_MARGIN = 1.25


def cap_margin() -> float:
    import os

    env = os.environ.get("TLA_RAFT_CAP_MARGIN")
    return max(1.0, float(env) if env else DEFAULT_CAP_MARGIN)


def pow2ceil(n: int) -> int:
    """Smallest power of two >= n (>= 1)."""
    return 1 << max(0, int(n) - 1).bit_length()


def _ratio_model(level_sizes) -> tuple[float, float]:
    """(last growth ratio, per-level ratio decay) from observed levels."""
    f = [int(x) for x in level_sizes if x > 0]
    if len(f) < 2:
        return 3.0, DEFAULT_DECAY  # early fan-out: conservative
    ratios = [f[i] / f[i - 1] for i in range(1, len(f))]
    r = ratios[-1]
    # the decay itself shrinks with depth, so estimate from the LAST
    # three ratio steps only (median: one skewed level can't bend it);
    # measured on the golden record this tracks the forward decay
    # within ~7% over an 8-level horizon
    diffs = [
        ratios[i - 1] - ratios[i]
        for i in range(max(1, len(ratios) - 3), len(ratios))
    ]
    if diffs:
        diffs.sort()
        d = diffs[len(diffs) // 2]
    else:
        d = DEFAULT_DECAY
    # clamp: negative observed decay (noise) would forecast super-
    # exponential growth; huge decay would truncate the run to nothing.
    # Both clamps are conservative for CAPACITY use (they over-predict).
    return r, min(0.08, max(0.005, d))


def forecast_new_states(
    level_sizes,
    target_depth: int | None,
    max_levels: int = 128,
) -> list[int]:
    """Extrapolated per-level new-state counts beyond the observed prefix.

    ``level_sizes``: observed new states for levels 0..L (level 0 is the
    single init state).  Returns forecasts for levels L+1..target_depth;
    with ``target_depth=None`` (fixpoint run) the projection runs until
    the modeled frontier decays below 1 state or ``max_levels`` is hit.
    Empty when the target is already reached or there is no signal yet.
    """
    obs = [int(x) for x in level_sizes]
    depth_now = len(obs) - 1
    if depth_now < 1 or (target_depth is not None and target_depth <= depth_now):
        return []
    r, d = _ratio_model(obs)
    if target_depth is None:
        # open horizon: a noise-floored decay would extrapolate early
        # ratios into astronomically large "fixpoints" (observed: 10^29
        # on a 50-state config).  Force at least the measured reference
        # decay, and below: trust the projection only if it CONVERGES.
        d = max(d, DEFAULT_DECAY)
    out: list[int] = []
    f = float(obs[-1])
    level = depth_now
    while len(out) < max_levels:
        level += 1
        if target_depth is not None and level > target_depth:
            break
        r = max(0.05, r - d)
        f = f * r
        if f < 1.0:
            break
        out.append(int(f) + 1)
    if target_depth is None and len(out) >= max_levels:
        return []  # projection never reached a fixpoint: no usable signal
    return out


def horizon_forecast(level_sizes, distinct: int, target_depth: int | None):
    """The one shared presize signal: (peak_new, final_distinct, budget).

    Horizon-limited (PRESIZE_HORIZON) per-level forecast plus the
    TLA_RAFT_PRESIZE_BYTES budget, parsed in exactly one place so the
    two engines cannot drift on the model (they still apply their own
    engine-specific margins and pow2 quantization to these numbers).
    Returns None when there is no usable signal yet.
    """
    import os

    fut = forecast_new_states(level_sizes, target_depth)[:PRESIZE_HORIZON]
    if not fut:
        return None
    budget = int(float(os.environ.get("TLA_RAFT_PRESIZE_BYTES", "4e9")))
    return max(fut), distinct + sum(fut), budget


def shape_plan(level_sizes, target_depth: int | None,
               margin: float | None = None) -> list[int]:
    """Margin-inflated per-level row forecast — the AOT prewarm's input.

    One entry per forecast level over the horizon: the new-state rows
    that level is expected to need, inflated by the same 1.25 margin
    the presize floors apply.  The engines quantize these through their
    own capacity functions (pow2 / half-step / chunk-multiple) into the
    ladder of program shapes worth compiling ahead of time
    (engine/pipeline.Prewarmer); emitting the raw rows from ONE place
    keeps the prewarmed ladder and the presize floors from drifting.
    Empty when there is no usable signal yet.
    """
    if margin is None:
        margin = cap_margin()
    fut = forecast_new_states(level_sizes, target_depth)[:PRESIZE_HORIZON]
    return [int(f * margin) + 1 for f in fut]


def pow2_ladder(lo: int, hi: int) -> list[int]:
    """Power-of-two capacities strictly above ``lo`` up to ceil(hi).

    The magnitude steps a growing structure will visit on its way from
    the current capacity to a forecast peak — each one a program shape
    the prewarmer can compile before the run needs it."""
    out: list[int] = []
    c = pow2ceil(max(1, lo))
    if c <= lo:
        c <<= 1
    top = pow2ceil(max(1, hi))
    while c <= top:
        out.append(c)
        c <<= 1
    return out


def sieve_bytes(dev_bytes: int) -> int:
    """Device bytes the spill sieve will pin once tiering demotes its
    first generation (ops/sieve.py sieve_words_for: 1/8 of the hot
    budget by default, TLA_RAFT_SIEVE_BYTES overrides) — charged into
    the pre-OOM HBM forecast ahead of the first demotion, because the
    filter is allocated at FULL size the moment spill starts."""
    from ..ops.sieve import sieve_words_for

    return sieve_words_for(int(dev_bytes)) * 8


def forecast_final_distinct(level_sizes, distinct: int,
                            target_depth: int | None) -> int:
    """Forecast total distinct states at the end of the run."""
    return distinct + sum(forecast_new_states(level_sizes, target_depth))


def forecast_peak_new(level_sizes, target_depth: int | None) -> int:
    """Forecast the largest per-level new-state count over the run."""
    fut = forecast_new_states(level_sizes, target_depth)
    return max(fut, default=0)


def per_device_forecast(level_sizes, distinct: int,
                        target_depth: int | None, n_devices: int):
    """Per-device capacity signal for the 1/D-sharded deep-sweep mesh.

    Fingerprint ownership (fp % D) is hash-uniform, so each device's
    share of a forecast level is ~peak/D with multiplicative skew that
    shrinks as levels grow; the 1.35x margin covers the +3-sigma
    binomial skew down to ~100-state shares (below that the absolute
    +32 floor dominates).  Returns None when there is no usable signal,
    else a dict of per-device row forecasts:

      peak_rows:  largest per-level new-state share one device owns
      final_rows: final distinct-state share one device owns — the
                  entry forecast for the per-owner membership
                  structures: the deep sieve cache and the hash-slab
                  visited shards (ops/hashstore.py slab_rows sizes a
                  slab from this at the quantized <=1/2 load factor;
                  8 B/slot => ~16 B per forecast entry against the
                  byte budget)
      budget:     TLA_RAFT_PRESIZE_BYTES, passed through for the same
                  clamping the engines already apply
    """
    sig = horizon_forecast(level_sizes, distinct, target_depth)
    if sig is None:
        return None
    peak_new, final_distinct, budget = sig
    share = peak_new / n_devices
    peak_rows = int(share * 1.35) + 32
    final_rows = int(final_distinct / n_devices * 1.35) + 32
    return dict(peak_rows=peak_rows, final_rows=final_rows, budget=budget)
