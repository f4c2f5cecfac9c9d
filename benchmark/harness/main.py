"""One benchmark run of one cell: set-up, a timed window, the verdict.

    python benchmark/run.py --workload ref-deep --seed 7 --seconds 40 --trace 0

Set-up starts the process, initializes JAX on the TPU, loads the
persistent compile cache and runs the cell's warm-up (one block of its
traffic), so nothing compiles inside the window.  The window runs whole
checks back to back through ``tla_raft_tpu.check.run_check``, the
in-process core of the CLI, each on a new checker with its own visited
store, and closes when the check in flight at ``--seconds`` returns.
Every check, the warm-up's included, is compared with the reference's
answer.  The last line of standard output is the result as JSON; with
``--trace 1`` the window runs under the profiler and the line carries
the per-layer metrics instead of the end-to-end ones.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
import traceback

from . import compare, device, spec, trace
from . import traffic as traffic_gen


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def raft_config(config: dict):
    """The configuration as the checker's own cfg parser reads it."""
    from tla_raft_tpu.cfgparse import parse_cfg, to_raft_config

    c = config["constants"]
    text = "\n".join([
        "CONSTANTS",
        f"    MaxTerm = {c['MaxTerm']}",
        f"    MaxRestart = {c['MaxRestart']}",
        f"    MaxElection = {c['MaxElection']}",
        "    Servers = {" + ", ".join(c["Servers"]) + "}",
        "    Vals = {" + ", ".join(c["Vals"]) + "}",
        f"SYMMETRY {config['symmetry']}",
        f"VIEW {config['view']}",
        "INIT Init",
        "NEXT Next",
        "INVARIANT",
        *config["invariants"],
    ])
    return to_raft_config(parse_cfg(text))


def keep_every_compile_cached() -> None:
    """Cache every compile, not only those over 1 s.

    ``platform.setup_jax`` sets the persistent cache's threshold to 1 s
    on each ``run_check``, so the many short compiles would run again in
    every process; the threshold goes back to 0 after it."""
    from tla_raft_tpu import platform

    if getattr(platform.setup_jax, "_bench_wrapped", False):
        return
    setup = platform.setup_jax

    def setup_jax():
        jax = setup()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        return jax

    setup_jax._bench_wrapped = True
    platform.setup_jax = setup_jax


@dataclasses.dataclass(frozen=True)
class Counters:
    """The telemetry hub's running totals at one instant."""

    compiles: int
    cache_hits: int
    compile_s: float
    levels: int
    level_s: float
    fetch_wait_s: float

    @classmethod
    def read(cls, hub) -> "Counters":
        return cls(
            compiles=hub.compiles, cache_hits=hub.cache_hits,
            compile_s=hub.compile_s, levels=hub.levels,
            level_s=sum(hub.level_seconds), fetch_wait_s=hub.fetch_wait_s,
        )

    def minus(self, other: "Counters") -> "Counters":
        return Counters(*(a - b for a, b in zip(
            dataclasses.astuple(self), dataclasses.astuple(other))))


@dataclasses.dataclass
class Context:
    """What a metric's reader may read (``benchmark/metrics/<name>.py``)."""

    window: list  # CheckResult of each check in the window
    window_s: float
    setup_s: float
    setup_compile_s: float
    in_window: Counters  # hub totals accrued inside the window
    memory_peak_bytes: int | None
    trace: trace.Trace | None
    level_programs: list  # module-name patterns of the level programs
    peak: dict  # the device's row of peaks.json


def run_check_once(cfg, depth: int) -> compare.CheckResult:
    """One whole check; a check that raises is recorded, not fatal."""
    import jax

    from tla_raft_tpu import check

    with jax.profiler.TraceAnnotation(f"bench.check depth={depth}"):
        try:
            summary = check.run_check(cfg, max_depth=depth)
        except Exception as e:  # judged as a failed check, and shown
            traceback.print_exc()
            return compare.CheckResult(max_depth=depth, error=repr(e))
    return compare.CheckResult.from_summary(depth, summary)


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def measure(cell: spec.Cell, devs, seed: int, seconds: float,
            traced: bool) -> dict:
    """Set-up, window and verdict of one run: the result line's dict."""
    import jax

    from tla_raft_tpu.obs import telemetry

    keep_every_compile_cached()
    kind = devs[0].device_kind
    # the table of peaks, and the level programs by XLA module name
    peaks = _load(os.path.join(cell.bench_dir, "peaks.json"))["devices"]
    if kind not in peaks:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    level_programs = _load(
        os.path.join(cell.bench_dir, "programs.json")
    )["level_programs"]
    cfg = raft_config(cell.config)
    hub = telemetry.TelemetryHub(run_dir=None)
    telemetry.install(hub)
    try:
        warm = [run_check_once(cfg, d)
                for d in traffic_gen.warmup(cell.traffic)]
        setup_compile_s = hub.compile_s
        depths = traffic_gen.checks(cell.traffic, seed)
        capture = trace.Capture() if traced else None
        if capture is not None:
            capture.start()
        window: list[compare.CheckResult] = []
        before = Counters.read(hub)
        setup_s = device.seconds_since_process_start()
        t0 = time.monotonic()
        with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
            while True:
                r = run_check_once(cfg, next(depths))
                window.append(r)
                if r.error or time.monotonic() - t0 >= seconds:
                    break
        window_s = time.monotonic() - t0
        in_window = Counters.read(hub).minus(before)
        tr = capture.stop() if capture is not None else None
    finally:
        telemetry.install(None)
    peak_bytes = device.memory_peak_bytes(devs)

    ctx = Context(
        window=window, window_s=window_s, setup_s=setup_s,
        setup_compile_s=setup_compile_s, in_window=in_window,
        memory_peak_bytes=peak_bytes, trace=tr,
        level_programs=level_programs, peak=peaks[kind],
    )
    metrics = {}
    for m in (cell.per_layer if traced else cell.end_to_end):
        value = cell.reader(m)(ctx)
        if value is not None:
            metrics[m.name] = {"value": value, "unit": m.unit}

    print(
        f"window: {len(window)} checks (depths "
        f"{[r.max_depth for r in window]}) in {window_s:.3f} s; "
        f"compiles in the window: {in_window.compiles} "
        f"({in_window.cache_hits} served by the persistent cache, "
        f"{in_window.compiles - in_window.cache_hits} compiled, "
        f"{in_window.compile_s:.3f} s); "
        f"set-up {setup_s:.3f} s with {setup_compile_s:.3f} s of compiles",
        flush=True,
    )
    compared = compare.compare(warm + window, cell.answers)
    dev = device.describe(devs)
    dev["memory_peak_bytes"] = peak_bytes
    out = {
        "correct": compare.holds(compared),
        "attempted": len(window),
        "failed": compare.failed_checks(window, cell.answers),
        "metrics": metrics,
        "device": dev,
    }
    if tr is not None:
        dev["busy_s"] = trace.busy_s(tr)
        dev["window_s"] = trace.window_s(tr)
        out["breakdown"] = {
            "device_ops": trace.top_ops(tr),
            "idle_gaps": trace.idle_gaps(tr),
        }
    out["compared"] = compared
    return out


def main(argv=None, root: str = spec.ROOT,
         bench_dir: str = spec.BENCH_DIR) -> int:
    args = parse_args(argv)
    cell = spec.load_cell(args.workload, root=root, bench_dir=bench_dir)
    # JAX's persistent cache lives at a fixed path inside the checkout:
    # the path is part of the cache key, and nothing is shared with
    # another checkout
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(root, ".jax_cache")
    if root not in sys.path:
        sys.path.insert(0, root)
    try:
        devs = device.require(cell.chips)
    except device.NoAccelerator as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    out = measure(cell, devs, args.seed, args.seconds, bool(args.trace))
    for name, c in out["compared"].items():
        print(f"compared {name} {c['value']} limit {c['limit']}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0
