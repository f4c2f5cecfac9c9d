"""Capture the window's profiler trace and reduce it to numbers.

Capture writes an ``.xplane.pb`` under a temporary directory (removed
after reading).  :func:`extract` turns it into a small neutral form, a
:class:`Trace` of (name, start_ns, end_ns) events on one clock:

* ``ops[d]``: the XLA operations that ran on device ``d``;
* ``modules[d]``: the whole programs (XLA modules) that ran on it;
* ``host``: the host's annotated spans (the benchmark's own
  ``bench.window`` and ``bench.check`` spans, JAX's dispatch and fetch
  spans, the checker's own).

The reductions work on that form alone, so a recorded trace checks them
(``benchmark/tests/test_trace.py``).  Busy time is the union of the
operation intervals inside the window; an idle gap is a stretch of the
window with no operation on the device, named by the innermost host span
that covers its middle.
"""

from __future__ import annotations

import collections
import dataclasses
import glob
import json
import re
import shutil
import tempfile

WINDOW_SPAN = "bench.window"

Event = tuple  # (name, start_ns, end_ns)


@dataclasses.dataclass
class Trace:
    ops: dict[int, list[Event]]
    modules: dict[int, list[Event]]
    host: list[Event]

    def to_json(self) -> dict:
        return dict(
            ops={str(k): v for k, v in self.ops.items()},
            modules={str(k): v for k, v in self.modules.items()},
            host=self.host,
        )

    @classmethod
    def from_json(cls, doc: dict) -> "Trace":
        def evs(xs):
            return [(str(n), int(s), int(e)) for n, s, e in xs]

        return cls(
            ops={int(k): evs(v) for k, v in doc["ops"].items()},
            modules={int(k): evs(v) for k, v in doc["modules"].items()},
            host=evs(doc["host"]),
        )


class Capture:
    """``jax.profiler`` over the window: host spans at level 1, no
    Python tracer (it records every call and slows the host)."""

    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")

    def start(self) -> None:
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.dir, profiler_options=opts)

    def stop(self) -> "Trace":
        import jax

        jax.profiler.stop_trace()
        try:
            paths = glob.glob(f"{self.dir}/**/*.xplane.pb", recursive=True)
            if len(paths) != 1:
                raise RuntimeError(f"expected one trace file, got {paths}")
            return extract(paths[0])
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")


def extract(path: str) -> Trace:
    """The neutral form of one ``.xplane.pb`` file."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops: dict[int, list] = {}
    modules: dict[int, list] = {}
    host: list = []
    for plane in pd.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(1))
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops[dev] = _events(line)
                elif line.name == "XLA Modules":
                    modules[dev] = _events(line)
        elif plane.name.startswith("/host:CPU"):
            # the thread that ran the window: its spans say what the
            # host was doing; other threads' spans would only blur that
            for line in plane.lines:
                evs = _events(line)
                if any(n == WINDOW_SPAN for n, _s, _e in evs):
                    host = evs
    return Trace(ops=ops, modules=modules, host=host)


def _events(line) -> list:
    # a TPU op's event name is its whole HLO text; its instruction name
    # (before " = ") is enough to tell ops apart
    return [
        (e.name.split(" = ", 1)[0], int(e.start_ns),
         int(e.start_ns + e.duration_ns))
        for e in line.events
    ]


def save(trace: Trace, path: str) -> None:
    with open(path, "w") as f:
        json.dump(trace.to_json(), f)


def load(path: str) -> Trace:
    with open(path) as f:
        return Trace.from_json(json.load(f))


# -- reductions --------------------------------------------------------------

def window(trace: Trace) -> tuple[int, int]:
    """The benchmark's ``bench.window`` span: (start_ns, end_ns)."""
    spans = [(s, e) for n, s, e in trace.host if n == WINDOW_SPAN]
    if len(spans) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN} span, found "
                         f"{len(spans)}")
    return spans[0]


def _union(events, lo: int, hi: int) -> list[tuple[int, int]]:
    """Merged intervals of ``events`` clipped to [lo, hi)."""
    iv = sorted((max(s, lo), min(e, hi)) for _n, s, e in events
                if e > lo and s < hi)
    out: list[list[int]] = []
    for s, e in iv:
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_s(trace: Trace) -> float:
    """Seconds in which an operation ran, averaged over the devices."""
    lo, hi = window(trace)
    if not trace.ops:
        return 0.0
    per_dev = [
        sum(e - s for s, e in _union(evs, lo, hi)) / 1e9
        for evs in trace.ops.values()
    ]
    return sum(per_dev) / len(per_dev)


def window_s(trace: Trace) -> float:
    lo, hi = window(trace)
    return (hi - lo) / 1e9


def idle_gaps(trace: Trace, top: int = 10) -> list[list]:
    """The longest stretches with no operation on device 0, each named
    by the innermost host span covering its middle."""
    lo, hi = window(trace)
    busy = _union(trace.ops.get(min(trace.ops), []) if trace.ops else [],
                  lo, hi)
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if t < hi:
        gaps.append((t, hi))
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for s, e in gaps[:top]:
        out.append([_host_at(trace, (s + e) // 2), (e - s) / 1e9])
    return out


def _host_at(trace: Trace, t: int) -> str:
    best = None
    for n, s, e in trace.host:
        if s <= t < e and n != WINDOW_SPAN:
            if best is None or e - s < best[1] - best[0]:
                best = (s, e, n)
    return best[2] if best else "host: no span"


def top_ops(trace: Trace, top: int = 10) -> list[list]:
    """The device operations that took the most time in the window
    (device 0), summed by name."""
    lo, hi = window(trace)
    tot: collections.Counter = collections.Counter()
    for n, s, e in trace.ops.get(min(trace.ops), []) if trace.ops else []:
        if e > lo and s < hi:
            tot[n] += (min(e, hi) - max(s, lo)) / 1e9
    return [[n, v] for n, v in tot.most_common(top)]


def program_s(trace: Trace, patterns: list[str]) -> float:
    """Device seconds of the programs whose module name matches one of
    ``patterns``, inside the window, summed over the devices."""
    lo, hi = window(trace)
    rx = [re.compile(p) for p in patterns]
    total = 0
    for evs in trace.modules.values():
        for n, s, e in evs:
            if e > lo and s < hi and any(r.search(n) for r in rx):
                total += min(e, hi) - max(s, lo)
    return total / 1e9
