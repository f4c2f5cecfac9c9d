"""The program's own spans in a window's trace.

The checker records named host spans (``raft.*``, the catalogue
``tla_raft_tpu/obs/telemetry.SPANS``) as profiler events on the thread
that runs the checks, so they sit in ``Trace.host`` beside the
benchmark's ``bench.*`` spans and JAX's own.  A program without them
(an older checkout) leaves every reduction here empty, and the readers
built on them report nothing.
"""

from __future__ import annotations

import bisect
import collections

from . import trace

PREFIX = "raft."
NO_SPAN = "no raft.* span"

Interval = tuple  # (start_ns, end_ns)


def clipped(tr: trace.Trace, name: str) -> list[Interval]:
    """The spans called ``name`` that overlap the window, clipped to it."""
    lo, hi = trace.window(tr)
    return [(max(s, lo), min(e, hi)) for n, s, e in tr.host
            if n == name and e > lo and s < hi]


def within(inner: list[Interval], outer: list[Interval]) -> list[Interval]:
    """The intervals of ``inner`` that lie inside one of ``outer``."""
    return [(s, e) for s, e in inner
            if any(os_ <= s and e <= oe for os_, oe in outer)]


def total_ns(intervals: list[Interval]) -> int:
    return sum(e - s for s, e in intervals)


def idle_by_span(tr: trace.Trace) -> list[list]:
    """Seconds of the window with no operation on device 0, by the
    innermost ``raft.*`` span over each instant (``NO_SPAN`` outside
    them), longest first."""
    lo, hi = trace.window(tr)
    ops = tr.ops.get(min(tr.ops), []) if tr.ops else []
    idle, t = [], lo
    for s, e in trace._union(ops, lo, hi):
        if s > t:
            idle.append((t, s))
        t = max(t, e)
    if t < hi:
        idle.append((t, hi))
    spans = [(max(s, lo), min(e, hi), n) for n, s, e in tr.host
             if n.startswith(PREFIX) and e > lo and s < hi]
    cuts = sorted({lo, hi, *(x for s, e, _n in spans for x in (s, e))})
    # the innermost span over each stretch between consecutive cuts
    names = []
    for a, b in zip(cuts, cuts[1:]):
        mid = (a + b) // 2
        over = [(e - s, n) for s, e, n in spans if s <= mid < e]
        names.append(min(over)[1] if over else NO_SPAN)
    out: collections.Counter = collections.Counter()
    for gs, ge in idle:
        i = bisect.bisect_right(cuts, gs) - 1
        a = gs
        while a < ge:
            b = min(ge, cuts[i + 1])
            out[names[i]] += b - a
            a, i = b, i + 1
    return [[n, ns / 1e9] for n, ns in out.most_common()]
