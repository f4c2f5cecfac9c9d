"""The checker's profiler spans (obs/telemetry.py ``SPANS``).

One tiny superstep run under ``jax.profiler`` with the benchmark's
capture options: the program's ``raft.*`` spans land on the calling
thread's line of the host plane, nest as the catalogue says, and count
one ``raft.check`` per ``run_check`` and one ``raft.fetch`` per
ledgered fetch.  Without a hub a span is the shared no-op.
"""

import glob
import os
import re

import pytest

from tla_raft_tpu.config import RaftConfig
from tla_raft_tpu.obs import telemetry as tel

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
S2 = RaftConfig(n_servers=2, n_vals=1, max_election=1, max_restart=1)
CHECKS = 2

# the innermost raft.* span each span may sit in (None: outermost)
PARENTS = {
    "raft.check": {None},
    "raft.check.construct": {"raft.check"},
    "raft.check.init": {"raft.check"},
    "raft.check.levels": {"raft.check"},
    "raft.check.finish": {"raft.check"},
    "raft.superstep.prepare": {"raft.check.levels"},
    "raft.superstep.dispatch": {"raft.check.levels"},
    "raft.superstep.unpack": {"raft.check.levels"},
    "raft.level.commit": {"raft.check.levels"},
    "raft.level.single": {"raft.check.levels"},
    "raft.fetch": {"raft.check.levels", "raft.level.single"},
}

# what the superstep path reaches on every check of S2
REACHED = set(PARENTS) - {"raft.level.single"}


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """(raft.* spans on the calling thread, hub, summaries)."""
    import jax
    from jax.profiler import ProfileData

    from tla_raft_tpu.check import run_check

    d = str(tmp_path_factory.mktemp("spans"))
    hub = tel.TelemetryHub(run_dir=None)
    tel.install(hub)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    try:
        jax.profiler.start_trace(d, profiler_options=opts)
        try:
            summaries = [run_check(S2, chunk=64) for _ in range(CHECKS)]
        finally:
            jax.profiler.stop_trace()
    finally:
        tel.install(None)
    (path,) = glob.glob(f"{d}/**/*.xplane.pb", recursive=True)
    lines = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                evs = [(e.name, int(e.start_ns),
                        int(e.start_ns + e.duration_ns))
                       for e in line.events if e.name.startswith("raft.")]
                if evs:
                    lines.append(evs)
    assert len(lines) == 1, "raft.* spans on more than one thread"
    return lines[0], hub, summaries


def _parent(spans, sp):
    """The innermost other span enclosing ``sp``."""
    _name, s, e = sp
    best = None
    for other in spans:
        _o_name, o_s, o_e = other
        if other is sp or not (o_s <= s and e <= o_e):
            continue
        if best is None or o_e - o_s < best[2] - best[1]:
            best = other
    return None if best is None else best[0]


def test_spans_nest_as_catalogued(traced):
    spans, _hub, _summaries = traced
    names = {n for n, _s, _e in spans}
    assert REACHED <= names, REACHED - names
    for sp in spans:
        assert _parent(spans, sp) in PARENTS[sp[0]], sp


def test_one_check_span_per_run_check(traced):
    spans, _hub, summaries = traced
    checks = [sp for sp in spans if sp[0] == "raft.check"]
    assert len(checks) == len(summaries) == CHECKS
    for phase in ("raft.check.construct", "raft.check.init",
                  "raft.check.levels"):
        # each check runs each phase, in order, once
        assert sum(sp[0] == phase for sp in spans) == CHECKS, phase
    # finish has two sites: the engine's tail, then check.py's summary
    assert sum(sp[0] == "raft.check.finish" for sp in spans) == 2 * CHECKS
    for _c, c_s, c_e in checks:
        inside = [(s, n) for n, s, e in spans
                  if c_s <= s and e <= c_e and n.startswith("raft.check.")]
        order = [n for _s, n in sorted(inside)]
        assert order[:3] == ["raft.check.construct", "raft.check.init",
                             "raft.check.levels"]
        assert order[-1] == "raft.check.finish"


def test_one_fetch_span_per_ledgered_fetch(traced):
    spans, hub, summaries = traced
    assert hub.fetches > 0
    assert sum(sp[0] == "raft.fetch" for sp in spans) == hub.fetches
    # the superstep path ran: one dispatch span per superstep
    assert (sum(sp[0] == "raft.superstep.dispatch" for sp in spans)
            == hub.supersteps > 0)
    assert all(s["ok"] and s["distinct"] == 50 for s in summaries)


def test_span_without_hub_never_calls_the_annotator(monkeypatch):
    calls = []
    monkeypatch.setattr(tel, "_ANNOTATOR", calls.append)
    assert tel.current() is None
    with tel.span("raft.check"):
        pass
    stack = tel.SpanStack()
    stack.push("raft.check.init")
    stack.pop_to(0)
    assert calls == []


def test_span_stack_closes_innermost_first(monkeypatch):
    log = []

    class Rec:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            log.append(("enter", self.name))

        def __exit__(self, *exc):
            log.append(("exit", self.name))

    monkeypatch.setattr(tel, "_ANNOTATOR", Rec)
    with tel.TelemetryHub(run_dir=None):
        stack = tel.SpanStack()
        stack.push("raft.check.levels")
        stack.push("raft.level.single")
        stack.pop_to(1)
        with tel.span("raft.level.commit"):
            pass
        stack.pop_to(0)
    assert log == [
        ("enter", "raft.check.levels"), ("enter", "raft.level.single"),
        ("exit", "raft.level.single"),
        ("enter", "raft.level.commit"), ("exit", "raft.level.commit"),
        ("exit", "raft.check.levels"),
    ]


def test_every_span_site_is_catalogued():
    """Span names in the package are ``raft.*`` names from SPANS."""
    rx = re.compile(r"""\b(?:span|push)\(\s*["']([^"']+)["']""")
    used = set()
    for path in glob.glob(os.path.join(REPO, "tla_raft_tpu", "**", "*.py"),
                          recursive=True):
        with open(path) as f:
            used |= set(rx.findall(f.read()))
    assert used, "no span sites found"
    assert all(n.startswith("raft.") for n in tel.SPANS)
    assert used <= set(tel.SPANS), used - set(tel.SPANS)


def test_in_memory_hub_keeps_no_stream():
    """A hub with no path aggregates, and encodes and buffers nothing."""
    mem = tel.TelemetryHub(run_dir=None)
    for i in range(3 * tel.FLUSH_EVERY):
        mem.emit("fetch", s=0.5, b=8)
        mem.emit("level_commit", level=i + 1, n_new=2, distinct=3,
                 generated=4, slab_cap=0)
    assert mem._buf == []
    assert mem.n_events == 6 * tel.FLUSH_EVERY
    assert mem.levels == 3 * tel.FLUSH_EVERY
    assert mem.fetches == 3 * tel.FLUSH_EVERY
    assert mem.fetch_wait_s == pytest.approx(1.5 * tel.FLUSH_EVERY)
    assert mem.snapshot()["level_new"] == [2] * (3 * tel.FLUSH_EVERY)
