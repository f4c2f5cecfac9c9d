"""The env readers behind the engine's tunable knobs.

Each knob's value comes from its environment variable, else a constant.
These cases pin the constants the chip runs with when nothing is set,
that a set variable is read as given, and each reader's clamp or parse
at its edge.
"""

import pytest

from tla_raft_tpu.engine import forecast, pipeline, superstep
from tla_raft_tpu.ops import sieve
from tla_raft_tpu.store import tiered

MIB = 1 << 20

READERS = {
    "span": (superstep.span_from_env, "TLA_RAFT_SUPERSTEP"),
    "window": (pipeline.window_from_env, "TLA_RAFT_PIPELINE_WINDOW"),
    "cap_margin": (forecast.cap_margin, "TLA_RAFT_CAP_MARGIN"),
    "fanout": (tiered.compact_fanout_from_env, "TLA_RAFT_COMPACT_FANOUT"),
    "fseg_bytes": (tiered.fseg_bytes_from_env, "TLA_RAFT_FSEG_BYTES"),
    "warm_bytes": (tiered.warm_bytes_from_env, "TLA_RAFT_WARM_BYTES"),
    # words of a 1 MiB hot tier's sieve: 1/8 of the budget, as u64
    # words rounded down to a power of two
    "sieve_words": (lambda: sieve.sieve_words_for(MIB),
                    "TLA_RAFT_SIEVE_BYTES"),
    # a 4 KiB hot tier's sieve sits on the 8 KiB floor
    "sieve_floor": (lambda: sieve.sieve_words_for(4096),
                    "TLA_RAFT_SIEVE_BYTES"),
}

CASES = [
    # (reader, env value or None for unset, expected)
    ("span", None, 4),
    ("span", "8", 8),
    ("span", "0", 1),
    ("window", None, 2),
    ("window", "3", 3),
    ("window", "", 2),
    ("cap_margin", None, 1.25),
    ("cap_margin", "1.5", 1.5),
    ("cap_margin", "0.5", 1.0),
    ("fanout", None, 8),
    ("fanout", "2", 2),
    ("fanout", "0", 1),
    ("fseg_bytes", None, 0),
    ("fseg_bytes", "32768", 32768),
    ("fseg_bytes", "1e6", 1_000_000),
    ("warm_bytes", None, 1 << 30),
    ("warm_bytes", "64", 64),
    ("warm_bytes", "1e6", 1_000_000),
    ("sieve_words", None, (MIB >> 3) // 8),
    ("sieve_words", "1e6", 1 << 16),
    ("sieve_floor", None, 1024),
]


@pytest.mark.parametrize(
    "name,value,want", CASES,
    ids=[f"{n}-{'unset' if v is None else v or 'empty'}"
         for n, v, _ in CASES],
)
def test_env_reader(name, value, want, monkeypatch):
    read, var = READERS[name]
    if value is None:
        monkeypatch.delenv(var, raising=False)
    else:
        monkeypatch.setenv(var, value)
    got = read()
    assert got == want and type(got) is type(want), (got, want)
