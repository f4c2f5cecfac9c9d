"""The comparison that decides ``correct``.

Every check the run made is held against the reference's answer for its
depth bound (``benchmark/reference/answers/<config>.json``, computed once
by ``benchmark/reference/raftref.cpp``): the verdict, the depth reached,
the generated count and every level's count of new states.  The checker
computes in exact integers, so each number compared has the limit 0.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass
class CheckResult:
    """What one check returned, in the reference's terms."""

    max_depth: int
    ok: bool | None = None
    depth: int | None = None
    generated: int | None = None
    level_sizes: tuple[int, ...] = ()
    error: str | None = None

    @classmethod
    def from_summary(cls, max_depth: int, summary: dict) -> "CheckResult":
        return cls(
            max_depth=max_depth, ok=bool(summary["ok"]),
            depth=int(summary["depth"]),
            generated=int(summary["generated"]),
            level_sizes=tuple(int(x) for x in summary["level_sizes"]),
        )

    @property
    def distinct(self) -> int:
        return sum(self.level_sizes)


def _level_gap(got: tuple[int, ...], want: list[int]) -> int:
    n = max(len(got), len(want))
    got = tuple(got) + (0,) * (n - len(got))
    want = list(want) + [0] * (n - len(want))
    return max((abs(a - b) for a, b in zip(got, want)), default=0)


def compare(results: list[CheckResult], answers: dict) -> dict:
    """Numbers compared, each ``{"value": v, "limit": l}``; all must hold.

    ``checks`` is the count compared (at least 1); every other number is
    a count of wrong checks or the widest gap, with the limit 0.
    """
    by_depth = answers["depths"]
    failed = verdicts = depths = 0
    gen_gap = lvl_gap = 0
    for r in results:
        want = by_depth.get(str(r.max_depth))
        if r.error is not None or want is None:
            failed += 1
            continue
        verdicts += r.ok != want["ok"]
        depths += r.depth != want["depth"]
        gen_gap = max(gen_gap, abs(r.generated - want["generated"]))
        lvl_gap = max(lvl_gap, _level_gap(r.level_sizes,
                                          want["level_sizes"]))
    return {
        "checks": {"value": len(results), "limit": 1},
        "checks_failed": {"value": failed, "limit": 0},
        "verdicts_wrong": {"value": verdicts, "limit": 0},
        "depths_wrong": {"value": depths, "limit": 0},
        "generated_gap": {"value": gen_gap, "limit": 0},
        "level_gap": {"value": lvl_gap, "limit": 0},
    }


def holds(compared: dict) -> bool:
    """``checks`` is a floor; every other number is a ceiling."""
    for name, c in compared.items():
        if name == "checks":
            if c["value"] < c["limit"]:
                return False
        elif c["value"] > c["limit"]:
            return False
    return True


def failed_checks(results: list[CheckResult], answers: dict) -> int:
    """How many checks raised or disagree with the reference anywhere."""
    return sum(
        1 for r in results if not holds(compare([r], answers))
    )
