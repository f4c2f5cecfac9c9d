"""Build and run the plain reference checker; write a configuration's answers.

The answers of a configuration are computed once and committed, because
exhaustive BFS over a fixed configuration is deterministic: the answer for
depth bound D is the reference's run to D.  Regenerate them after adding
or changing a configuration:

    python benchmark/reference/ref.py answers raft-ref 15

This module imports nothing of the checker under test.
"""

from __future__ import annotations

import hashlib
import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
SRC = os.path.join(HERE, "raftref.cpp")
BUILD_DIR = os.path.join(HERE, "_build")


def build() -> str:
    """Compile ``raftref.cpp`` once per source digest; the binary's path."""
    with open(SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    binary = os.path.join(BUILD_DIR, f"raftref-{digest}")
    if not os.path.exists(binary):
        os.makedirs(BUILD_DIR, exist_ok=True)
        tmp = f"{binary}.tmp{os.getpid()}"
        subprocess.run(
            ["g++", "-O3", "-std=c++17", "-pthread", SRC, "-o", tmp],
            check=True,
        )
        os.replace(tmp, binary)
    return binary


def reference_args(constants: dict) -> list[str]:
    """Servers, Vals, MaxElection, MaxRestart (MaxTerm is vestigial in
    Raft.tla: terms are bounded by MaxElection)."""
    return [
        str(len(constants["Servers"])), str(len(constants["Vals"])),
        str(constants["MaxElection"]), str(constants["MaxRestart"]),
    ]


def run(constants: dict, max_depth: int, *, fp_bits: int = 64,
        salt: int = 0, fold: str = "full", threads: int | None = None
        ) -> dict:
    """One reference check to ``max_depth``: its JSON result line."""
    threads = threads or min(os.cpu_count() or 1, 8)
    cmd = [build(), *reference_args(constants), str(max_depth),
           str(threads), "--fp-bits", str(fp_bits), "--salt", str(salt),
           "--fold", fold]
    out = subprocess.run(cmd, check=True, capture_output=True, text=True)
    return json.loads(out.stdout.strip().splitlines()[-1])


def answers_from_run(res: dict) -> dict:
    """Per depth bound D <= the run's depth: the answer of a run to D."""
    out = {}
    lv = res["level_sizes"]
    gen = res["generated_by_level"]
    last = res["depth"]
    for d in range(1, last + 1):
        out[str(d)] = dict(
            ok=True if d < last else res["ok"], depth=d,
            generated=gen[d - 1], distinct=sum(lv[: d + 1]),
            level_sizes=lv[: d + 1],
        )
    return out


def write_answers(config_name: str, max_depth: int) -> str:
    with open(os.path.join(BENCH_DIR, "configs", config_name + ".json")) as f:
        config = json.load(f)
    res = run(config["constants"], max_depth)
    doc = dict(
        config=config_name, constants=config["constants"],
        computed_by="benchmark/reference/raftref.cpp",
        depths=answers_from_run(res),
    )
    path = os.path.join(HERE, "answers", config_name + ".json")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(doc, f, indent=1)
        f.write("\n")
    return path


if __name__ == "__main__":
    if len(sys.argv) != 4 or sys.argv[1] != "answers":
        sys.exit("usage: ref.py answers <config> <max_depth>")
    print(write_answers(sys.argv[2], int(sys.argv[3])))
