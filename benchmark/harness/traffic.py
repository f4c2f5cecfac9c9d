"""The one traffic generator: reads a mix's parameters, yields checks.

A mix is a data file under ``benchmark/traffic/``.  Kind
``repeat_checks`` is a closed loop of whole depth-bounded checks run back
to back by one client, as a user or a CI job waits for each verdict:

``depths``
    The depth bounds of one block.  Every block holds each of them once.
``shuffle``
    When true, each block's order is drawn from the seed; otherwise the
    blocks repeat in the listed order and the seed changes nothing.

The warm-up is one whole block in the listed order, so every shape the
window uses is compiled before it opens.
"""

from __future__ import annotations

import random
from typing import Iterator

KINDS = ("repeat_checks",)


def validate(traffic: dict) -> None:
    if traffic.get("kind") not in KINDS:
        raise ValueError(f"unknown traffic kind {traffic.get('kind')!r}")
    depths = traffic.get("depths")
    if not depths or not all(isinstance(d, int) and d >= 1 for d in depths):
        raise ValueError(f"depths must be positive integers: {depths!r}")


def warmup(traffic: dict) -> list[int]:
    validate(traffic)
    return list(traffic["depths"])


def checks(traffic: dict, seed: int) -> Iterator[int]:
    """The window's depth bounds, block after block, without end."""
    validate(traffic)
    rng = random.Random(seed)
    block = list(traffic["depths"])
    while True:
        if traffic.get("shuffle"):
            rng.shuffle(block)
        yield from block
