"""The device a run lands on: required, named, and its memory peak."""

from __future__ import annotations

import os


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def require(chips: int):
    """The first ``chips`` TPU devices; never a CPU fallback."""
    import jax

    try:
        devs = jax.devices("tpu")
    except RuntimeError as e:
        raise NoAccelerator(f"JAX found no TPU: {e}") from None
    if len(devs) < chips:
        raise NoAccelerator(
            f"the cell needs {chips} TPU chips, JAX found {len(devs)}"
        )
    return devs[:chips]


def describe(devs) -> dict:
    return dict(
        platform=devs[0].platform, kind=devs[0].device_kind,
        count=len(devs),
    )


def memory_peak_bytes(devs) -> int | None:
    """``peak_bytes_in_use`` of the fullest device, where reported."""
    peaks = []
    for d in devs:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def seconds_since_process_start() -> float:
    """Seconds since this process started (``/proc``, 10 ms ticks)."""
    import time

    with open("/proc/self/stat") as f:
        # the command name may hold spaces: fields count from its ')'
        fields = f.read().rsplit(")", 1)[1].split()
    ticks = int(fields[19])  # field 22, starttime, in ticks since boot
    return (time.clock_gettime(time.CLOCK_BOOTTIME)
            - ticks / os.sysconf("SC_CLK_TCK"))
