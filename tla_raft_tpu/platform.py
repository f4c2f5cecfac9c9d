"""Shared JAX bootstrap for CLI entry points and scripts.

* The run must land on the device the caller asked for: unless
  ``JAX_PLATFORMS`` names the CPU, a backend other than ``tpu`` is an
  error, never a silent fallback.
* Compiles go to JAX's persistent cache: ``JAX_COMPILATION_CACHE_DIR``
  when set (JAX reads it itself), else one fixed directory inside the
  checkout — the directory is part of the cache key, so a path that
  moves between runs never hits.
"""

from __future__ import annotations

import os

# <checkout>/.jax_cache (gitignored, and left out of chip copies)
CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache"
)


def cpu_requested() -> bool:
    """True iff the caller put the CPU first in ``JAX_PLATFORMS`` (tests,
    CPU rehearsals).  ``tpu,cpu`` asks for the TPU: its CPU entry is
    JAX's fallback, which is exactly what must not pass silently."""
    return os.environ.get("JAX_PLATFORMS", "").split(",")[0] == "cpu"


def setup_jax():
    """Compile cache + platform check; returns the jax module."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    backend = jax.default_backend()
    if backend != "tpu" and not cpu_requested():
        raise RuntimeError(
            f"JAX found no TPU (backend {backend!r}, devices "
            f"{jax.devices()}); set JAX_PLATFORMS=cpu to run on the CPU"
        )
    return jax


def check_chip_workers(n_workers: int) -> None:
    """Refuse to start more than one chip-using worker process.

    A chip belongs to one process at a time, and a JAX process claims
    every chip of its host: nothing here gives a worker a chip of its
    own, so a second worker would fail or hang at backend start-up,
    whatever the chip count.  CPU-pinned workers (``JAX_PLATFORMS=cpu``)
    use no chip and are not capped."""
    if n_workers > 1 and not cpu_requested():
        raise ValueError(
            f"{n_workers} worker processes would share this host's TPU "
            "chips, and each claims them all at start-up; run one, or "
            "set JAX_PLATFORMS=cpu to run the workers on the CPU"
        )
