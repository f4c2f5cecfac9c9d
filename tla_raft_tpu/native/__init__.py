"""Native runtime tier: lazy-built C++ components bound via ctypes.

``HostFPStore`` wraps fpstore.cpp — the external-memory fingerprint store
that takes over TLC's FPSet role (JVM heap + ``states/`` disk spill in
the reference's myrun.sh) when a run's visited set outgrows device HBM.
The shared library is compiled on first use with the system toolchain
and cached next to the source, keyed by a hash of what built it.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import platform
import subprocess

import numpy as np

_DIR = os.path.dirname(__file__)
_SRC = os.path.join(_DIR, "fpstore.cpp")
_SO = os.path.join(_DIR, "libfpstore.so")
_BASE_SRC = os.path.join(_DIR, "cpubase.cpp")
_BASE_BIN = os.path.join(_DIR, "cpubase")


def _host_key() -> str:
    """What ``-march=native`` compiles for: the build host's CPU."""
    try:
        with open("/proc/cpuinfo") as f:
            info = [
                ln for ln in f.read().splitlines()
                if ln.startswith(("model name", "flags"))
            ][:2]
    except OSError:
        info = []
    return "|".join([platform.machine(), *info])


def _build(src: str, out: str, cmd: list[str], force: bool,
           host_specific: bool) -> str:
    """Compile ``src`` -> ``out`` unless ``out.key`` records a build of
    this exact source and command (and, for ``-march=native`` builds,
    this CPU) — a binary copied from another host is rebuilt, never
    run."""
    h = hashlib.sha256()
    with open(src, "rb") as f:
        h.update(f.read())
    h.update(" ".join(cmd).encode())
    if host_specific:
        h.update(_host_key().encode())
    key = h.hexdigest()[:16]
    stamp = out + ".key"
    if not force and os.path.exists(out) and os.path.exists(stamp):
        with open(stamp) as f:
            if f.read().strip() == key:
                return out
    tmp = f"{out}.tmp{os.getpid()}"
    subprocess.run([*cmd, src, "-o", tmp], check=True, capture_output=True)
    # compiled-artifact cache swap, not a checkpoint artifact
    os.replace(tmp, out)  # graftlint: waive[GL009]
    with open(tmp, "w") as f:
        f.write(key)
    os.replace(tmp, stamp)  # graftlint: waive[GL009]
    return out


def build_native(force: bool = False) -> str:
    """Compile fpstore.cpp -> libfpstore.so (cached by source hash)."""
    return _build(
        _SRC, _SO, ["g++", "-O2", "-std=c++17", "-shared", "-fPIC"],
        force, host_specific=False,
    )


def build_cpubase(force: bool = False) -> str:
    """Compile cpubase.cpp -> the native CPU baseline checker binary.

    The multithreaded C++ explicit-state checker of the same spec family
    (the honest stand-in for `tlc -workers N`, BASELINE.md).  Built with ``-march=native``,
    so the cache is keyed by the host's CPU as well as the source."""
    return _build(
        _BASE_SRC, _BASE_BIN,
        ["g++", "-O3", "-march=native", "-std=c++17", "-pthread", "-w"],
        force, host_specific=True,
    )


_lib = None


def _load():
    global _lib
    if _lib is None:
        lib = ctypes.CDLL(build_native())
        lib.fpstore_open.restype = ctypes.c_void_p
        lib.fpstore_open.argtypes = [ctypes.c_char_p, ctypes.c_uint64]
        lib.fpstore_count.restype = ctypes.c_uint64
        lib.fpstore_count.argtypes = [ctypes.c_void_p]
        lib.fpstore_num_runs.restype = ctypes.c_uint64
        lib.fpstore_num_runs.argtypes = [ctypes.c_void_p]
        lib.fpstore_bloom_skips.restype = ctypes.c_uint64
        lib.fpstore_bloom_skips.argtypes = [ctypes.c_void_p]
        lib.fpstore_contains.restype = None
        lib.fpstore_contains.argtypes = [
            ctypes.c_void_p,
            ctypes.POINTER(ctypes.c_uint64),
            ctypes.c_uint64,
            ctypes.POINTER(ctypes.c_uint8),
        ]
        lib.fpstore_insert.restype = ctypes.c_uint64
        lib.fpstore_insert.argtypes = lib.fpstore_contains.argtypes
        lib.fpstore_compact.restype = ctypes.c_int
        lib.fpstore_compact.argtypes = [ctypes.c_void_p]
        lib.fpstore_close.restype = None
        lib.fpstore_close.argtypes = [ctypes.c_void_p]
        _lib = lib
    return _lib


class HostFPStore:
    """Sorted-run external-memory u64 set with batched insert/membership."""

    def __init__(self, dirpath: str, mem_budget_entries: int = 0):
        os.makedirs(dirpath, exist_ok=True)
        self._dir = dirpath
        self._budget = mem_budget_entries
        self._lib = _load()
        self._h = self._lib.fpstore_open(
            dirpath.encode(), ctypes.c_uint64(mem_budget_entries)
        )
        if not self._h:
            raise RuntimeError("fpstore_open failed")

    def __len__(self) -> int:
        return int(self._lib.fpstore_count(self._h))

    @property
    def num_runs(self) -> int:
        return int(self._lib.fpstore_num_runs(self._h))

    @property
    def bloom_skips(self) -> int:
        """Run binary searches avoided by the per-run blooms (built at
        spill time, in-memory only — see fpstore.cpp).  Bloom hits are
        not proof of membership, so the filter only short-circuits the
        per-run search; it never feeds the phase-1 drop."""
        return int(self._lib.fpstore_bloom_skips(self._h))

    def _ptrs(self, fps: np.ndarray):
        fps = np.ascontiguousarray(fps, np.uint64)
        out = np.zeros(len(fps), np.uint8)
        return (
            fps,
            out,
            fps.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        )

    def contains(self, fps: np.ndarray) -> np.ndarray:
        fps, out, p_in, p_out = self._ptrs(fps)
        self._lib.fpstore_contains(self._h, p_in, len(fps), p_out)
        return out.astype(bool)

    def insert(self, fps: np.ndarray) -> np.ndarray:
        """Insert a batch; returns the is-new mask (False = already seen,
        including duplicates earlier in the same batch)."""
        fps, out, p_in, p_out = self._ptrs(fps)
        added = self._lib.fpstore_insert(self._h, p_in, len(fps), p_out)
        if added == np.uint64(0xFFFFFFFFFFFFFFFF):
            raise IOError("fpstore spill failed")
        return out.astype(bool)

    def compact(self) -> None:
        if self._lib.fpstore_compact(self._h) != 0:
            raise IOError("fpstore compact failed")

    def clear(self) -> None:
        """Empty the store in place (delta-log resume rebuilds it).

        Reopens a fresh native handle (close unlinks this handle's run
        files) and sweeps any orphaned ``run_*.fp`` left by a crashed
        process — those were never loaded, but they waste disk and their
        names will be reused.
        """
        import glob

        self.close()
        for f in glob.glob(os.path.join(self._dir, "run_*.fp")):
            os.unlink(f)
        self._h = self._lib.fpstore_open(
            self._dir.encode(), ctypes.c_uint64(self._budget)
        )
        if not self._h:
            raise RuntimeError("fpstore_open failed")

    def close(self) -> None:
        if self._h:
            self._lib.fpstore_close(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:  # graftlint: waive[GL003] — a destructor at
            # interpreter teardown must never raise, whatever the cause
            pass


def insert_sharded(stores: list, fps: np.ndarray) -> int:
    """Split ``fps`` by owner (fp % len(stores)) and insert each share
    into its store concurrently; returns the total newly-inserted count.

    The ctypes insert releases the GIL for the C++ sort/merge/spill, so
    D shards insert in parallel on a multi-core host — the deep-sweep
    mesh uses this to rebuild its per-owner stores on resume (and its
    level loop uses the same property for the double-buffered tail)."""
    from concurrent.futures import ThreadPoolExecutor

    D = len(stores)
    fps = np.ascontiguousarray(fps, np.uint64)
    shares = [fps[fps % np.uint64(D) == o] for o in range(D)]
    if len(fps) and not bool(np.all(fps[1:] >= fps[:-1])):
        # both resume callers pass np.unique output (sorted), and the
        # owner filter of a sorted array stays sorted — the O(n log n)
        # per-share re-sort only runs for unsorted inputs, so slab- or
        # log-sourced rebuilds skip the store-insert path's last
        # host-side sort entirely (the single-CPU rebuild tail)
        shares = [np.sort(s) for s in shares]

    def one(o):
        return int(stores[o].insert(shares[o]).sum()) if len(shares[o]) else 0

    from ..analysis.sanitize import forbid_device_dispatch_in_thread

    with ThreadPoolExecutor(
        max_workers=min(D, os.cpu_count() or 2),
        initializer=forbid_device_dispatch_in_thread,
    ) as ex:
        return sum(ex.map(one, range(D)))
