"""Native (C++) runtime components, built on first use.

The reference's native substrate is the JVM + Spark (no C++/CUDA anywhere —
SURVEY.md §2); this package holds the rebuild's own native pieces:

- ``event_log.cpp`` — append-only binary event log with C++ filtered scan
  (pio_tpu/storage/eventlog.py wraps it as a storage backend).
- ``als_pack.cpp`` — parallel (user, item) edge sort and degree count
  feeding the ALS trainer's device transfer (pio_tpu/models/als.py).

Build model: no wheels, no pybind11 — ``g++ -O3 -march=native`` at first
import, cached under ``$PIO_TPU_HOME/native/<src+flags sha>-<isa>.so`` so
rebuilds happen when the source, flags, or host ISA change (a
native-codegen binary never loads on a CPU missing its instructions).
ctypes loads the result. Environments
without a toolchain get :class:`NativeUnavailable` and callers fall back to
pure-Python backends.
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import subprocess
import threading
from typing import Optional

from pio_tpu.utils import knobs

log = logging.getLogger("pio_tpu.native")

_lock = threading.Lock()
_cache: dict = {}


class NativeUnavailable(RuntimeError):
    """No compiler / compile failed — use a pure-Python backend instead."""


def _build_dir() -> str:
    home = knobs.knob_str("PIO_TPU_HOME") or os.path.expanduser("~/.pio_tpu")
    d = os.path.join(home, "native")
    os.makedirs(d, exist_ok=True)
    return d


_FLAGS = ["-O3", "-march=native", "-shared", "-fPIC", "-std=c++17"]


def _host_isa_tag() -> str:
    """Short tag of this host's ISA feature set — part of the .so cache
    key, so a ``-march=native`` binary built on one CPU (shared home,
    baked image) is never loaded on a CPU missing its instructions
    (SIGILL), it just rebuilds."""
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith(("flags", "Features")):
                    return hashlib.sha256(line.encode()).hexdigest()[:8]
    except OSError:
        pass
    # no /proc/cpuinfo (macOS, sandbox): fall back to a platform string —
    # coarser than the feature set, but never a shared constant that
    # would let one host's -march=native binary load on another
    import platform

    return hashlib.sha256(
        f"{platform.system()}-{platform.machine()}-"
        f"{platform.processor()}".encode()
    ).hexdigest()[:8]


def build_library(name: str) -> str:
    """Compile ``<name>.cpp`` (beside this file) → cached .so path.
    Cache key = source hash + compile flags + host ISA tag."""
    src = os.path.join(os.path.dirname(__file__), f"{name}.cpp")
    with open(src, "rb") as f:
        digest = hashlib.sha256(
            f.read() + " ".join(_FLAGS).encode()
        ).hexdigest()[:16]
    out = os.path.join(
        _build_dir(), f"{name}-{digest}-{_host_isa_tag()}.so"
    )
    if os.path.exists(out):
        return out
    tmp = f"{out}.{os.getpid()}.tmp"  # per-process: concurrent first builds
    # -O3 + -march=native: the packers and the host scorer are SIMD-bound
    # inner loops; the ISA tag above keeps native codegen host-correct
    cmd = ["g++", *_FLAGS, "-o", tmp, src]
    try:
        proc = subprocess.run(
            cmd, capture_output=True, text=True, timeout=120
        )
    except (OSError, subprocess.TimeoutExpired) as e:
        raise NativeUnavailable(f"cannot run g++: {e}") from e
    if proc.returncode != 0:
        raise NativeUnavailable(
            f"g++ failed for {src}:\n{proc.stderr[-2000:]}"
        )
    os.replace(tmp, out)
    log.info("built native library %s", out)
    return out


_NUM_STR = 9  # string columns in a PelResult (see event_log.cpp)


class PelResult(ctypes.Structure):
    _fields_ = [
        ("n", ctypes.c_int64),
        ("time_us", ctypes.POINTER(ctypes.c_int64)),
        ("ctime_us", ctypes.POINTER(ctypes.c_int64)),
        # POINTER(c_char), not c_char_p: arenas are length-delimited binary
        # (c_char_p would truncate at the first NUL on conversion)
        ("arena", ctypes.POINTER(ctypes.c_char) * _NUM_STR),
        ("off", ctypes.POINTER(ctypes.c_uint32) * _NUM_STR),
    ]


def event_log_lib():
    """Load (building if needed) the event-log library; cached."""
    with _lock:
        if "event_log" in _cache:
            return _cache["event_log"]
        # first-use compile fills the cache: serializing the build
        # under _lock is the point (one compiler run per library)
        # pio: disable=lock-blocking-call
        lib = ctypes.CDLL(build_library("event_log"))
        lib.pel_append.argtypes = [
            ctypes.c_char_p, ctypes.c_char_p, ctypes.c_int64,
            ctypes.c_int,  # do_sync (durability knob)
        ]
        lib.pel_append.restype = ctypes.c_int
        lib.pel_scan.argtypes = [
            ctypes.c_char_p,  # path
            ctypes.c_char_p, ctypes.c_int,  # event_names set, count
            ctypes.c_char_p, ctypes.c_char_p,  # entity_type, entity_id
            ctypes.c_char_p, ctypes.c_char_p,  # target type/id
            ctypes.c_char_p,  # event_id
            ctypes.c_int64, ctypes.c_int64,  # start, until (us)
            ctypes.c_int, ctypes.c_int64,  # reversed, limit
            ctypes.POINTER(PelResult),
        ]
        lib.pel_scan.restype = ctypes.c_int
        lib.pel_free_result.argtypes = [ctypes.POINTER(PelResult)]
        lib.pel_free_result.restype = None
        lib.pel_count.argtypes = [ctypes.c_char_p]
        lib.pel_count.restype = ctypes.c_int64
        lib.pel_repair.argtypes = [ctypes.c_char_p]
        lib.pel_repair.restype = ctypes.c_int64
        lib.pel_compact.argtypes = [ctypes.c_char_p]
        lib.pel_compact.restype = ctypes.c_int64
        _cache["event_log"] = lib
        return lib


def als_pack_lib():
    """Load (building if needed) the ALS edge-sort library; cached."""
    with _lock:
        if "als_pack" in _cache:
            return _cache["als_pack"]
        # first-use compile fills the cache: serializing the build
        # under _lock is the point (one compiler run per library)
        # pio: disable=lock-blocking-call
        lib = ctypes.CDLL(build_library("als_pack"))
        i32p = ctypes.POINTER(ctypes.c_int32)
        i64p = ctypes.POINTER(ctypes.c_int64)
        f32p = ctypes.POINTER(ctypes.c_float)
        lib.als_pack_count.argtypes = [
            i32p, ctypes.c_int64, ctypes.c_int32, ctypes.c_int32, i64p
        ]
        lib.als_pack_count.restype = ctypes.c_int64
        lib.als_sort_by_entity.argtypes = [
            i32p, i32p, f32p, ctypes.c_int64, ctypes.c_int32, i64p,
            i32p, f32p,
        ]
        lib.als_sort_by_entity.restype = ctypes.c_int
        lib.als_sort_within_entity.argtypes = [
            i32p, f32p, ctypes.c_int32, i64p,
        ]
        lib.als_sort_within_entity.restype = ctypes.c_int
        _cache["als_pack"] = lib
        return lib


def topn_host_lib():
    """Load (building if needed) the host top-N scorer library; cached."""
    with _lock:
        if "topn_host" in _cache:
            return _cache["topn_host"]
        # first-use compile fills the cache: serializing the build
        # under _lock is the point (one compiler run per library)
        # pio: disable=lock-blocking-call
        lib = ctypes.CDLL(build_library("topn_host"))
        f32p = ctypes.POINTER(ctypes.c_float)
        i32p = ctypes.POINTER(ctypes.c_int32)
        i64p = ctypes.POINTER(ctypes.c_int64)
        lib.topn_host_f32.argtypes = [
            f32p, f32p, ctypes.c_int32, ctypes.c_int32, ctypes.c_int32,
            i32p, ctypes.c_int64, ctypes.c_int32, i64p, f32p,
        ]
        lib.topn_host_f32.restype = ctypes.c_int
        _cache["topn_host"] = lib
        return lib
