"""Loader for the native C++ runtime core (csrc/ -> libpaddle_tpu_core.so).

The reference framework's runtime services are native C++ (profiler host
event recorder paddle/fluid/platform/profiler/, TCP comm bootstrap
platform/gen_comm_id_helper.cc, DataFeed framework/data_feed.h, monitor
platform/monitor.cc). This module loads our C++ equivalents via ctypes,
building the shared library on first use whenever the recorded source
hash is missing or differs from csrc/ (g++ is always present in the
toolchain; there is no pybind11 in this environment — ctypes is the
binding layer, mirroring the reference's pybind role at
paddle/fluid/pybind/pybind.cc).
"""
from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading

_LIB = None
_LOCK = threading.Lock()

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_LIB_PATH = os.path.join(_REPO_ROOT, "paddle_tpu", "lib",
                         "libpaddle_tpu_core.so")
_CSRC = os.path.join(_REPO_ROOT, "csrc")


_HASH_PATH = _LIB_PATH + ".srchash"
_BUILD_FLAGS = ["-O2", "-std=c++17", "-fPIC", "-Wall", "-pthread",
                "-shared"]
_built_here = False


def _sources():
    # single source of truth: every .cc in csrc/ (mirrors csrc/Makefile)
    # EXCEPT capi.cc — the C inference API embeds CPython and builds as
    # its own .so via `make -C csrc capi`
    return sorted(os.path.join(_CSRC, f) for f in os.listdir(_CSRC)
                  if f.endswith(".cc") and f != "capi.cc")


def _source_hash():
    """sha256 over the build flags and every source/header in csrc/.
    The .so is git-ignored, so a checkout or a copy of the tree has
    either no library or one whose mtime means nothing; the recorded
    hash is the only evidence that it was built from THESE sources."""
    h = hashlib.sha256(" ".join(_BUILD_FLAGS).encode())
    headers = sorted(os.path.join(_CSRC, f) for f in os.listdir(_CSRC)
                     if f.endswith(".h"))
    for path in _sources() + headers:
        h.update(os.path.basename(path).encode())
        with open(path, "rb") as f:
            h.update(f.read())
    return h.hexdigest()


def _recorded_hash():
    try:
        with open(_HASH_PATH) as f:
            return f.read().strip()
    except OSError:
        return None


def _build(src_hash):
    global _built_here
    os.makedirs(os.path.dirname(_LIB_PATH), exist_ok=True)
    # build beside the target and rename: a concurrent process (the
    # multi-process tests start eight at once) never dlopens a
    # half-written library
    tmp = "%s.%d.tmp" % (_LIB_PATH, os.getpid())
    cmd = ["g++"] + _BUILD_FLAGS + ["-o", tmp] + _sources()
    try:
        subprocess.run(cmd, check=True, capture_output=True)
        os.replace(tmp, _LIB_PATH)
    finally:
        if os.path.exists(tmp):
            os.remove(tmp)
    with open(_HASH_PATH + ".tmp%d" % os.getpid(), "w") as f:
        f.write(src_hash + "\n")
    os.replace(f.name, _HASH_PATH)
    _built_here = True


def built_in_this_process():
    """True when get_lib() compiled csrc/ in this process (chip_smoke.py
    prints it: a fresh checkout must build, a warm tree must not)."""
    return _built_here


def _declare(lib):
    c = ctypes
    # trace.cc
    lib.pt_trace_enable.argtypes = [c.c_int]
    lib.pt_trace_disable.argtypes = []
    lib.pt_trace_level.restype = c.c_int
    lib.pt_trace_push.argtypes = [c.c_char_p, c.c_int]
    lib.pt_trace_pop.argtypes = []
    lib.pt_trace_instant.argtypes = [c.c_char_p, c.c_int]
    lib.pt_trace_counter.argtypes = [c.c_char_p, c.c_int64]
    lib.pt_trace_dump.argtypes = [c.c_char_p]
    lib.pt_trace_dump.restype = c.c_int
    lib.pt_trace_event_count.restype = c.c_int64
    # store.cc
    lib.pt_store_server_start.argtypes = [c.c_int]
    lib.pt_store_server_start.restype = c.c_int
    lib.pt_store_server_port.argtypes = [c.c_int]
    lib.pt_store_server_port.restype = c.c_int
    lib.pt_store_server_stop.argtypes = [c.c_int]
    lib.pt_store_connect.argtypes = [c.c_char_p, c.c_int, c.c_int]
    lib.pt_store_connect.restype = c.c_int
    lib.pt_store_close.argtypes = [c.c_int]
    lib.pt_store_set.argtypes = [c.c_int, c.c_char_p, c.c_char_p, c.c_int]
    lib.pt_store_set.restype = c.c_int
    lib.pt_store_get.argtypes = [c.c_int, c.c_char_p, c.c_void_p, c.c_int,
                                 c.c_int64]
    lib.pt_store_get.restype = c.c_int
    lib.pt_store_add.argtypes = [c.c_int, c.c_char_p, c.c_int64,
                                 c.POINTER(c.c_int64)]
    lib.pt_store_add.restype = c.c_int
    # nonced (idempotent) add
    lib.pt_store_add_nonced.argtypes = [
        c.c_int, c.c_char_p, c.c_int64, c.c_uint64, c.c_uint64,
        c.POINTER(c.c_int64)]
    lib.pt_store_add_nonced.restype = c.c_int
    lib.pt_store_counter_get.argtypes = [c.c_int, c.c_char_p,
                                         c.POINTER(c.c_int64)]
    lib.pt_store_counter_get.restype = c.c_int
    lib.pt_store_delete.argtypes = [c.c_int, c.c_char_p]
    lib.pt_store_delete.restype = c.c_int
    # feed.cc
    lib.pt_feed_create.argtypes = [c.c_int, c.c_int, c.c_uint64]
    lib.pt_feed_create.restype = c.c_int
    lib.pt_feed_add_file.argtypes = [c.c_int, c.c_char_p]
    lib.pt_feed_add_file.restype = c.c_int
    lib.pt_feed_start.argtypes = [c.c_int, c.c_int]
    lib.pt_feed_start.restype = c.c_int
    lib.pt_feed_next.argtypes = [c.c_int, c.c_void_p, c.c_int]
    lib.pt_feed_next.restype = c.c_int
    lib.pt_feed_destroy.argtypes = [c.c_int]
    lib.pt_feed_write_open.argtypes = [c.c_char_p]
    lib.pt_feed_write_open.restype = c.c_void_p
    lib.pt_feed_write_record.argtypes = [c.c_void_p, c.c_char_p, c.c_int]
    lib.pt_feed_write_record.restype = c.c_int
    lib.pt_feed_write_close.argtypes = [c.c_void_p]
    # interp.cc
    lib.pt_interp_create.argtypes = [c.c_int]
    lib.pt_interp_create.restype = c.c_int
    lib.pt_interp_add_dep.argtypes = [c.c_int, c.c_int, c.c_int]
    lib.pt_interp_add_dep.restype = c.c_int
    INSTR_FN = c.CFUNCTYPE(c.c_int, c.c_void_p, c.c_int64)
    lib.pt_interp_run.argtypes = [c.c_int, INSTR_FN, c.c_void_p,
                                  c.c_int]
    lib.pt_interp_run.restype = c.c_int
    lib.pt_interp_last_error.argtypes = [c.c_int]
    lib.pt_interp_last_error.restype = c.c_int64
    lib.pt_interp_executed.argtypes = [c.c_int]
    lib.pt_interp_executed.restype = c.c_int
    lib.pt_interp_destroy.argtypes = [c.c_int]
    lib._INSTR_FN = INSTR_FN
    # stats.cc
    lib.pt_stat_add.argtypes = [c.c_char_p, c.c_int64]
    lib.pt_stat_get.argtypes = [c.c_char_p]
    lib.pt_stat_get.restype = c.c_int64
    lib.pt_stat_peak.argtypes = [c.c_char_p]
    lib.pt_stat_peak.restype = c.c_int64
    lib.pt_stat_reset.argtypes = [c.c_char_p]
    lib.pt_stat_dump.argtypes = [c.c_char_p, c.c_int]
    lib.pt_stat_dump.restype = c.c_int
    return lib


def get_lib():
    """Load (building if needed) the native core; returns the ctypes CDLL."""
    global _LIB
    if _LIB is not None:
        return _LIB
    with _LOCK:
        if _LIB is not None:
            return _LIB
        src_hash = _source_hash()
        if not os.path.exists(_LIB_PATH) or _recorded_hash() != src_hash:
            _build(src_hash)
        _LIB = _declare(ctypes.CDLL(_LIB_PATH))
    return _LIB


def available():
    try:
        get_lib()
        return True
    except Exception:
        return False


# ---- thin pythonic wrappers -------------------------------------------------

class Stats:
    """Named global counters (reference platform/monitor.cc STAT_ADD)."""

    @staticmethod
    def add(name, delta=1):
        get_lib().pt_stat_add(name.encode(), int(delta))

    @staticmethod
    def get(name):
        return int(get_lib().pt_stat_get(name.encode()))

    @staticmethod
    def peak(name):
        return int(get_lib().pt_stat_peak(name.encode()))

    @staticmethod
    def reset(name):
        get_lib().pt_stat_reset(name.encode())

    @staticmethod
    def dump():
        buf = ctypes.create_string_buffer(1 << 16)
        n = get_lib().pt_stat_dump(buf, len(buf))
        out = {}
        for part in buf.raw[:n].decode().split(";"):
            if "=" in part:
                k, v = part.split("=", 1)
                out[k] = int(v)
        return out
