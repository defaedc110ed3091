"""ctypes bindings for the C++ multi-level queue core (native/src/mlq.cpp).

Uses ctypes rather than pybind11 (not available in this image); the C ABI
is intentionally narrow: handles in, handles out.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from typing import List, Optional, Tuple

from llmq_tpu.utils.logging import get_logger

log = get_logger("native")

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
_SRC = os.path.join(_REPO_ROOT, "native", "src", "mlq.cpp")
_SO = os.path.join(os.path.dirname(os.path.abspath(__file__)), "_libmlq.so")
#: sha256 of the source the .so was built from, kept beside it: a tree
#: copy (git checkout, rsync, the chip tool) rewrites every mtime, so
#: staleness is decided from the source's CONTENT.
_STAMP = _SO + ".srchash"

#: Absolute-path override for the loaded library. The sanitizer harness
#: (scripts/analysis/run_sanitizers.py, docs/analysis.md) points this at
#: an asan/ubsan-instrumented variant from native/build/ so the REAL
#: Python queue suites drive the instrumented core; the override is
#: loaded as-is (no rebuild, no staleness check) and a missing/unloadable
#: path is a hard error, not a silent fallback to the production .so.
_ENV_OVERRIDE = "LLMQ_NATIVE_LIB"

ERR_NOT_FOUND = -1
ERR_FULL = -2
ERR_EMPTY = -3
ERR_EXISTS = -4

_lib_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_load_failed = False


def _build_if_needed() -> bool:
    if not os.path.exists(_SRC):
        return os.path.exists(_SO)
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()
    if os.path.exists(_SO) and os.path.exists(_STAMP):
        with open(_STAMP) as f:
            if f.read().strip() == digest:
                return True
    # Build beside the target and rename: concurrent first imports
    # (worker processes, parallel tests) never load a half-written .so.
    tmp = f"{_SO}.tmp.{os.getpid()}"
    try:
        subprocess.run(
            ["g++", "-O2", "-std=c++17", "-fPIC", "-Wall", "-Wextra",
             "-Werror", "-shared", "-o", tmp, _SRC],
            check=True, capture_output=True, timeout=120,
        )
        os.replace(tmp, _SO)
        with open(_STAMP, "w") as f:
            f.write(digest)
        return True
    except (OSError, subprocess.SubprocessError) as e:
        # No compiler (or a failed build) → the Python queue core
        # serves; /api/v1/queues/stats "core" says which one did.
        log.warning("native queue core build failed; using Python fallback: %s", e)
        return False


def load_native() -> Optional[ctypes.CDLL]:
    global _lib, _load_failed
    with _lib_lock:
        if _lib is not None:
            return _lib
        if _load_failed:
            return None
        override = os.environ.get(_ENV_OVERRIDE, "")
        if override:
            # An explicit override must fail loudly: the caller asked
            # for a specific (typically sanitizer-instrumented) build,
            # and silently testing the production .so instead would
            # defeat the harness.
            lib = ctypes.CDLL(override)
        else:
            if not _build_if_needed():
                _load_failed = True
                return None
            try:
                lib = ctypes.CDLL(_SO)
            except OSError as e:
                log.warning("native queue core load failed; using Python fallback: %s", e)
                _load_failed = True
                return None
        lib.mlq_create.restype = ctypes.c_void_p
        lib.mlq_create.argtypes = []
        lib.mlq_destroy.restype = None
        lib.mlq_destroy.argtypes = [ctypes.c_void_p]
        lib.mlq_create_queue.restype = ctypes.c_int64
        lib.mlq_create_queue.argtypes = [ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int64]
        lib.mlq_remove_queue.restype = ctypes.c_int64
        lib.mlq_remove_queue.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
        lib.mlq_has_queue.restype = ctypes.c_int64
        lib.mlq_has_queue.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
        lib.mlq_push.restype = ctypes.c_int64
        lib.mlq_push.argtypes = [ctypes.c_void_p, ctypes.c_char_p, ctypes.c_uint64,
                                 ctypes.c_int32, ctypes.c_double]
        lib.mlq_pop.restype = ctypes.c_int64
        lib.mlq_pop.argtypes = [ctypes.c_void_p, ctypes.c_char_p, ctypes.c_double,
                                ctypes.POINTER(ctypes.c_uint64),
                                ctypes.POINTER(ctypes.c_double)]
        lib.mlq_pop_handle.restype = ctypes.c_int64
        lib.mlq_pop_handle.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                       ctypes.c_uint64, ctypes.c_double,
                                       ctypes.POINTER(ctypes.c_double)]
        lib.mlq_pop_if.restype = ctypes.c_int64
        lib.mlq_pop_if.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                   ctypes.c_uint64, ctypes.c_double]
        lib.mlq_peek.restype = ctypes.c_int64
        lib.mlq_peek.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                 ctypes.POINTER(ctypes.c_uint64)]
        lib.mlq_size.restype = ctypes.c_int64
        lib.mlq_size.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
        lib.mlq_complete.restype = ctypes.c_int64
        lib.mlq_complete.argtypes = [ctypes.c_void_p, ctypes.c_char_p, ctypes.c_double]
        lib.mlq_fail.restype = ctypes.c_int64
        lib.mlq_fail.argtypes = [ctypes.c_void_p, ctypes.c_char_p, ctypes.c_double]
        lib.mlq_requeue_accounting.restype = ctypes.c_int64
        lib.mlq_requeue_accounting.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
        lib.mlq_discard.restype = ctypes.c_int64
        lib.mlq_discard.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                    ctypes.c_uint64]
        lib.mlq_stats.restype = ctypes.c_int64
        lib.mlq_stats.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                  ctypes.POINTER(ctypes.c_int64),
                                  ctypes.POINTER(ctypes.c_double)]
        lib.mlq_queue_names.restype = ctypes.c_int64
        lib.mlq_queue_names.argtypes = [ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int64]
        _lib = lib
        return _lib


def native_available() -> bool:
    return load_native() is not None


class NativeMLQ:
    """Thin OO wrapper over the C ABI. Raises nothing; returns error codes
    so the Python MultiLevelQueue layer maps them to typed exceptions."""

    def __init__(self) -> None:
        lib = load_native()
        if lib is None:
            raise RuntimeError("native queue core unavailable")
        self._lib = lib
        self._h = ctypes.c_void_p(lib.mlq_create())

    def __del__(self) -> None:
        h = getattr(self, "_h", None)
        if h and getattr(self, "_lib", None) is not None:
            try:
                self._lib.mlq_destroy(h)
            except Exception:  # noqa: BLE001 — interpreter teardown
                pass
            self._h = None

    def create_queue(self, name: str, capacity: int) -> int:
        return self._lib.mlq_create_queue(self._h, name.encode(), capacity)

    def remove_queue(self, name: str) -> int:
        return self._lib.mlq_remove_queue(self._h, name.encode())

    def has_queue(self, name: str) -> bool:
        return bool(self._lib.mlq_has_queue(self._h, name.encode()))

    def push(self, name: str, handle: int, priority: int, enqueue_ts: float) -> int:
        return self._lib.mlq_push(self._h, name.encode(), handle, priority, enqueue_ts)

    def pop(self, name: str, now: float) -> Tuple[int, int, float]:
        """Returns (err, handle, wait_time)."""
        out_h = ctypes.c_uint64(0)
        out_w = ctypes.c_double(0.0)
        err = self._lib.mlq_pop(self._h, name.encode(), now,
                                ctypes.byref(out_h), ctypes.byref(out_w))
        return err, out_h.value, out_w.value

    def pop_handle(self, name: str, handle: int, now: float) -> Tuple[int, float]:
        """Pop a SPECIFIC pending handle with full pop accounting (the
        fair-dequeue layer's extraction op). Returns (err, wait)."""
        out_w = ctypes.c_double(0.0)
        err = self._lib.mlq_pop_handle(self._h, name.encode(), handle,
                                       now, ctypes.byref(out_w))
        return err, out_w.value

    def pop_if(self, name: str, expected_handle: int, now: float) -> int:
        """Atomic check-and-pop: pops only if the top is still
        ``expected_handle``. Returns 0, -5 (mismatch) or an error code."""
        return self._lib.mlq_pop_if(self._h, name.encode(), expected_handle, now)

    def peek(self, name: str) -> Tuple[int, int]:
        out_h = ctypes.c_uint64(0)
        err = self._lib.mlq_peek(self._h, name.encode(), ctypes.byref(out_h))
        return err, out_h.value

    def size(self, name: str) -> int:
        return self._lib.mlq_size(self._h, name.encode())

    def complete(self, name: str, process_time: float) -> int:
        return self._lib.mlq_complete(self._h, name.encode(), process_time)

    def fail(self, name: str, process_time: float) -> int:
        return self._lib.mlq_fail(self._h, name.encode(), process_time)

    def requeue_accounting(self, name: str) -> int:
        return self._lib.mlq_requeue_accounting(self._h, name.encode())

    def discard(self, name: str, handle: int) -> int:
        return self._lib.mlq_discard(self._h, name.encode(), handle)

    def stats(self, name: str) -> Tuple[int, List[int], List[float]]:
        out_i = (ctypes.c_int64 * 5)()
        out_d = (ctypes.c_double * 2)()
        err = self._lib.mlq_stats(self._h, name.encode(), out_i, out_d)
        return err, list(out_i), list(out_d)

    def queue_names(self) -> List[str]:
        # Retry with a doubled buffer on ERR_FULL (overflow must not be
        # folded into the empty case — that would silently drop every
        # queue from queue_names/total_size/get_all_stats).
        size = 1 << 16
        while True:
            buf = ctypes.create_string_buffer(size)
            n = self._lib.mlq_queue_names(self._h, buf, len(buf))
            if n == ERR_FULL:
                size *= 2
                if size > (1 << 28):
                    raise RuntimeError(
                        "mlq_queue_names overflow: registry exceeds 256MB")
                continue
            if n <= 0:
                return []
            return buf.value.decode().split("\n")
