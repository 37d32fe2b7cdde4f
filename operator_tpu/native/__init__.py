"""Native runtime components (C++ via ctypes) with pure-Python fallbacks.

The scanner (native/logscan.cpp) is compiled the first time it is needed
into ``OPERATOR_TPU_NATIVE_DIR`` (default: alongside this package) under a
name that carries a hash of its source, ``liblogscan-<sha256[:16]>.so``:
a library is only ever loaded if it was built from the source that sits
in this checkout, and a fresh checkout (which holds no ``.so`` — they are
git-ignored) builds its own.  A missing source, compiler or writable
directory selects the Python scanner, and ``MultiPatternScanner.native``
says which one ran.
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import subprocess
import tempfile
import threading
from typing import Optional, Sequence

log = logging.getLogger(__name__)

_SOURCE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    "native",
    "logscan.cpp",
)
_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_lib_failed = False


def _lib_path() -> Optional[str]:
    """Where the library built from THIS source lives (None: no source)."""
    try:
        with open(_SOURCE, "rb") as f:
            digest = hashlib.sha256(f.read()).hexdigest()[:16]
    except OSError:
        return None
    directory = os.environ.get("OPERATOR_TPU_NATIVE_DIR") or os.path.dirname(
        os.path.abspath(__file__)
    )
    return os.path.join(directory, f"liblogscan-{digest}.so")


def _build_library(target: str) -> bool:
    """Compile logscan.cpp to ``target`` (atomically: concurrent builders
    each rename a complete file into place)."""
    try:
        with tempfile.TemporaryDirectory(dir=os.path.dirname(target)) as tmp:
            scratch = os.path.join(tmp, os.path.basename(target))
            subprocess.run(
                ["g++", "-O2", "-shared", "-fPIC", "-std=c++17", _SOURCE, "-o", scratch],
                check=True, capture_output=True, timeout=120,
            )
            os.replace(scratch, target)
        return True
    except (OSError, subprocess.SubprocessError) as exc:
        log.info("native scanner build skipped: %s", exc)
        return False


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _lib_failed
    with _lock:
        if _lib is not None or _lib_failed:
            return _lib
        path = _lib_path()
        if path is None or not (os.path.exists(path) or _build_library(path)):
            _lib_failed = True
            return None
        try:
            lib = ctypes.CDLL(path)
            lib.ls_build.restype = ctypes.c_void_p
            lib.ls_build.argtypes = [
                ctypes.POINTER(ctypes.c_char_p),
                ctypes.POINTER(ctypes.c_int32),
                ctypes.c_int32,
            ]
            lib.ls_scan.restype = ctypes.c_int64
            lib.ls_scan.argtypes = [
                ctypes.c_void_p,
                ctypes.c_char_p,
                ctypes.c_int64,
                ctypes.POINTER(ctypes.c_int32),
                ctypes.POINTER(ctypes.c_int64),
                ctypes.c_int64,
            ]
            lib.ls_free.restype = None
            lib.ls_free.argtypes = [ctypes.c_void_p]
            _lib = lib
        except OSError as exc:
            log.warning("native scanner load failed (%s); using Python fallback", exc)
            _lib_failed = True
        return _lib


class _PyScanner:
    """Fallback: one ``bytes.find`` sweep per literal (C-speed inner loop,
    O(literals) passes instead of the automaton's single pass)."""

    def __init__(self, literals: Sequence[bytes]) -> None:
        self.literals = list(literals)

    def scan_arrays(self, text: bytes, max_hits: int = 1 << 20):
        import numpy as np

        ids: list[int] = []
        offsets: list[int] = []
        for literal_id, literal in enumerate(self.literals):
            if not literal:
                continue
            start = 0
            while len(ids) < max_hits:
                found = text.find(literal, start)
                if found < 0:
                    break
                ids.append(literal_id)
                offsets.append(found + len(literal) - 1)
                start = found + 1
        return np.asarray(ids, np.int32), np.asarray(offsets, np.int64)

    def scan(self, text: bytes, max_hits: int = 1 << 20) -> list[tuple[int, int]]:
        ids, offsets = self.scan_arrays(text, max_hits)
        return [(int(i), int(o)) for i, o in zip(ids, offsets)]


class _NativeScanner:
    def __init__(self, lib: ctypes.CDLL, literals: Sequence[bytes]) -> None:
        self._lib = lib
        array = (ctypes.c_char_p * len(literals))(*literals)
        lens = (ctypes.c_int32 * len(literals))(*[len(l) for l in literals])
        self._handle = lib.ls_build(array, lens, len(literals))

    def scan_arrays(self, text: bytes, max_hits: int = 1 << 20):
        import numpy as np

        out_ids = np.empty(max_hits, np.int32)
        out_offsets = np.empty(max_hits, np.int64)
        count = self._lib.ls_scan(
            self._handle,
            text,
            len(text),
            out_ids.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            out_offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            max_hits,
        )
        return out_ids[:count].copy(), out_offsets[:count].copy()

    def __del__(self):  # pragma: no cover - interpreter shutdown ordering
        try:
            if self._handle:
                self._lib.ls_free(self._handle)
                self._handle = None
        except (AttributeError, TypeError):
            pass


class MultiPatternScanner:
    """Find all occurrences of N byte literals in one text pass.

    ``scan`` returns (literal_id, end_offset) pairs; ``scan_arrays`` the
    same as two numpy arrays (the prefilter's vectorised path).  Backed by
    the C++ Aho-Corasick automaton when available, else the Python
    fallback.
    """

    def __init__(self, literals: Sequence[bytes]) -> None:
        lib = _load()
        self.native = lib is not None
        self._impl = (
            _NativeScanner(lib, literals) if lib is not None else _PyScanner(literals)
        )

    def scan_arrays(self, text: bytes, max_hits: Optional[int] = None):
        """-> (ids [N] int32, end_offsets [N] int64) numpy arrays.

        Never drops hits: a saturated buffer retries with 4x capacity
        (dropping would silently lose prefilter candidates = lost matches).
        """
        capacity = max_hits or max(4096, len(text) // 4)
        while True:
            ids, offsets = self._impl.scan_arrays(text, capacity)
            if len(ids) < capacity:
                return ids, offsets
            capacity *= 4

    def scan(self, text: bytes, max_hits: Optional[int] = None) -> list[tuple[int, int]]:
        ids, offsets = self.scan_arrays(text, max_hits)
        return [(int(i), int(o)) for i, o in zip(ids, offsets)]
