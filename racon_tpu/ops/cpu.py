"""ctypes bindings to the native CPU compute engines.

The shared library (racon_tpu/native/libracon_native.so) provides the
edlib-equivalent banded global aligner and the spoa-equivalent POA
consensus engine.  Calls release the GIL, so the Polisher's thread pool
achieves real parallelism on the CPU fallback path, mirroring the
reference's per-thread spoa engines (src/polisher.cpp:180-184,490-503).

The library is built on demand with `make` the first time it is needed,
and rebuilt whenever its tracked sources or the host's -march target
change (``build_stamp``).
"""

from __future__ import annotations

import ctypes
import fcntl
import hashlib
import os
import subprocess
import threading
from typing import List, Optional, Tuple

import numpy as np

_NATIVE_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "native")


def _lib_path() -> str:
    """Resolved at call time so RACON_TPU_NATIVE_LIB (e.g. the ASan
    `make debug` build) works even when set after import."""
    return os.environ.get(
        "RACON_TPU_NATIVE_LIB",
        os.path.join(_NATIVE_DIR, "libracon_native.so"))

_lib = None
_lib_lock = threading.Lock()


# every tracked input of the native build: a change to any of them,
# or to the host CPU the -march=native code targets, rebuilds
_BUILD_INPUTS = ("Makefile", "align.cpp", "poa.cpp", "poa_batch.cpp",
                 "poa_graph.hpp")


def _stamp_path() -> str:
    return os.path.join(_NATIVE_DIR, "libracon_native.stamp")


def _march() -> str:
    """The target ``-march=native`` resolves to on this host (a
    library copied from another machine may use instructions this one
    lacks)."""
    cxx = os.environ.get("CXX", "g++")
    try:
        out = subprocess.run(
            [cxx, "-march=native", "-Q", "--help=target"],
            capture_output=True, text=True, timeout=60).stdout
    except (OSError, subprocess.SubprocessError):
        return "unknown"
    for line in out.splitlines():
        parts = line.split()
        if len(parts) == 2 and parts[0] == "-march=":
            return parts[1]
    return "unknown"


def build_stamp() -> str:
    """Hash of the tracked native sources plus the host's -march
    target: the library is current only when its stamp matches."""
    h = hashlib.sha256()
    for name in _BUILD_INPUTS:
        h.update(name.encode())
        with open(os.path.join(_NATIVE_DIR, name), "rb") as f:
            h.update(f.read())
    h.update(_march().encode())
    return h.hexdigest()


def _needs_build(stamp: str) -> bool:
    if not os.path.exists(_lib_path()):
        return True
    try:
        with open(_stamp_path()) as f:
            return f.read().strip() != stamp
    except OSError:
        return True


def _build_library() -> None:
    lib_path = _lib_path()
    if "RACON_TPU_NATIVE_LIB" in os.environ:
        if not os.path.exists(lib_path):
            raise RuntimeError(
                f"[racon_tpu::native] RACON_TPU_NATIVE_LIB points at a "
                f"missing library: {lib_path}")
        return
    stamp = build_stamp()
    if not _needs_build(stamp):
        return
    # one builder at a time across processes (pytest-xdist workers
    # all reach here on a fresh checkout); -B rebuilds every object,
    # since objects copied from another host look up to date to make
    with open(os.path.join(_NATIVE_DIR, ".build.lock"), "w") as lock:
        fcntl.flock(lock, fcntl.LOCK_EX)
        if not _needs_build(stamp):
            return
        proc = subprocess.run(["make", "-B", "-C", _NATIVE_DIR, "-j"],
                              capture_output=True, text=True)
        if proc.returncode != 0:
            raise RuntimeError(
                "[racon_tpu::native] build failed:\n" + proc.stderr)
        with open(_stamp_path(), "w") as f:
            f.write(stamp + "\n")


def get_library() -> ctypes.CDLL:
    global _lib
    if _lib is not None:
        return _lib
    with _lib_lock:
        if _lib is not None:
            return _lib
        _build_library()
        lib = ctypes.CDLL(_lib_path())
        lib.rt_edit_distance.restype = ctypes.c_int32
        lib.rt_edit_distance.argtypes = [
            ctypes.c_char_p, ctypes.c_int32,
            ctypes.c_char_p, ctypes.c_int32]
        lib.rt_align.restype = ctypes.c_int64
        lib.rt_align.argtypes = [
            ctypes.c_char_p, ctypes.c_int32,
            ctypes.c_char_p, ctypes.c_int32,
            ctypes.c_char_p, ctypes.c_int64,
            ctypes.POINTER(ctypes.c_int32)]
        lib.rt_poa_consensus.restype = ctypes.c_int64
        lib.rt_poa_consensus.argtypes = [
            ctypes.c_char_p,                        # seqs blob
            np.ctypeslib.ndpointer(np.int64),       # offsets
            ctypes.c_char_p,                        # quals blob
            np.ctypeslib.ndpointer(np.uint8),       # has_qual
            np.ctypeslib.ndpointer(np.int32),       # begins
            np.ctypeslib.ndpointer(np.int32),       # ends
            ctypes.c_int32,                         # n_seqs
            ctypes.c_int32,                         # window_type
            ctypes.c_int32,                         # trim
            ctypes.c_int32, ctypes.c_int32, ctypes.c_int32,  # m, x, g
            ctypes.c_char_p, ctypes.c_int64,        # out, out_cap
            ctypes.POINTER(ctypes.c_int32)]         # status
        _lib = lib
        return _lib


def edit_distance(query: bytes, target: bytes) -> int:
    """Global Levenshtein distance (edlib default-config equivalent)."""
    lib = get_library()
    return lib.rt_edit_distance(query, len(query), target, len(target))


def align(query: bytes, target: bytes) -> str:
    """Global alignment; returns a standard CIGAR (M covers mismatches)."""
    return align_with_distance(query, target)[0]


def align_with_distance(query: bytes, target: bytes):
    """Global alignment; returns (CIGAR, edit distance) -- the
    distance feeds the polisher's per-run divergence probe."""
    lib = get_library()
    cap = 4 * (len(query) + len(target)) + 16
    buf = ctypes.create_string_buffer(cap)
    dist = ctypes.c_int32(0)
    n = lib.rt_align(query, len(query), target, len(target), buf, cap,
                     ctypes.byref(dist))
    if n < 0:
        raise RuntimeError(
            f"[racon_tpu::align] native aligner failed (code {n}) on pair "
            f"({len(query)} x {len(target)})")
    return buf.raw[:n].decode(), int(dist.value)


class PoaEngine:
    """CPU POA consensus engine bound to one set of alignment scores.

    One engine is shared by all threads (the native call is reentrant),
    unlike the reference's per-thread spoa engines -- the prealloc
    rationale does not apply here.
    """

    def __init__(self, match: int = 3, mismatch: int = -5, gap: int = -4):
        self.match = match
        self.mismatch = mismatch
        self.gap = gap
        get_library()  # build/bind eagerly

    def consensus(self, window, trim: bool) -> bytes:
        sequences: List[bytes] = window.sequences
        qualities: List[Optional[bytes]] = window.qualities
        positions: List[Tuple[int, int]] = window.positions
        n = len(sequences)

        offsets = np.zeros(n + 1, dtype=np.int64)
        for i, s in enumerate(sequences):
            offsets[i + 1] = offsets[i] + len(s)
        seqs_blob = b"".join(sequences)
        quals_blob = b"".join(
            q if q is not None else b"\x00" * len(s)
            for s, q in zip(sequences, qualities))
        has_qual = np.array([1 if q is not None else 0 for q in qualities],
                            dtype=np.uint8)
        begins = np.array([p[0] for p in positions], dtype=np.int32)
        ends = np.array([p[1] for p in positions], dtype=np.int32)

        out_cap = 4 * len(sequences[0]) + 4096
        out = ctypes.create_string_buffer(out_cap)
        status = ctypes.c_int32(0)
        lib = get_library()
        length = lib.rt_poa_consensus(
            seqs_blob, offsets, quals_blob, has_qual, begins, ends,
            n, window.type.value, 1 if trim else 0,
            self.match, self.mismatch, self.gap,
            out, out_cap, ctypes.byref(status))
        if length < 0:
            raise RuntimeError(
                f"[racon_tpu::PoaEngine] consensus buffer overflow in "
                f"window {window.id}:{window.rank}")
        if status.value == 2:
            window.warn_chimeric()
        return out.raw[:length]
