"""ctypes loader for the C++ native library, with on-demand g++ build.

Mirrors the reference's backend-by-availability seam (cuDNN helpers are
looked up reflectively and absent classes fall through to the built-in path,
`ConvolutionLayer.java:69-79`): if the shared library can be built/loaded,
hot host paths use it; otherwise every caller gets `None` and runs its
pure-Python fallback.
"""
from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import subprocess
import threading
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

logger = logging.getLogger("deeplearning4j_tpu")

_SRC = Path(__file__).parent / "src" / "dl4jtpu_native.cpp"

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_tried = False


def _so_path() -> Path:
    """The shared library's path, NAMED BY ITS SOURCE'S CONTENT: the
    binary is git-ignored and tools copy working trees between machines,
    so a file's presence (or its mtime) proves nothing about what it was
    built from — a digest in the name does, and a stale or foreign
    binary is simply never found."""
    digest = hashlib.sha1(_SRC.read_bytes()).hexdigest()[:12]
    return Path(__file__).parent / f"_dl4jtpu_native.{digest}.so"


def _build(so: Path) -> bool:
    tmp = so.with_suffix(f".{os.getpid()}.tmp")  # concurrent builders
    cmd = ["g++", "-O3", "-std=c++17", "-shared", "-fPIC",
           "-o", str(tmp), str(_SRC)]
    try:
        proc = subprocess.run(cmd, capture_output=True, text=True, timeout=120)
    except (OSError, subprocess.TimeoutExpired) as e:
        logger.info("native build unavailable (%s); using Python fallbacks", e)
        return False
    if proc.returncode != 0:
        logger.warning("native build failed; using Python fallbacks:\n%s",
                       proc.stderr[-2000:])
        return False
    os.replace(tmp, so)
    return True


def native_lib() -> Optional[ctypes.CDLL]:
    """The loaded library, building it on first call; None if unavailable."""
    global _lib, _tried
    with _lock:
        if _tried:
            return _lib
        _tried = True
        so = _so_path()
        if not so.exists() and not _build(so):
            return None
        try:
            lib = ctypes.CDLL(str(so))
        except OSError as e:
            logger.warning("native library load failed (%s)", e)
            return None
        lib.dl4j_csv_parse.restype = ctypes.c_void_p
        lib.dl4j_csv_parse.argtypes = [ctypes.c_char_p, ctypes.c_int, ctypes.c_char]
        lib.dl4j_csv_ok.argtypes = [ctypes.c_void_p]
        lib.dl4j_csv_rows.restype = ctypes.c_int64
        lib.dl4j_csv_rows.argtypes = [ctypes.c_void_p]
        lib.dl4j_csv_cols.restype = ctypes.c_int64
        lib.dl4j_csv_cols.argtypes = [ctypes.c_void_p]
        lib.dl4j_csv_data.restype = ctypes.POINTER(ctypes.c_double)
        lib.dl4j_csv_data.argtypes = [ctypes.c_void_p]
        lib.dl4j_csv_free.argtypes = [ctypes.c_void_p]
        lib.dl4j_wc_create.restype = ctypes.c_void_p
        lib.dl4j_wc_add_file.argtypes = [ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int]
        lib.dl4j_wc_total.restype = ctypes.c_int64
        lib.dl4j_wc_total.argtypes = [ctypes.c_void_p]
        lib.dl4j_wc_unique.restype = ctypes.c_int64
        lib.dl4j_wc_unique.argtypes = [ctypes.c_void_p]
        lib.dl4j_wc_serialize.restype = ctypes.c_int64
        lib.dl4j_wc_serialize.argtypes = [ctypes.c_void_p,
                                          ctypes.POINTER(ctypes.c_char_p)]
        lib.dl4j_buf_free.argtypes = [ctypes.c_char_p]
        lib.dl4j_wc_free.argtypes = [ctypes.c_void_p]
        _lib = lib
        return _lib


def native_available() -> bool:
    return native_lib() is not None


def csv_parse_numeric(path, skip_lines: int = 0,
                      delimiter: str = ",") -> Optional[np.ndarray]:
    """Parse an all-numeric rectangular CSV into an (N, C) float64 array via
    the native parser. Returns None when the library is unavailable OR the
    file has string/ragged content — callers then run the Python path."""
    lib = native_lib()
    if lib is None or len(delimiter) != 1:
        return None
    h = lib.dl4j_csv_parse(str(path).encode(), int(skip_lines),
                           delimiter.encode())
    try:
        if not lib.dl4j_csv_ok(h):
            return None
        rows, cols = lib.dl4j_csv_rows(h), lib.dl4j_csv_cols(h)
        if rows == 0:
            return np.zeros((0, 0), np.float64)
        out = np.ctypeslib.as_array(lib.dl4j_csv_data(h),
                                    shape=(rows, cols)).copy()
        return out
    finally:
        lib.dl4j_csv_free(h)


def count_words(paths: List, lowercase: bool = True) -> Optional[Dict[str, int]]:
    """Count whitespace-separated tokens across text files via the native
    counter (vocab-construction hot loop). None if unavailable.

    Case folding happens HERE, over unique words only — the C tokenizer is
    byte-oriented and its tolower would be ASCII-only, which would diverge
    from the Python fallback's str.lower() on non-ASCII corpora."""
    lib = native_lib()
    if lib is None:
        return None
    h = lib.dl4j_wc_create()
    try:
        for p in paths:
            if not lib.dl4j_wc_add_file(h, str(p).encode(), 0):
                return None  # IO error: let caller fall back / raise its way
        buf = ctypes.c_char_p()
        n = lib.dl4j_wc_serialize(h, ctypes.byref(buf))
        if n < 0:
            return None
        try:
            raw = ctypes.string_at(buf, n)
        finally:
            lib.dl4j_buf_free(buf)
        counts: Dict[str, int] = {}
        # records are "word\tcount\n": split on \n ONLY — tokens may contain
        # other chars str.splitlines() treats as line breaks (\x1c, U+2028)
        for line in raw.decode("utf-8", errors="replace").split("\n"):
            if not line:
                continue
            word, _, c = line.rpartition("\t")
            if lowercase:
                word = word.lower()
            counts[word] = counts.get(word, 0) + int(c)
        return counts
    finally:
        lib.dl4j_wc_free(h)
