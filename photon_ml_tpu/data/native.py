"""Native (C++) host-ingestion bindings via ctypes.

The reference's ingestion hot loops run on Spark executors (JVM); here
they are host-side, so the text-parsing inner loop lives in
native/fast_parse.cpp behind a C ABI (the environment has no pybind11 —
ctypes is the binding layer). The library is built from the committed
``native/*.cpp`` on first use into the git-ignored ``.so``. Every caller
keeps a pure-Python path with the same semantics, ~60x slower on Avro
decode — so losing the library is logged as a WARNING, never in silence.
"""

from __future__ import annotations

import ctypes
import logging
import os
import subprocess
from typing import Optional

import numpy as np

logger = logging.getLogger("photon_ml_tpu.native")

_NATIVE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    "native",
)
_LIB_PATH = os.path.join(_NATIVE_DIR, "libphoton_native.so")
_lib: Optional[ctypes.CDLL] = None
_load_attempted = False


_SOURCES = ("fast_parse.cpp", "avro_decode.cpp", "avro_encode.cpp")


def _source_paths() -> list[str]:
    return [
        p
        for p in (os.path.join(_NATIVE_DIR, s) for s in _SOURCES)
        if os.path.exists(p)
    ]


def _build() -> bool:
    srcs = _source_paths()
    attempts = [srcs] if srcs else []
    if len(srcs) > 1:
        # avro_decode.cpp needs zlib; if that link fails (no libz on the
        # host), still build fast_parse alone so the libsvm accelerator
        # survives
        attempts.append(srcs[:1])
    # build beside the target and rename: a concurrent first use in
    # another process must never load a half-written library
    tmp = f"{_LIB_PATH}.{os.getpid()}.tmp"
    for attempt in attempts:
        cmd = ["g++", "-O3", "-fPIC", "-shared", "-std=c++17", "-pthread",
               "-o", tmp, *attempt]
        if any("avro_decode" in s for s in attempt):
            cmd.append("-lz")
        try:
            subprocess.run(cmd, check=True, capture_output=True, timeout=240)
            os.replace(tmp, _LIB_PATH)
            return True
        except (OSError, subprocess.SubprocessError) as e:
            logger.warning(
                "native build failed for %s (%s: %s)",
                [os.path.basename(a) for a in attempt], e,
                (getattr(e, "stderr", b"") or b"").decode(errors="replace")[-400:],
            )
        finally:
            if os.path.exists(tmp):
                os.remove(tmp)
    logger.warning(
        "native library unavailable: ingest falls back to the pure-Python "
        "decoders (~60x slower on Avro)"
    )
    return False


def load_native() -> Optional[ctypes.CDLL]:
    """The native library, building it on first use; None if unavailable.

    ``PHOTON_NO_NATIVE=1`` hides the library even when it exists — the
    supported way to force (and test) the pure-Python fallback paths;
    checked before the load cache so toggling the env var mid-process
    (e.g. a monkeypatch) takes effect immediately.
    """
    global _lib, _load_attempted
    if os.environ.get("PHOTON_NO_NATIVE"):
        return None
    if _lib is not None or _load_attempted:
        return _lib
    _load_attempted = True
    srcs = _source_paths()
    stale = os.path.exists(_LIB_PATH) and any(
        os.path.getmtime(s) > os.path.getmtime(_LIB_PATH) for s in srcs
    )
    if (not os.path.exists(_LIB_PATH) or stale) and not _build():
        if not os.path.exists(_LIB_PATH):
            return None  # nothing to load; stale-but-present still loads
    try:
        lib = ctypes.CDLL(_LIB_PATH)
    except OSError as e:
        logger.warning(
            "native library load failed (%s): ingest falls back to the "
            "pure-Python decoders (~60x slower on Avro)", e
        )
        return None
    lib.libsvm_count.restype = ctypes.c_int
    lib.libsvm_count.argtypes = [
        ctypes.c_char_p,
        ctypes.c_int64,
        ctypes.POINTER(ctypes.c_int64),
        ctypes.POINTER(ctypes.c_int64),
    ]
    lib.libsvm_parse.restype = ctypes.c_int64
    lib.libsvm_parse.argtypes = [
        ctypes.c_char_p,
        ctypes.c_int64,
        ctypes.c_int,
        np.ctypeslib.ndpointer(np.float64, flags="C_CONTIGUOUS"),
        np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS"),
        np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS"),
        np.ctypeslib.ndpointer(np.float64, flags="C_CONTIGUOUS"),
        ctypes.POINTER(ctypes.c_int64),
        ctypes.POINTER(ctypes.c_int64),
    ]
    _lib = lib
    return _lib


def parse_libsvm_native(
    data: bytes, zero_based: bool = False
) -> Optional[tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray, int]]:
    """(values, rows, cols, labels, num_features) or None if the native
    library is unavailable. Raises ValueError on malformed input, matching
    the python parser's errors."""
    lib = load_native()
    if lib is None:
        return None
    n_rows = ctypes.c_int64()
    n_nnz = ctypes.c_int64()
    lib.libsvm_count(data, len(data), ctypes.byref(n_rows), ctypes.byref(n_nnz))
    values = np.empty(n_nnz.value, np.float64)
    rows = np.empty(n_nnz.value, np.int64)
    cols = np.empty(n_nnz.value, np.int64)
    labels = np.empty(n_rows.value, np.float64)
    parsed_rows = ctypes.c_int64()
    parsed_slots = ctypes.c_int64()
    max_col = lib.libsvm_parse(
        data, len(data), 0 if zero_based else 1, values, rows, cols, labels,
        ctypes.byref(parsed_rows), ctypes.byref(parsed_slots),
    )
    if max_col == -3:
        raise ValueError(
            "negative feature index (wrong zero_based setting?)"
        )
    if max_col == -2:
        raise ValueError("malformed libsvm token")
    # max_col == -1 is a VALID labels-only file: num_features = 0
    # the two passes must tokenize identically, or the arrays contain
    # uninitialized tails — refuse rather than return garbage
    if parsed_rows.value != n_rows.value or parsed_slots.value != n_nnz.value:
        raise ValueError(
            "malformed libsvm input: count/parse passes disagree "
            f"(rows {parsed_rows.value} vs {n_rows.value}, "
            f"nnz {parsed_slots.value} vs {n_nnz.value})"
        )
    return values, rows, cols, labels, int(max_col) + 1
