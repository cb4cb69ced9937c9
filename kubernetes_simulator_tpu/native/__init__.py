"""Native runtime layer — ctypes bindings to the C++ host-side components
(``native/*.cpp``): gang-aware wave packing and columnar trace IO.

The shared library is built lazily with ``g++ -O3`` into
``native/_build/`` the first time it is needed and cached by source mtime.
Every entry point has a pure-Python fallback (the original implementations)
so the framework still runs where no toolchain exists; parity between the
two is pinned by tests/test_native.py.
"""

from __future__ import annotations

import ctypes
import logging
import os
import subprocess
from pathlib import Path
from typing import Optional

import numpy as np

log = logging.getLogger(__name__)

_REPO = Path(__file__).resolve().parent.parent.parent
_SRC = _REPO / "native"
_BUILD = _SRC / "_build"
_LIB: Optional[ctypes.CDLL] = None
_TRIED = False

_SOURCES = ("wavepack.cpp", "traceio.cpp", "borg2019.cpp")


def _build_lib() -> Optional[Path]:
    so = _BUILD / "libksim.so"
    srcs = [_SRC / s for s in _SOURCES]
    if not all(s.exists() for s in srcs):
        return None
    if so.exists() and so.stat().st_mtime >= max(s.stat().st_mtime for s in srcs):
        return so
    _BUILD.mkdir(parents=True, exist_ok=True)
    # Compile to a process-private path and os.replace into place, so a
    # concurrent process never dlopens a partially written .so.
    tmp = _BUILD / f"libksim.{os.getpid()}.so"
    cmd = ["g++", "-O3", "-shared", "-fPIC", "-std=c++17", "-o", str(tmp)] + [
        str(s) for s in srcs
    ]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        os.replace(tmp, so)
    except (OSError, subprocess.SubprocessError) as e:
        tmp.unlink(missing_ok=True)
        # Loud: the Python packers are minutes slower at 1M pods, so a
        # drop to them must show up in the log with the compiler's words.
        stderr = getattr(e, "stderr", None) or b""
        log.warning(
            "native build failed, using the pure-Python fallbacks: %r\n%s",
            e, stderr.decode(errors="replace"),
        )
        return None
    return so


def _lib() -> Optional[ctypes.CDLL]:
    global _LIB, _TRIED
    if _LIB is None and not _TRIED:
        _TRIED = True
        if os.environ.get("KSIM_NO_NATIVE"):
            return None
        so = _build_lib()
        if so is not None:
            try:
                lib = ctypes.CDLL(str(so))
            except OSError as e:
                log.warning("native library %s did not load: %r", so, e)
                return None
            lib.ksim_pack_waves.restype = ctypes.c_int64
            lib.ksim_pack_waves.argtypes = [
                ctypes.POINTER(ctypes.c_int32), ctypes.c_int64,
                ctypes.POINTER(ctypes.c_int32), ctypes.c_int64,
                ctypes.c_int32, ctypes.POINTER(ctypes.c_int32),
            ]
            lib.ksim_trace_count.restype = ctypes.c_int64
            lib.ksim_trace_count.argtypes = [ctypes.c_char_p]
            lib.ksim_trace_parse.restype = ctypes.c_int64
            lib.ksim_trace_parse.argtypes = [
                ctypes.c_char_p, ctypes.c_int64,
                ctypes.POINTER(ctypes.c_double), ctypes.POINTER(ctypes.c_float),
                ctypes.POINTER(ctypes.c_float), ctypes.POINTER(ctypes.c_int32),
                ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int64),
                ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_float),
            ]
            lib.ksim_trace_write.restype = ctypes.c_int64
            lib.ksim_trace_write.argtypes = [
                ctypes.c_char_p, ctypes.c_int64,
                ctypes.POINTER(ctypes.c_double), ctypes.POINTER(ctypes.c_float),
                ctypes.POINTER(ctypes.c_float), ctypes.POINTER(ctypes.c_int32),
                ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int64),
                ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_float),
            ]
            lib.ksim_borg2019_count.restype = ctypes.c_int64
            lib.ksim_borg2019_count.argtypes = [ctypes.c_char_p]
            lib.ksim_borg2019_parse.restype = ctypes.c_int64
            lib.ksim_borg2019_parse.argtypes = [
                ctypes.c_char_p, ctypes.c_int64,
                ctypes.POINTER(ctypes.c_double), ctypes.POINTER(ctypes.c_int32),
                ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int64),
                ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_int64),
                ctypes.POINTER(ctypes.c_float), ctypes.POINTER(ctypes.c_float),
            ]
            _LIB = lib
    return _LIB


def available() -> bool:
    return _lib() is not None


def _i32p(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_int32))


def _i64p(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_int64))


def pack_waves_native(
    order: np.ndarray, group_of: np.ndarray, wave_width: int
) -> Optional[np.ndarray]:
    """[num_waves, W] i32 wave table (PAD=-1), or None if the native lib is
    unavailable. A gang wider than the wave fills consecutive waves from a
    wave's first slot (same contract as the Python packer)."""
    lib = _lib()
    if lib is None:
        return None
    order = np.ascontiguousarray(order, dtype=np.int32)
    group_of = np.ascontiguousarray(group_of, dtype=np.int32)
    n = order.shape[0]
    out = np.empty((max(n, 1), wave_width), dtype=np.int32)
    waves = lib.ksim_pack_waves(
        _i32p(order), n, _i32p(group_of), group_of.shape[0], wave_width, _i32p(out)
    )
    if waves < 0:
        raise ValueError(f"wave width must be positive, got {wave_width}")
    return out[:waves].copy()


def read_trace_csv(path: str | os.PathLike) -> Optional[dict]:
    """Columnar task-event trace → dict of numpy arrays, or None if the
    native lib is unavailable (callers fall back to numpy loadtxt)."""
    lib = _lib()
    if lib is None:
        return None
    p = str(path).encode()
    n = lib.ksim_trace_count(p)
    if n < 0:
        raise FileNotFoundError(path)
    cols = {
        "arrival": np.empty(n, np.float64),
        "cpu": np.empty(n, np.float32),
        "mem": np.empty(n, np.float32),
        "priority": np.empty(n, np.int32),
        "group_id": np.empty(n, np.int64),  # real Borg collection ids > 2^31
        "app_id": np.empty(n, np.int64),
        "tolerates": np.empty(n, np.int32),
        "duration": np.empty(n, np.float32),
    }
    got = lib.ksim_trace_parse(
        p, n,
        cols["arrival"].ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
        cols["cpu"].ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        cols["mem"].ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        _i32p(cols["priority"]), _i64p(cols["group_id"]), _i64p(cols["app_id"]),
        _i32p(cols["tolerates"]),
        cols["duration"].ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
    )
    if got < 0:
        raise ValueError(f"malformed trace file: {path}")
    return {k: v[:got] for k, v in cols.items()}


def read_borg2019_events(path: str | os.PathLike) -> Optional[dict]:
    """Borg-2019 schema CSV (instance_events / collection_events) → raw
    per-event columnar arrays (time_us, etype, cid, iidx, prio, alloc,
    cpu, mem), or None when the native lib is unavailable OR the file
    needs the tolerant csv.DictReader fallback (quoted fields, missing
    required columns). Sentinels: prio/alloc −1 = field absent."""
    lib = _lib()
    if lib is None:
        return None
    if not os.path.exists(path):
        raise FileNotFoundError(path)
    # Streaming newline count (an upper bound on data rows — blanks and
    # the header over-allocate slightly; parse() returns the real count).
    # Avoids the C side slurping the whole file twice at the
    # billions-of-rows scale this exists for.
    n = 0
    with open(path, "rb") as f:
        while True:
            buf = f.read(1 << 24)
            if not buf:
                break
            n += buf.count(b"\n")
    n += 1  # file may lack a trailing newline
    p = str(path).encode()
    cols = {
        "time_us": np.empty(n, np.float64),
        "etype": np.empty(n, np.int32),
        "cid": np.empty(n, np.int64),
        "iidx": np.empty(n, np.int64),
        "prio": np.empty(n, np.int32),
        "alloc": np.empty(n, np.int64),
        "cpu": np.empty(n, np.float32),
        "mem": np.empty(n, np.float32),
    }
    got = lib.ksim_borg2019_parse(
        p, n,
        cols["time_us"].ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
        _i32p(cols["etype"]), _i64p(cols["cid"]), _i64p(cols["iidx"]),
        _i32p(cols["prio"]), _i64p(cols["alloc"]),
        cols["cpu"].ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        cols["mem"].ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
    )
    if got < 0:
        return None  # unsupported shape → csv.DictReader fallback
    return {k: v[:got] for k, v in cols.items()}


def write_trace_csv(path: str | os.PathLike, cols: dict) -> bool:
    """Write a columnar trace; False if the native lib is unavailable."""
    lib = _lib()
    if lib is None:
        return False
    n = len(cols["arrival"])
    arrs = {
        "arrival": np.ascontiguousarray(cols["arrival"], np.float64),
        "cpu": np.ascontiguousarray(cols["cpu"], np.float32),
        "mem": np.ascontiguousarray(cols["mem"], np.float32),
        "priority": np.ascontiguousarray(cols["priority"], np.int32),
        "group_id": np.ascontiguousarray(cols["group_id"], np.int64),
        "app_id": np.ascontiguousarray(cols["app_id"], np.int64),
        "tolerates": np.ascontiguousarray(cols["tolerates"], np.int32),
        "duration": np.ascontiguousarray(cols["duration"], np.float32),
    }
    got = lib.ksim_trace_write(
        str(path).encode(), n,
        arrs["arrival"].ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
        arrs["cpu"].ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        arrs["mem"].ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        _i32p(arrs["priority"]), _i64p(arrs["group_id"]), _i64p(arrs["app_id"]),
        _i32p(arrs["tolerates"]),
        arrs["duration"].ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
    )
    if got != n:
        raise IOError(f"short trace write to {path}: {got}/{n}")
    return True
