"""ctypes loader for the native runtime library (native/resp.cpp).

Builds ``native/build/librtpu-<digest>.so`` on first use with g++ (the image
has no pybind11; the C ABI + ctypes is the binding layer — see repo
guidelines).  The artifact is keyed by the CONTENT of resp.cpp: the library a
process loads is always built from the source next to it, whatever a copied
tree did to mtimes, and nothing built is checked in.  Every entry point
degrades to pure Python if the toolchain or library is unavailable, so the
framework never hard-requires the native path — ``build_status()`` says
which of the two happened.
"""
from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from typing import Optional

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
_NATIVE_DIR = os.path.join(_REPO_ROOT, "native")
_SRC_PATH = os.path.join(_NATIVE_DIR, "resp.cpp")

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
# "untried" | "disabled" (RTPU_NO_NATIVE) | "no_source" | "loaded" (artifact
# for this source already on disk) | "built" | "build_failed" | "load_failed"
_status = "untried"


class RtpuToken(ctypes.Structure):
    _fields_ = [
        ("type", ctypes.c_int32),
        ("flags", ctypes.c_int32),
        ("val", ctypes.c_int64),
        ("off", ctypes.c_uint64),
    ]


def so_path() -> Optional[str]:
    """Artifact path for the resp.cpp on disk, or None without a source."""
    try:
        with open(_SRC_PATH, "rb") as fh:
            digest = hashlib.sha256(fh.read()).hexdigest()[:16]
    except OSError:
        return None
    return os.path.join(_NATIVE_DIR, "build", f"librtpu-{digest}.so")


def _build(dst: str) -> bool:
    """Compile resp.cpp to `dst`, atomically: concurrent first users (N
    spawned servers) each build to a private name and rename into place."""
    os.makedirs(os.path.dirname(dst), exist_ok=True)
    tmp = f"{dst}.{os.getpid()}"
    try:
        subprocess.run(
            ["g++", "-O3", "-fPIC", "-shared", "-std=c++17", "-o", tmp, _SRC_PATH],
            check=True,
            capture_output=True,
            timeout=120,
        )
        os.replace(tmp, dst)
        return True
    except (OSError, subprocess.SubprocessError):
        return False
    finally:
        if os.path.exists(tmp):
            os.remove(tmp)


def build_status() -> str:
    """How the last ``load()`` resolved the library (see ``_status``)."""
    return _status


def _bind(lib: ctypes.CDLL) -> ctypes.CDLL:
    """Declare every entry point; raises AttributeError on a library built
    from an older resp.cpp (missing symbols)."""
    lib.rtpu_resp_scan.restype = ctypes.c_int64
    lib.rtpu_resp_scan.argtypes = [
        ctypes.POINTER(ctypes.c_char),
        ctypes.c_uint64,
        ctypes.POINTER(RtpuToken),
        ctypes.c_uint64,
        ctypes.POINTER(ctypes.c_uint64),
        ctypes.POINTER(ctypes.c_uint64),
    ]
    lib.rtpu_encode_reply.restype = ctypes.c_int64
    lib.rtpu_encode_reply.argtypes = [
        ctypes.c_void_p,  # int32* ops (op | marker<<8)
        ctypes.c_void_p,  # int64* vals
        ctypes.c_void_p,  # int64* offs
        ctypes.c_uint64,
        ctypes.c_void_p,  # byte pool
        ctypes.c_void_p,  # output arena
        ctypes.c_uint64,
    ]
    lib.rtpu_lz4_compress.restype = ctypes.c_int64
    lib.rtpu_lz4_compress.argtypes = [
        ctypes.c_char_p,
        ctypes.c_uint64,
        ctypes.POINTER(ctypes.c_char),
        ctypes.c_uint64,
    ]
    lib.rtpu_lz4_decompress.restype = ctypes.c_int64
    lib.rtpu_lz4_decompress.argtypes = [
        ctypes.c_char_p,
        ctypes.c_uint64,
        ctypes.POINTER(ctypes.c_char),
        ctypes.c_uint64,
        ctypes.POINTER(ctypes.c_uint64),
    ]
    lib.rtpu_crc16.restype = ctypes.c_uint16
    lib.rtpu_crc16.argtypes = [ctypes.c_char_p, ctypes.c_uint64]
    lib.rtpu_calc_slots.restype = None
    lib.rtpu_calc_slots.argtypes = [
        ctypes.c_char_p,
        ctypes.POINTER(ctypes.c_uint64),
        ctypes.POINTER(ctypes.c_uint64),
        ctypes.c_uint64,
        ctypes.POINTER(ctypes.c_uint16),
    ]
    return lib


def load() -> Optional[ctypes.CDLL]:
    """The native library, or None if unavailable (pure-Python fallback).
    Resolved once per process; ``build_status()`` says how."""
    global _lib, _status
    if _status != "untried":
        return _lib
    with _lock:
        if _status == "untried":
            _lib, _status = _resolve()
        return _lib


def _resolve():
    if os.environ.get("RTPU_NO_NATIVE"):
        return None, "disabled"
    path = so_path()
    if path is None:
        return None, "no_source"
    status = "loaded"
    if not os.path.exists(path):
        if not _build(path):
            return None, "build_failed"
        status = "built"
    try:
        return _bind(ctypes.CDLL(path)), status
    except (OSError, AttributeError):
        return None, "load_failed"
