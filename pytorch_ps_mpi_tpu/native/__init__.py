"""Native (C++) runtime components, bound via ctypes.

The reference's native surface lives in third-party C deps — c-blosc for the
byte pipeline (`/root/reference/mpi_comms.py:18-30`) and libmpi for transport.
Transport here is XLA's ICI/DCN collectives (in-compiler, no host library to
write), but the host-side byte pipeline — checkpoint serialization and any
consumer needing framed compressed buffers — is in-repo
C++: `src/ps_serial.cpp`, built lazily with g++ into ``_lib/`` and loaded with
ctypes (no pybind11 in this image; the C ABI + ctypes keeps the binding
zero-dependency).  Buffer pointers from numpy arrays pass straight through —
the zero-copy design `/root/reference/serialization.py` was reaching for.

The library file is named after a hash of the sources and the compile
command (`build_id`), so a binary built from another tree — ``_lib/`` is
git-ignored and travels with a copied directory — is never loaded: a
changed source byte is a new name, and a missing name is a build.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import tempfile

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRCS = [os.path.join(_DIR, "src", f)
         for f in ("ps_serial.cpp", "ps_loader.cpp")]
_LIBDIR = os.path.join(_DIR, "_lib")
_CXX = ["g++", "-O3", "-std=c++17", "-fPIC", "-shared", "-pthread"]

_lib_handle = None


def build_id() -> str:
    """12 hex digits over the source bytes and the compile command."""
    h = hashlib.sha256(" ".join(_CXX).encode())
    for src in _SRCS:
        with open(src, "rb") as f:
            h.update(b"\0" + os.path.basename(src).encode() + b"\0")
            h.update(f.read())
    return h.hexdigest()[:12]


def lib_path() -> str:
    return os.path.join(_LIBDIR, f"libps_native-{build_id()}.so")


def _build() -> str:
    """Compile the shared library for the current sources if it is not
    there yet (atomic rename so concurrent importers race safely)."""
    out = lib_path()
    if os.path.exists(out):
        return out
    os.makedirs(_LIBDIR, exist_ok=True)
    fd, tmp = tempfile.mkstemp(suffix=".so", dir=_LIBDIR)
    os.close(fd)
    cmd = [*_CXX, "-o", tmp, *_SRCS]
    from ..errors import NativeToolchainError
    try:
        subprocess.run(cmd, check=True, capture_output=True, text=True)
    except FileNotFoundError as e:
        os.unlink(tmp)
        raise NativeToolchainError(
            f"native build needs g++ on PATH: {' '.join(cmd)}") from e
    except subprocess.CalledProcessError as e:  # pragma: no cover
        os.unlink(tmp)
        raise NativeToolchainError(
            f"native build failed: {' '.join(cmd)}\n{e.stderr}") from e
    os.replace(tmp, out)
    return out


def lib() -> ctypes.CDLL:
    """The loaded native library (built on first use)."""
    global _lib_handle
    if _lib_handle is None:
        h = ctypes.CDLL(_build())
        h.ps_max_compressed.restype = ctypes.c_size_t
        h.ps_max_compressed.argtypes = [ctypes.c_size_t]
        for name in ("ps_lz_compress", "ps_lz_decompress"):
            fn = getattr(h, name)
            fn.restype = ctypes.c_longlong
            fn.argtypes = [ctypes.c_void_p, ctypes.c_size_t,
                           ctypes.c_void_p, ctypes.c_size_t]
        for name in ("ps_shuffle", "ps_unshuffle"):
            fn = getattr(h, name)
            fn.restype = None
            fn.argtypes = [ctypes.c_void_p, ctypes.c_void_p,
                           ctypes.c_size_t, ctypes.c_size_t]
        h.ps_crc32.restype = ctypes.c_uint32
        h.ps_crc32.argtypes = [ctypes.c_uint32, ctypes.c_void_p,
                               ctypes.c_size_t]
        h.ps_tree_decode.restype = ctypes.c_longlong
        h.ps_tree_decode.argtypes = [
            ctypes.c_void_p, ctypes.c_size_t, ctypes.c_void_p,
            ctypes.c_void_p, ctypes.c_size_t, ctypes.c_void_p,
            ctypes.c_size_t, ctypes.c_int,
            ctypes.POINTER(ctypes.c_longlong)]
        h.ps_tree_encode.restype = ctypes.c_longlong
        h.ps_tree_encode.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
            ctypes.c_size_t, ctypes.c_int, ctypes.c_void_p, ctypes.c_size_t,
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int,
            ctypes.POINTER(ctypes.c_longlong)]
        h.ps_gather_rows.restype = None
        h.ps_gather_rows.argtypes = [ctypes.c_void_p, ctypes.c_void_p,
                                     ctypes.c_size_t, ctypes.c_size_t,
                                     ctypes.c_void_p, ctypes.c_int]
        _lib_handle = h
    return _lib_handle
