"""ctypes seam to the native host library (native/challenge.cpp).

The build's native surface (SURVEY §2a: host-side native code in C++):
batched Ed25519 challenge-scalar computation for the verify host path,
and libcrypto's Ed25519 signing for a process's own vertices.
The library is compiled on demand with ``g++ -O2 -shared -fPIC`` into the
package's ``native/`` directory and loaded with ctypes — no pybind11 /
build-system dependency. The object's name carries a hash of the source
it was built from, so an object left over from an older challenge.cpp
(or copied around with a fresh mtime) is never loaded. A build or load
failure raises: running without the library is a choice
(``DAGRIDER_NATIVE=0``, the hashlib and pure-Python signing paths —
which stay the differential-testing oracles, tests/test_native.py), not
a fallback.
"""

from __future__ import annotations

import ctypes
import glob
import hashlib
import os
import subprocess
import threading
import weakref
from typing import Optional, Sequence

import numpy as np

_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "native")
_SRC = os.path.join(_DIR, "challenge.cpp")

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None


def _so_path() -> str:
    with open(_SRC, "rb") as fh:
        tag = hashlib.sha256(fh.read()).hexdigest()[:16]
    return os.path.join(_DIR, f"libdagrider_native.{tag}.so")


def _build(so: str) -> None:
    """Compile to a temp file, then atomically rename into place — two
    processes racing a cold cache must never load a half-written object.
    Objects of older sources are removed."""
    tmp = f"{so}.{os.getpid()}.tmp"
    try:
        proc = subprocess.run(
            ["g++", "-O2", "-shared", "-fPIC", "-o", tmp, _SRC],
            capture_output=True,
            timeout=120,
        )
        if proc.returncode != 0:
            raise RuntimeError(
                f"g++ failed on {_SRC}: "
                f"{proc.stderr.decode(errors='replace')[-2000:]}"
            )
        os.replace(tmp, so)
    finally:
        if os.path.exists(tmp):
            os.remove(tmp)
    for old in glob.glob(os.path.join(_DIR, "libdagrider_native*.so")):
        if old != so:
            try:
                os.remove(old)
            except OSError:
                pass  # another process's; not ours to insist on


def load() -> ctypes.CDLL:
    """The native library, built from the tracked source on first use.
    Raises when it cannot be built or loaded (no g++, a compile error)."""
    global _lib
    with _lock:
        if _lib is not None:
            return _lib
        so = _so_path()
        if not os.path.exists(so):
            _build(so)
        lib = ctypes.CDLL(so)
        u8p = ctypes.POINTER(ctypes.c_uint8)
        u64p = ctypes.POINTER(ctypes.c_uint64)
        lib.dagrider_challenge_batch.argtypes = [
            u8p, u8p, u8p, u64p, ctypes.c_uint64, u8p,
        ]
        lib.dagrider_challenge_batch.restype = None
        lib.dagrider_ed25519_key_new.argtypes = [ctypes.c_char_p]
        lib.dagrider_ed25519_key_new.restype = ctypes.c_void_p
        lib.dagrider_ed25519_sign.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_size_t, ctypes.c_char_p,
        ]
        lib.dagrider_ed25519_sign.restype = ctypes.c_int
        lib.dagrider_ed25519_key_free.argtypes = [ctypes.c_void_p]
        lib.dagrider_ed25519_key_free.restype = None
        _lib = lib
        return _lib


def _u8(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))


def challenge_batch(
    rs: np.ndarray, pks: np.ndarray, msgs: Sequence[bytes]
) -> np.ndarray:
    """k_i = SHA-512(R_i || A_i || M_i) mod L for the whole batch.

    rs/pks: uint8[n, 32]; msgs: n byte strings. Returns uint8[n, 32]
    little-endian scalars.

    Thread-safe and re-entrant: every buffer the C call reads or writes
    is allocated per call (the copies above this line are part of the
    contract, not an optimization), the library keeps no global state,
    and ctypes releases the GIL for the duration of the foreign call —
    the parallel host-prep engine (verifier/prep.py) relies on exactly
    this, invoking it concurrently from row-block worker threads so N
    blocks hash in genuinely parallel native code.
    """
    lib = load()
    n = len(msgs)
    if rs.shape != (n, 32) or pks.shape != (n, 32):
        raise ValueError("rs/pks must be uint8[n, 32]")
    rs = np.ascontiguousarray(rs, dtype=np.uint8)
    pks = np.ascontiguousarray(pks, dtype=np.uint8)
    blob = b"".join(msgs)
    offs = np.zeros(n + 1, dtype=np.uint64)
    np.cumsum([len(m) for m in msgs], out=offs[1:])
    data = np.frombuffer(blob, dtype=np.uint8) if blob else np.zeros(1, dtype=np.uint8)
    out = np.zeros((n, 32), dtype=np.uint8)
    lib.dagrider_challenge_batch(
        _u8(rs),
        _u8(pks),
        _u8(data),
        offs.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
        ctypes.c_uint64(n),
        _u8(out),
    )
    return out


class Ed25519Key:
    """libcrypto's Ed25519 signing key for one RFC 8032 seed, made once:
    making it derives the public key with a scalar multiply. Signatures
    are byte-identical to ``crypto.ed25519.sign`` (RFC 8032 signing is
    deterministic). The key is freed with this object."""

    def __init__(self, lib: ctypes.CDLL, handle: int):
        self._sign = lib.dagrider_ed25519_sign
        self._handle = handle
        weakref.finalize(self, lib.dagrider_ed25519_key_free, handle)

    @classmethod
    def make(cls, seed: bytes) -> Optional["Ed25519Key"]:
        """The key for a 32-byte seed, or None where libcrypto or its
        Ed25519 cannot be resolved. Builds the library on first use."""
        if len(seed) != 32:
            raise ValueError("seed must be 32 bytes")
        lib = load()
        handle = lib.dagrider_ed25519_key_new(seed)
        return cls(lib, handle) if handle else None

    def sign(self, message: bytes) -> bytes:
        out = ctypes.create_string_buffer(64)
        rc = self._sign(self._handle, message, len(message), out)
        if rc != 0:
            raise RuntimeError(f"libcrypto's Ed25519 signing failed ({rc})")
        return out.raw
