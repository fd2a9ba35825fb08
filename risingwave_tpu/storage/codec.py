"""ctypes bindings for the native storage codec (+ numpy fallback).

The C++ library (native/rwtpu_codec.cpp) implements the hot host-side
loops: memcomparable scalar encoding, varint block encode/decode,
crc32c.  A pure-numpy fallback keeps the storage layer functional
without a toolchain — a failed build or load is logged once with its
reason and ``native_available()`` says false.

How the library is built, named and checked.  It is built on first use
with ``g++ -O3 -shared -fPIC`` — no ``-march``, no ISA flag: a library
built on one x86-64 host must run on any other, and the crc32c loop is
picked at load from what the running CPU reports
(``crc32c_impl()``: ``"hw"`` the CPU's instruction, ``"slice8"`` the
portable table loop, ``"python"`` no library at all).  It is cached
beside the source as ``librwtpu_codec-<hash>.so``, the hash being of
the source's CONTENT: a library left by another source, however new
its mtime, is never opened (``native/*.so`` is git-ignored, so a copied
working tree carries whatever was built last).  Whatever is opened
answers a known-answer check of every function's symbol and of
``rw_crc32c`` before anyone uses it; one that fails is rebuilt once,
then given up for the fallback.  The fallback's crc32c is a table loop
in Python, ~10 MB/s: it refuses more than ``PY_CRC32C_MAX`` bytes with
``NativeCodecRequired`` — a server must not silently spend minutes a
checkpoint.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import sys
import threading

import numpy as np

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)
)))
_SRC = os.path.join(_REPO_ROOT, "native", "rwtpu_codec.cpp")

#: crc32c known answers (Castagnoli, reflected; RFC 3720 B.4)
CRC32C_KNOWN = (
    (b"123456789", 0xE3069283),
    (bytes(32), 0x8A9136AA),
    (b"\xff" * 32, 0x62A8AB43),
)
#: the most the Python crc32c takes on: a couple of seconds' worth
PY_CRC32C_MAX = 16 << 20

_lock = threading.Lock()
_lib = None
_native_failed = False


class NativeCodecRequired(RuntimeError):
    """The native library is missing and the input is too large for
    the Python fallback to finish in seconds."""


def _so_path(src: str, lib_dir: str) -> str:
    with open(src, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    return os.path.join(lib_dir, f"librwtpu_codec-{digest}.so")


def _build(src: str, so: str) -> None:
    # several roles may start on one checkout: build under a name of
    # this process's own and rename into place, so nobody ever loads a
    # half-written file
    tmp = f"{so}.{os.getpid()}.tmp"
    try:
        subprocess.run(["g++", "-O3", "-shared", "-fPIC", src, "-o", tmp],
                       check=True, capture_output=True)
        os.replace(tmp, so)
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)


def _bind(lib) -> None:
    """Declare every function (a missing symbol raises here) and hold
    ``rw_crc32c`` to its known answers."""
    u8p = ctypes.POINTER(ctypes.c_uint8)
    i64p = ctypes.POINTER(ctypes.c_int64)
    f64p = ctypes.POINTER(ctypes.c_double)
    lib.mc_encode_i64.argtypes = [i64p, ctypes.c_int64, u8p]
    lib.mc_decode_i64.argtypes = [u8p, ctypes.c_int64, i64p]
    lib.mc_encode_f64.argtypes = [f64p, ctypes.c_int64, u8p]
    lib.mc_decode_f64.argtypes = [u8p, ctypes.c_int64, f64p]
    lib.block_encode.argtypes = [u8p, i64p, u8p, i64p,
                                 ctypes.c_int64, u8p, ctypes.c_int64]
    lib.block_encode.restype = ctypes.c_int64
    lib.block_scan.argtypes = [u8p, ctypes.c_int64, i64p, i64p, i64p]
    lib.block_scan.restype = ctypes.c_int64
    lib.block_decode.argtypes = [u8p, ctypes.c_int64, u8p, i64p,
                                 u8p, i64p]
    lib.block_decode.restype = ctypes.c_int64
    lib.rw_crc32c.argtypes = [u8p, ctypes.c_int64]
    lib.rw_crc32c.restype = ctypes.c_uint32
    lib.rw_crc32c_impl.argtypes = []
    lib.rw_crc32c_impl.restype = ctypes.c_char_p
    lib.rw_crc32c_with.argtypes = [ctypes.c_char_p, u8p, ctypes.c_int64,
                                   ctypes.POINTER(ctypes.c_int32)]
    lib.rw_crc32c_with.restype = ctypes.c_uint32
    for data, want in CRC32C_KNOWN:
        arr = np.frombuffer(data, np.uint8)
        got = int(lib.rw_crc32c(_u8(arr), len(arr)))
        if got != want:
            raise RuntimeError(
                f"rw_crc32c({data[:9]!r}..) = {got:#x}, not {want:#x}")


def open_library(src: str, lib_dir: str):
    """The library of ``src``, from ``lib_dir`` if the one there is of
    this source and answers right, else built there (once: a library
    that still fails after its rebuild raises)."""
    import _ctypes

    so = _so_path(src, lib_dir)
    for rebuilt in (False, True):
        if rebuilt or not os.path.exists(so):
            _build(src, so)
        lib = None
        try:
            lib = ctypes.CDLL(so)
            _bind(lib)
            return lib
        except (OSError, AttributeError, RuntimeError):
            if rebuilt:
                raise
            if lib is not None:
                # or the loader hands the same mapping back for the
                # rebuilt file of the same name
                _ctypes.dlclose(lib._handle)


def _load():
    global _lib, _native_failed
    if _lib is not None or _native_failed:
        return _lib
    with _lock:
        if _lib is not None or _native_failed:
            return _lib
        try:
            _lib = open_library(_SRC, os.path.dirname(_SRC))
        except Exception as e:
            _native_failed = True
            reason = getattr(e, "stderr", None) or e
            if isinstance(reason, bytes):
                reason = reason.decode(errors="replace")
            print(f"native codec unavailable, using numpy: {reason}",
                  file=sys.stderr, flush=True)
    return _lib


def native_available() -> bool:
    return _load() is not None


def crc32c_impl() -> str:
    """Which loop ``crc32c`` runs in this process: ``"hw"`` (the CPU's
    crc32c instruction), ``"slice8"`` (the library's table loop) or
    ``"python"`` (no library)."""
    lib = _load()
    return lib.rw_crc32c_impl().decode() if lib is not None else "python"


def _u8(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))


def _i64(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_int64))


def _f64(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_double))


# ---------------------------------------------------------------------------
# memcomparable encoding


def mc_encode_i64(vals: np.ndarray) -> np.ndarray:
    vals = np.ascontiguousarray(vals, np.int64)
    lib = _load()
    out = np.empty(len(vals) * 8, np.uint8)
    if lib is not None:
        lib.mc_encode_i64(_i64(vals), len(vals), _u8(out))
        return out.reshape(len(vals), 8)
    u = (vals.view(np.uint64) ^ np.uint64(1 << 63)).byteswap()
    return u.view(np.uint8).reshape(len(vals), 8)


def mc_decode_i64(data: np.ndarray) -> np.ndarray:
    data = np.ascontiguousarray(data, np.uint8).reshape(-1, 8)
    lib = _load()
    out = np.empty(len(data), np.int64)
    if lib is not None:
        lib.mc_decode_i64(_u8(data), len(data), _i64(out))
        return out
    u = data.reshape(-1).view(np.uint64).byteswap()
    return (u ^ np.uint64(1 << 63)).view(np.int64)


def mc_encode_f64(vals: np.ndarray) -> np.ndarray:
    vals = np.ascontiguousarray(vals, np.float64)
    lib = _load()
    out = np.empty(len(vals) * 8, np.uint8)
    if lib is not None:
        lib.mc_encode_f64(_f64(vals), len(vals), _u8(out))
        return out.reshape(len(vals), 8)
    u = vals.view(np.uint64)
    u = np.where(u >> np.uint64(63), ~u, u | np.uint64(1 << 63))
    return u.byteswap().view(np.uint8).reshape(len(vals), 8)


def mc_decode_f64(data: np.ndarray) -> np.ndarray:
    data = np.ascontiguousarray(data, np.uint8).reshape(-1, 8)
    lib = _load()
    out = np.empty(len(data), np.float64)
    if lib is not None:
        lib.mc_decode_f64(_u8(data), len(data), _f64(out))
        return out
    u = data.reshape(-1).view(np.uint64).byteswap()
    u = np.where(u >> np.uint64(63), u & np.uint64(0x7FFFFFFFFFFFFFFF), ~u)
    return u.view(np.float64)


# ---------------------------------------------------------------------------
# block codec


def block_encode(keys: np.ndarray, key_offsets: np.ndarray,
                 vals: np.ndarray, val_offsets: np.ndarray) -> bytes:
    """Encode n records given flat byte pools + (n+1) offset arrays."""
    n = len(key_offsets) - 1
    keys = np.ascontiguousarray(keys, np.uint8)
    vals = np.ascontiguousarray(vals, np.uint8)
    key_offsets = np.ascontiguousarray(key_offsets, np.int64)
    val_offsets = np.ascontiguousarray(val_offsets, np.int64)
    lib = _load()
    if lib is not None:
        cap = int(keys.size + vals.size + 20 * n + 64)
        out = np.empty(cap, np.uint8)
        w = lib.block_encode(_u8(keys), _i64(key_offsets), _u8(vals),
                             _i64(val_offsets), n, _u8(out), cap)
        if w < 0:
            raise RuntimeError("block_encode overflow")
        return out[:w].tobytes()
    # fallback
    import io
    buf = io.BytesIO()
    for i in range(n):
        k = keys[key_offsets[i]:key_offsets[i + 1]].tobytes()
        v = vals[val_offsets[i]:val_offsets[i + 1]].tobytes()
        buf.write(_varint(len(k)))
        buf.write(k)
        buf.write(_varint(len(v)))
        buf.write(v)
    return buf.getvalue()


def block_decode(data: bytes):
    """Decode a block → (keys, key_offsets, vals, val_offsets)."""
    arr = np.frombuffer(data, np.uint8)
    lib = _load()
    if lib is not None:
        n = np.zeros(1, np.int64)
        kb = np.zeros(1, np.int64)
        vb = np.zeros(1, np.int64)
        rc = lib.block_scan(_u8(arr), len(arr), _i64(n), _i64(kb), _i64(vb))
        if rc < 0:
            raise ValueError("corrupt block")
        keys = np.empty(int(kb[0]), np.uint8)
        vals = np.empty(int(vb[0]), np.uint8)
        ko = np.empty(int(n[0]) + 1, np.int64)
        vo = np.empty(int(n[0]) + 1, np.int64)
        got = lib.block_decode(_u8(arr), len(arr), _u8(keys), _i64(ko),
                               _u8(vals), _i64(vo))
        if got != n[0]:
            raise ValueError("corrupt block")
        return keys, ko, vals, vo
    # fallback
    keys_l, vals_l = [], []
    i = 0
    while i < len(data):
        klen, i = _read_varint(data, i)
        keys_l.append(data[i:i + klen]); i += klen
        vlen, i = _read_varint(data, i)
        vals_l.append(data[i:i + vlen]); i += vlen
    ko = np.cumsum([0] + [len(k) for k in keys_l]).astype(np.int64)
    vo = np.cumsum([0] + [len(v) for v in vals_l]).astype(np.int64)
    keys = np.frombuffer(b"".join(keys_l), np.uint8)
    vals = np.frombuffer(b"".join(vals_l), np.uint8)
    return keys, ko, vals, vo


def crc32c(data) -> int:
    """crc32c of any contiguous buffer of bytes."""
    lib = _load()
    arr = np.frombuffer(data, np.uint8)
    if lib is not None:
        return int(lib.rw_crc32c(_u8(arr), len(arr)))
    return crc32c_py(arr)


def crc32c_with(impl: str, data) -> int | None:
    """crc32c by one named loop of the library (``"hw"``, ``"slice8"``,
    ``"bytewise"``: the definition, a table lookup a byte); None where
    this CPU or this build lacks it."""
    lib = _load()
    if lib is None:
        return None
    arr = np.frombuffer(data, np.uint8)
    ok = ctypes.c_int32()
    got = lib.rw_crc32c_with(impl.encode(), _u8(arr), len(arr),
                             ctypes.byref(ok))
    return int(got) if ok.value else None


_PY_CRC_TABLE: list[int] = []


def crc32c_py(data) -> int:
    """The fallback: a table lookup a byte, in Python (~10 MB/s)."""
    data = bytes(data)
    if len(data) > PY_CRC32C_MAX:
        raise NativeCodecRequired(
            f"crc32c of {len(data)} bytes without the native codec "
            f"(the Python loop takes on {PY_CRC32C_MAX}): the library "
            f"did not build or load, see this process's stderr")
    if not _PY_CRC_TABLE:
        table = []
        for c in range(256):
            for _ in range(8):
                c = (0x82F63B78 ^ (c >> 1)) if c & 1 else (c >> 1)
            table.append(c)
        _PY_CRC_TABLE[:] = table
    table = _PY_CRC_TABLE
    c = 0xFFFFFFFF
    for b in data:
        c = table[(c ^ b) & 0xFF] ^ (c >> 8)
    return c ^ 0xFFFFFFFF


def _varint(v: int) -> bytes:
    out = bytearray()
    while v >= 0x80:
        out.append((v & 0x7F) | 0x80)
        v >>= 7
    out.append(v)
    return bytes(out)


def _read_varint(data: bytes, i: int):
    x = 0
    shift = 0
    while True:
        b = data[i]
        i += 1
        x |= (b & 0x7F) << shift
        if not (b & 0x80):
            return x, i
        shift += 7
