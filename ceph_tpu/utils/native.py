"""ctypes loader + wrapper for the native C++ engine (native/ec_cpu.cc).

Builds on first use (g++ -O3 -march=native) into native/build/, under a
name keyed on the source, the command and the CPU the compiler targets
(:func:`build_so`), so a copied tree never loads a binary built for
another host.  This is
the host-side codec used as the CPU baseline in bench.py, as the engine
of the failover replay, and as an independent oracle for the TPU kernels
(both implement the same doubling scheme, so parity bytes must agree
exactly with each other and with the numpy table-based oracle).
"""

from __future__ import annotations

import ctypes
import functools
import hashlib
import os
import pathlib
import subprocess
import threading

import numpy as np

_ROOT = pathlib.Path(__file__).resolve().parent.parent.parent
_SRC = _ROOT / "native" / "ec_cpu.cc"
_BUILD = _ROOT / "native" / "build"

_lock = threading.Lock()
_lib = None


@functools.lru_cache(maxsize=None)
def _compiler_target(flags: tuple[str, ...]) -> str:
    """The compiler's own expansion of ``flags``: ``-march=native``
    becomes this host's CPU and its ISA extensions there."""
    r = subprocess.run(
        ["g++", *flags, "-E", "-v", "-x", "c++", os.devnull,
         "-o", os.devnull],
        capture_output=True, text=True, check=True,
    )
    return "\n".join(ln for ln in r.stderr.splitlines() if "cc1plus" in ln)


def build_so(stem: str, sources: list[pathlib.Path],
             cmd: list[str]) -> pathlib.Path:
    """Compile ``cmd`` into ``native/build/<stem>-<key>.so`` unless that
    file exists.  The key hashes the sources, the command and the
    compiler's expansion of it, never a file time."""
    h = hashlib.sha256()
    for src in sources:
        h.update(src.read_bytes())
    h.update("\0".join(cmd).encode())
    h.update(_compiler_target(tuple(c for c in cmd if c.startswith("-m")))
             .encode())
    so = _BUILD / f"{stem}-{h.hexdigest()[:16]}.so"
    if so.exists():
        return so
    _BUILD.mkdir(parents=True, exist_ok=True)
    tmp = so.with_suffix(f".{os.getpid()}.tmp")
    subprocess.run([*cmd, "-o", str(tmp)], check=True, capture_output=True)
    os.replace(tmp, so)  # atomic: a concurrent builder loads a whole file
    return so


def build() -> pathlib.Path:
    """Compile the native library if needed; returns the .so path."""
    from .arch import host_march_flags

    return build_so("libec_cpu", [_SRC], [
        "g++", "-O3", *host_march_flags(), "-funroll-loops", "-shared",
        "-fPIC", "-std=c++17", str(_SRC),
    ])


def lib() -> ctypes.CDLL:
    global _lib
    with _lock:
        if _lib is None:
            so = build()
            _lib = ctypes.CDLL(str(so))
            _lib.gf8_encode_flat.argtypes = [
                ctypes.POINTER(ctypes.c_int), ctypes.c_int, ctypes.c_int,
                ctypes.POINTER(ctypes.c_uint8), ctypes.POINTER(ctypes.c_uint8),
                ctypes.c_int64,
            ]
            _lib.gf16_encode_flat.argtypes = _lib.gf8_encode_flat.argtypes
            _lib.gf8_mul_region.argtypes = [
                ctypes.c_uint8, ctypes.POINTER(ctypes.c_uint8),
                ctypes.POINTER(ctypes.c_uint8), ctypes.c_int64,
            ]
            _lib.xor_region.argtypes = [
                ctypes.POINTER(ctypes.c_uint8), ctypes.POINTER(ctypes.c_uint8),
                ctypes.POINTER(ctypes.c_uint8), ctypes.c_int64,
            ]
            _lib.crc32c_sw.argtypes = [
                ctypes.c_uint32, ctypes.POINTER(ctypes.c_uint8), ctypes.c_int64,
            ]
            _lib.crc32c_sw.restype = ctypes.c_uint32
            _lib.crc32c_table.argtypes = _lib.crc32c_sw.argtypes
            _lib.crc32c_table.restype = ctypes.c_uint32
            for fn in (_lib.rs_vandermonde_matrix, _lib.cauchy_original_matrix):
                fn.argtypes = [
                    ctypes.c_int, ctypes.c_int, ctypes.c_int,
                    ctypes.POINTER(ctypes.c_int32),
                ]
                fn.restype = ctypes.c_int
        return _lib


def _u8ptr(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))


def encode(matrix: np.ndarray, data: np.ndarray, w: int = 8) -> np.ndarray:
    """Native single-thread GF matmul: data [k, n] uint8 -> parity [m, n]."""
    L = lib()
    matrix = np.ascontiguousarray(matrix, dtype=np.int32)
    data = np.ascontiguousarray(data, dtype=np.uint8)
    m, k = matrix.shape
    assert data.shape[0] == k and data.shape[1] % 8 == 0
    parity = np.empty((m, data.shape[1]), dtype=np.uint8)
    fn = L.gf8_encode_flat if w == 8 else L.gf16_encode_flat
    fn(
        matrix.ctypes.data_as(ctypes.POINTER(ctypes.c_int)), k, m,
        _u8ptr(data), _u8ptr(parity), data.shape[1],
    )
    return parity


_HOST_ACTIVE: bool | None = None


def host_engine_active() -> bool:
    """True when this native library builds and loads, cached in
    ``_HOST_ACTIVE``.  It gates one thing: whether the codec's host
    oracle (``MatrixErasureCode._host_matmul``, the failover replay's
    engine) runs the native matmul or the numpy one; every EC op of
    the served path goes through the dispatcher's lanes whatever the
    backend.  The name is kept because the benchmark's served-path
    fixture reads and sets ``_HOST_ACTIVE``."""
    global _HOST_ACTIVE
    if _HOST_ACTIVE is None:
        try:
            lib()
            _HOST_ACTIVE = True
        except (OSError, subprocess.CalledProcessError):
            _HOST_ACTIVE = False  # no compiler here: numpy serves
    return _HOST_ACTIVE


def crc32c(crc: int, data: bytes | np.ndarray) -> int:
    """crc32c (Castagnoli) with ceph_crc32c semantics: seed used raw, no
    pre/post inversion, so crcs compose across appends."""
    from .buffers import as_u8

    buf = as_u8(data)
    if buf.size == 0:
        return crc & 0xFFFFFFFF
    return int(lib().crc32c_sw(crc & 0xFFFFFFFF, _u8ptr(buf), buf.size))


# lean per-frame crc entry (msg/message.py hot path): the generic
# crc32c above pays ~10us of pure call scaffolding per invocation on a
# slow interpreter — as_u8 conversion, the lib() lock, and numpy's
# .ctypes pointer build — which dwarfs the actual crc of a sub-KiB
# header.  This binding passes c_void_p, so bytes go pointer-direct
# and writable buffers resolve via a zero-length from_buffer cast.
_crc_raw = None
_U8_0 = ctypes.c_uint8 * 0


def _crc_fn():
    global _crc_raw
    if _crc_raw is None:  # benign race: both winners bind the same fn
        L = lib()
        _crc_raw = ctypes.CFUNCTYPE(
            ctypes.c_uint32, ctypes.c_uint32, ctypes.c_void_p,
            ctypes.c_int64,
        )(("crc32c_sw", L))
    return _crc_raw


def crc32c_view(crc: int, buf, n: int | None = None) -> int:
    """crc32c over any bytes-like without conversion scaffolding:
    ``bytes`` pass their pointer directly, writable buffers
    (bytearray / slab memoryview) via ``from_buffer``, read-only
    views through a numpy pointer.  ``n`` overrides the length (crc a
    strict prefix of ``buf`` without slicing it — the decode path's
    body-minus-trailer case).  Bit-identical to :func:`crc32c`."""
    fn = _crc_fn()
    crc &= 0xFFFFFFFF
    if type(buf) is bytes:
        ln = len(buf) if n is None else n
        return fn(crc, buf, ln) if ln else crc
    mv = buf if isinstance(buf, memoryview) else memoryview(buf)
    if mv.ndim != 1 or mv.itemsize != 1:
        mv = mv.cast("B")
    ln = mv.nbytes if n is None else n
    if not ln:
        return crc
    try:
        base = _U8_0.from_buffer(mv)
        return fn(crc, ctypes.addressof(base), ln)
    except TypeError:  # read-only view: numpy exposes the pointer
        a = np.frombuffer(mv, np.uint8)
        return fn(crc, a.__array_interface__["data"][0], ln)


_crc_chunks_raw = None


def crc32c_chunks(crc: int, data: np.ndarray, chunk: int) -> np.ndarray:
    """crc32c of each ``chunk``-byte slice of ``data``, every one seeded
    with ``crc``, in ONE native call: ``out[i] == crc32c(crc,
    data[i * chunk:(i + 1) * chunk])``, as uint32.  A stripe-hash table
    row costs one call per shard rather than one per chunk, each of
    which would pay :func:`crc32c`'s conversion scaffolding."""
    global _crc_chunks_raw
    if _crc_chunks_raw is None:  # benign race, as in _crc_fn
        _crc_chunks_raw = ctypes.CFUNCTYPE(
            None, ctypes.c_uint32, ctypes.c_void_p, ctypes.c_int64,
            ctypes.c_int64, ctypes.c_void_p,
        )(("crc32c_chunks", lib()))
    buf = np.ascontiguousarray(data, dtype=np.uint8).reshape(-1)
    if chunk <= 0 or buf.size % chunk:
        raise ValueError(f"buffer {buf.size} not a multiple of chunk {chunk}")
    out = np.empty(buf.size // chunk, dtype=np.uint32)
    if out.size:
        _crc_chunks_raw(crc & 0xFFFFFFFF, buf.ctypes.data, buf.size, chunk,
                        out.ctypes.data)
    return out


def rs_vandermonde_matrix(k: int, m: int, w: int) -> np.ndarray:
    """Independently-coded systematic RS-Vandermonde oracle (see
    native/ec_cpu.cc): cross-checks the python construction."""
    out = np.zeros((m, k), dtype=np.int32)
    rc = lib().rs_vandermonde_matrix(
        k, m, w, out.ctypes.data_as(ctypes.POINTER(ctypes.c_int32))
    )
    if rc != 0:
        raise ValueError(f"rs_vandermonde_matrix({k},{m},{w}) rc={rc}")
    return out.astype(np.int64)


def cauchy_original_matrix(k: int, m: int, w: int) -> np.ndarray:
    """Independently-coded Cauchy-original oracle (native/ec_cpu.cc)."""
    out = np.zeros((m, k), dtype=np.int32)
    rc = lib().cauchy_original_matrix(
        k, m, w, out.ctypes.data_as(ctypes.POINTER(ctypes.c_int32))
    )
    if rc != 0:
        raise ValueError(f"cauchy_original_matrix({k},{m},{w}) rc={rc}")
    return out.astype(np.int64)


def mul_region(c: int, src: np.ndarray) -> np.ndarray:
    L = lib()
    src = np.ascontiguousarray(src, dtype=np.uint8)
    dst = np.empty_like(src)
    L.gf8_mul_region(c, _u8ptr(src), _u8ptr(dst), src.size)
    return dst


def xor_region(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    L = lib()
    a = np.ascontiguousarray(a, dtype=np.uint8)
    b = np.ascontiguousarray(b, dtype=np.uint8)
    dst = np.empty_like(a)
    L.xor_region(_u8ptr(a), _u8ptr(b), _u8ptr(dst), a.size)
    return dst
