"""ctypes loader + wrapper for the native C++ engine (native/ec_cpu.cc).

Builds on first use (g++ -O3 -march=native) into native/build/, under a
name keyed on the source, the command and the CPU the compiler targets
(:func:`build_so`), so a copied tree never loads a binary built for
another host.  This is
the host-side codec used as the CPU baseline in bench.py and as an
independent oracle for the TPU kernels (both implement the same doubling
scheme, so parity bytes must agree exactly with each other and with the
numpy table-based oracle).
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
            _lib.gf8_encode_stripes.argtypes = [
                ctypes.POINTER(ctypes.c_int), ctypes.c_int, ctypes.c_int,
                ctypes.c_int64, ctypes.c_int64,
                ctypes.POINTER(ctypes.c_uint8), ctypes.POINTER(ctypes.c_uint8),
            ]
            _lib.gf8_encode_stripes_block.argtypes = [
                ctypes.POINTER(ctypes.c_int), ctypes.c_int, ctypes.c_int,
                ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
                ctypes.c_int64,
                ctypes.POINTER(ctypes.c_uint8), ctypes.POINTER(ctypes.c_uint8),
            ]
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
    """True when jax's default backend is the host CPU and this native
    GF engine is loadable — the ONE routing gate shared by the encode
    stack (osd/ec_util) and the codec decode path (models/matrix_codec).
    A backend that fails to start raises here; only a native library
    that cannot be built reads as "not active"."""
    global _HOST_ACTIVE
    if _HOST_ACTIVE is None:
        import jax

        active = jax.default_backend() == "cpu"
        if active:
            try:
                lib()
            except (OSError, subprocess.CalledProcessError):
                active = False  # no compiler here: the jax lane serves
        _HOST_ACTIVE = active
    return _HOST_ACTIVE


_stripe_pool = None  # lazy ThreadPoolExecutor for the parallel encode
_PAR_MIN_BYTES = 1 << 21  # below 2 MiB the fork/join overhead wins
_stripe_workers_default = 1  # set by calibrate_stripe_workers()


def stripe_workers() -> int:
    """Worker threads for the parallel stripe encode (ctypes releases
    the GIL around the C call, so blocks really run in parallel).
    CEPH_TPU_NATIVE_WORKERS overrides (1 disables); otherwise the
    calibrated default — 1 until :func:`calibrate_stripe_workers` has
    proven parallelism wins on THIS host (container-throttled or
    single-channel boxes go memory-bound and lose to the serial pass,
    measured: 2 workers = 0.85x on a 2-vCPU cgroup)."""
    import os

    env = os.environ.get("CEPH_TPU_NATIVE_WORKERS")
    if env:
        try:
            return max(1, int(env))
        except ValueError:
            pass
    return _stripe_workers_default


def calibrate_stripe_workers(budget_s: float = 1.0) -> dict:
    """Race the serial vs all-cores stripe encode on a synthetic RS(8,3)
    batch and lock the winner in as the process default (the ISA-L
    cpu-dispatch idea, done by measurement instead of cpuid).  Called by
    the bench stack child and available to daemons at boot; returns the
    verdict dict for logs/round JSON."""
    global _stripe_workers_default
    import os
    import time as _time

    ncpu = max(1, os.cpu_count() or 1)
    verdict = {"cpus": ncpu, "workers": stripe_workers(),
               "serial_gbps": None, "parallel_gbps": None}
    pinned = os.environ.get("CEPH_TPU_NATIVE_WORKERS")
    if pinned:
        # an explicit operator pin ALWAYS wins: measuring would both
        # be pointless and (worse) clobber the override mid-race for
        # any concurrent encode reading stripe_workers()
        verdict["pinned"] = pinned
        return verdict
    if ncpu == 1:
        return verdict
    matrix = rs_vandermonde_matrix(8, 3, 8)
    S, cs, k = 256, 2048, 8
    buf = np.arange(S * k * cs, dtype=np.uint32).astype(np.uint8)

    def rate(workers: int) -> float:
        # flip only the process default (no env mutation): a concurrent
        # encode may take either lane mid-calibration — both are
        # correct, and the final default is restored below either way
        global _stripe_workers_default
        _stripe_workers_default = workers
        try:
            encode_stripes(matrix, buf, S, cs)  # warm (pool spin-up)
            t0 = _time.perf_counter()
            n = 0
            while _time.perf_counter() - t0 < budget_s / 2:
                encode_stripes(matrix, buf, S, cs)
                n += 1
            return buf.size * n / (_time.perf_counter() - t0)
        finally:
            _stripe_workers_default = 1
    try:
        ser = rate(1)
        par = rate(ncpu)
    except Exception:
        return verdict
    verdict["serial_gbps"] = round(ser / 1e9, 3)
    verdict["parallel_gbps"] = round(par / 1e9, 3)
    if par > ser * 1.1:  # demand a real win before going parallel
        _stripe_workers_default = ncpu
        verdict["workers"] = ncpu
    return verdict


def encode_stripes(
    matrix: np.ndarray, buf: np.ndarray, S: int, cs: int
) -> np.ndarray:
    """Fused stripe-layout encode: ``buf`` is the client's [S*k*cs] byte
    stream; returns [k+m, S*cs] whose rows are the per-shard buffers
    (data rows laid out + parity), produced in ONE pass over the input
    (the codec stack's transpose and matmul fused — see
    native/ec_cpu.cc gf8_encode_stripes).

    Large batches split their stripe range across host cores: each
    worker runs the STRIDED C body (gf8_encode_stripes_block) over a
    disjoint stripe range of the one shared output, so the parallel
    pass writes the same bytes as the serial pass with zero extra
    allocation or copy — stripes are independent in the GF algebra."""
    L = lib()
    matrix = np.ascontiguousarray(matrix, dtype=np.int32)
    m, k = matrix.shape
    buf = np.ascontiguousarray(buf.reshape(-1))
    assert buf.size == S * k * cs and cs % 8 == 0
    out = np.empty((k + m, S * cs), dtype=np.uint8)
    mptr = matrix.ctypes.data_as(ctypes.POINTER(ctypes.c_int))
    workers = stripe_workers()
    if workers <= 1 or S < 2 * workers or buf.size < _PAR_MIN_BYTES:
        L.gf8_encode_stripes(mptr, k, m, S, cs, _u8ptr(buf), _u8ptr(out))
        return out
    global _stripe_pool
    if _stripe_pool is None:
        from concurrent.futures import ThreadPoolExecutor

        with _lock:
            if _stripe_pool is None:
                # sized to the HOST, not to the current worker setting:
                # the pool is created once and outlives calibration /
                # env changes, so a transient low setting must not
                # permanently undersize it
                import os as _os

                _stripe_pool = ThreadPoolExecutor(
                    max_workers=max(2, _os.cpu_count() or 2),
                    thread_name_prefix="gf-stripes",
                )
    shard_len = S * cs
    step = -(-S // workers)
    in_addr = buf.ctypes.data
    out_ptr = _u8ptr(out)

    def run_block(s0: int) -> None:
        nS = min(step, S - s0)
        in_ptr = ctypes.cast(
            in_addr + s0 * k * cs, ctypes.POINTER(ctypes.c_uint8)
        )
        L.gf8_encode_stripes_block(
            mptr, k, m, s0, nS, cs, shard_len, in_ptr, out_ptr
        )

    futs = [
        _stripe_pool.submit(run_block, s0) for s0 in range(0, S, step)
    ]
    for f in futs:
        f.result()  # propagate any worker failure
    return out


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
