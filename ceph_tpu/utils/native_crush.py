"""ctypes loader for the native C straw2 mapper (native/crush_cpu.cc).

The compiled-C single-thread placement cost is the honest baseline for
the TPU bulk-sim benchmark (VERDICT r3 Weak #3: comparing the device
path only to the *Python* scalar oracle flattered it by ~300x).  The
fixed-point ln tables are generated into the build dir from
ceph_tpu/crush/ln_tables.py so the C engine and every other backend
share one source of truth.
"""

from __future__ import annotations

import ctypes
import os
import pathlib
import threading
import time

import numpy as np

_ROOT = pathlib.Path(__file__).resolve().parent.parent.parent
_SRC = _ROOT / "native" / "crush_cpu.cc"
_BUILD = _ROOT / "native" / "build"
_INC = _BUILD / "crush_ln_tables.inc"

_lock = threading.Lock()
_lib = None


def _write_tables() -> None:
    from ceph_tpu.crush.ln_tables import LL_TBL, RH_LH_TBL

    def fmt(name: str, vals) -> str:
        body = ",\n  ".join(
            ", ".join(f"0x{v:013x}ULL" for v in vals[i : i + 4])
            for i in range(0, len(vals), 4)
        )
        return (
            f"static const uint64_t {name}[{len(vals)}] = {{\n  {body}\n}};\n"
        )

    text = (
        "// GENERATED from ceph_tpu/crush/ln_tables.py — do not edit\n"
        + fmt("RH_LH_TBL", RH_LH_TBL)
        + fmt("LL_TBL", LL_TBL)
    )
    if _INC.exists() and _INC.read_text() == text:
        return
    _BUILD.mkdir(parents=True, exist_ok=True)
    tmp = _INC.with_suffix(f".{os.getpid()}.tmp")
    tmp.write_text(text)
    os.replace(tmp, _INC)


def build() -> pathlib.Path:
    from .arch import host_march_flags
    from .native import build_so

    _write_tables()
    return build_so("libcrush_cpu", [_SRC, _INC], [
        "g++", "-O3", *host_march_flags(), "-funroll-loops", "-shared",
        "-fPIC", "-std=c++17", f"-I{_BUILD}", str(_SRC),
    ])


def lib() -> ctypes.CDLL:
    global _lib
    with _lock:
        if _lib is None:
            so = build()
            _lib = ctypes.CDLL(str(so))
            _lib.crush_flat_firstn.argtypes = [
                ctypes.POINTER(ctypes.c_int32),   # items
                ctypes.POINTER(ctypes.c_uint32),  # item_weights
                ctypes.c_int,                     # n_items
                ctypes.c_int32,                   # bucket_id
                ctypes.POINTER(ctypes.c_uint32),  # weight
                ctypes.c_int,                     # n_weight
                ctypes.c_int,                     # max_devices
                ctypes.c_int,                     # numrep
                ctypes.c_int,                     # tries
                ctypes.POINTER(ctypes.c_uint32),  # xs
                ctypes.c_int64,                   # n_x
                ctypes.POINTER(ctypes.c_int32),   # out
            ]
            _lib.crush_flat_firstn.restype = None
        return _lib


def map_flat(cmap, ruleno: int, xs: np.ndarray, numrep: int,
             weight=None) -> np.ndarray:
    """Run the C mapper over ``xs``; returns [n_x, numrep] int32."""
    from ceph_tpu.crush.map import CRUSH_RULE_CHOOSE_FIRSTN, CRUSH_RULE_TAKE
    from ceph_tpu.crush.mapper_jax import _supports_flat

    if not _supports_flat(cmap, ruleno):
        raise ValueError("native C mapper covers the flat straw2 shape only")
    rule = cmap.rules[ruleno]
    take = next(s.arg1 for s in rule.steps if s.op == CRUSH_RULE_TAKE)
    firstn = any(s.op == CRUSH_RULE_CHOOSE_FIRSTN for s in rule.steps)
    if not firstn:
        raise ValueError("native C mapper implements firstn only")
    bucket = cmap.buckets[take]
    if weight is None:
        weight = cmap.get_weights()
    items = np.asarray(bucket.items, dtype=np.int32)
    iw = np.asarray(bucket.item_weights, dtype=np.uint32)
    wv = np.asarray(weight, dtype=np.uint32)
    xs = np.ascontiguousarray(xs, dtype=np.uint32)
    out = np.empty((len(xs), numrep), dtype=np.int32)
    tries = cmap.tunables.choose_total_tries + 1
    lib().crush_flat_firstn(
        items.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        iw.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)),
        len(items), np.int32(bucket.id),
        wv.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)),
        len(wv), cmap.max_devices, numrep, tries,
        xs.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)),
        len(xs),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
    )
    return out


def bench_flat(cmap, ruleno: int, numrep: int, n_x: int) -> float:
    """Seconds per mapping of the C engine; verifies a sample against
    the Python scalar oracle first (bit-exactness gate)."""
    from ceph_tpu.crush import mapper

    xs = np.arange(n_x, dtype=np.uint32)
    sample = np.linspace(0, n_x - 1, 64, dtype=np.uint32)
    rows = map_flat(cmap, ruleno, sample, numrep)
    for i, x in enumerate(sample):
        ref = mapper.crush_do_rule(cmap, ruleno, int(x), numrep)
        got = [v for v in rows[i] if v != -1]
        assert got == ref, (int(x), got, ref)
    t0 = time.perf_counter()
    map_flat(cmap, ruleno, xs, numrep)
    return (time.perf_counter() - t0) / n_x
