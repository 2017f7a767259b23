"""Accelerator/host capability probe (reference:src/arch/).

The reference probes CPUID once at startup (``ceph_arch_intel_sse42``,
``_avx2``, ... in reference:src/arch/intel.c, probe.cc) and SIMD
libraries (gf-complete, ISA-L, crc32c) dispatch on the flags.  The
TPU-native analog probes the XLA backend once: which platform JAX
compiles for, the device generation, and whether x64 is available —
and the GF kernel layer dispatches on the result the same way.

Host-side native builds ask :func:`host_march_flags` instead of
hardcoding ``-march=native`` (mirrors the reference's per-arch
compile-unit split, reference:src/erasure-code/jerasure/CMakeLists.txt).
"""

from __future__ import annotations

import dataclasses
import functools
import os
import pathlib
import platform as _host_platform
import subprocess

_REPO = pathlib.Path(__file__).resolve().parents[2]


@dataclasses.dataclass(frozen=True)
class ArchProbe:
    """Result of the one-time backend probe (``ceph_arch_probe`` analog)."""

    platform: str          # "tpu" | "cpu" | "gpu" — XLA compile target
    device_kind: str       # e.g. "TPU v5 lite", "cpu"
    num_devices: int
    has_mxu: bool          # systolic matmul unit (TPU) — prefers u32 lanes
    host_machine: str      # uname -m for the native C++ side

    @property
    def preferred_gf_kernel(self) -> str:
        """Which GF(2^w) engine family to jit by default: the u32
        packed-lane doubling kernels win on every backend measured so
        far (8 bytes/lane VPU ops, no gathers — gathers serialize on
        TPU; on CPU XLA vectorizes the same ops).  Bitmatrix scheduling
        stays a per-technique override at the codec layer (cauchy/
        liberation packetized codes), not a platform decision."""
        return "u32_doubling"


@functools.lru_cache(maxsize=None)
def probe() -> ArchProbe:
    """Probe once, like ``ceph_arch_probe()`` (reference:src/arch/probe.cc).

    Import of jax is deferred so pure-host tools (crushtool on maps,
    config handling) never pay for backend init.
    """
    import jax

    # a backend that fails to start raises: it is never reported as a
    # CPU host, so the caller sees the fault instead of a host number
    devices = jax.devices()
    plat = devices[0].platform
    return ArchProbe(
        platform=plat,
        device_kind=devices[0].device_kind,
        num_devices=len(devices),
        has_mxu=plat == "tpu",
        host_machine=_host_platform.machine(),
    )


def configure_compile_cache() -> str:
    """Place JAX's persistent compilation cache; returns its directory.

    Called by the entry points that hold a device (chip_smoke.py,
    bench.py's device process, the ``accel`` daemon role).  Where
    ``JAX_COMPILATION_CACHE_DIR`` is set JAX already reads it and
    nothing is set here; otherwise the cache is the fixed
    ``<repo>/.jax_cache`` (a fixed path, so a later run finds it)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = str(_REPO / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


@functools.lru_cache(maxsize=None)
def host_march_flags() -> list[str]:
    """Compiler flags for the native engine; falls back past
    unsupported -march values (old cross toolchains)."""
    for flags in (["-march=native"], ["-mcpu=native"], []):
        try:
            r = subprocess.run(
                ["g++", *flags, "-E", "-x", "c++", "-", "-o", "/dev/null"],
                input="", capture_output=True, text=True, timeout=30,
            )
        except (OSError, subprocess.TimeoutExpired):
            continue
        if r.returncode == 0:
            return flags
    return []


def dump() -> dict:
    p = probe()
    return dataclasses.asdict(p) | {
        "preferred_gf_kernel": p.preferred_gf_kernel,
        "host_march_flags": host_march_flags(),
    }
