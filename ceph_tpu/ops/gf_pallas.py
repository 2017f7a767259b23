"""Fused GF(2^w) matmul as a Pallas TPU kernel.

The XLA version (:func:`ceph_tpu.ops.gf_jax.make_gf_matmul_u32`) builds
an unrolled doubling/XOR graph and leaves fusion/tiling to the
compiler.  This kernel pins the whole computation into VMEM: each grid
step DMAs one [k, B] block of packed-u32 data on chip, walks the
doubling chains in registers, XOR-accumulates the m outputs, and
writes [m, B] back — data is read once and parity written once,
nothing else touches HBM.

Measured on a v5e-1 (dependency-chained methodology from bench.py,
RS(8,3) over 64 MiB): the block size is the lever —

    BLOCK=512   138 GB/s   (grid overhead dominates)
    BLOCK=4096  323 GB/s   vs the XLA kernel's 230 GB/s
    BLOCK=8192  324 GB/s
    BLOCK=16384 301 GB/s   (VMEM pressure)

so the fused kernel beats XLA's schedule by ~1.4x at the sweet spot.

Same contract as the XLA kernel: data [k, N4] uint32 -> parity
[m, N4] uint32, bit-identical bytes (tests pin them against the numpy
oracle and the XLA kernel).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .gf_jax import _PACK, _row_plans

BLOCK = 4096  # u32 lanes per grid step (x4 = 16 KiB per row)


def on_tpu() -> bool:
    """True when JAX's default backend is a TPU.  A backend that fails
    to start raises here: it is never read as "no TPU"."""
    return jax.default_backend() == "tpu"


def make_gf_matmul_pallas(matrix: np.ndarray, w: int = 8,
                          interpret: bool = False,
                          block: int | None = None):
    """Compile the fused kernel; returns fn(d32 [k, N4]) -> [m, N4].

    ``interpret=True`` runs the Pallas interpreter (CPU testing).
    N4 must be a multiple of ``block`` (default BLOCK) — callers fall
    back to the XLA kernel otherwise (the codec layer's batch sizes
    satisfy it).  bench.py passes block=8192 for its large shapes
    (measured ~4% over 4096 on a v5e); the codec default stays 4096 so
    smaller batches remain pallas-eligible.
    """
    from jax.experimental import pallas as pl

    BLOCK = block or globals()["BLOCK"]
    matrix = np.asarray(matrix)
    m, k = matrix.shape
    plans = _row_plans(matrix, w)
    mask_low, high_unit, poly = _PACK[w]
    shift = w - 1
    # per input row: which powers are needed, and by which outputs
    need: list[set[int]] = [set() for _ in range(k)]
    users: dict[tuple[int, int], list[int]] = {}
    for i, terms in enumerate(plans):
        for j, b in terms:
            need[j].add(b)
            users.setdefault((j, b), []).append(i)

    def kernel(d_ref, o_ref):
        accs = [None] * m
        for j in range(k):
            if not need[j]:
                continue
            cur = d_ref[j, :]
            maxb = max(need[j])
            for b in range(maxb + 1):
                if b in need[j]:
                    for i in users[(j, b)]:
                        accs[i] = cur if accs[i] is None else accs[i] ^ cur
                if b < maxb:
                    high = (cur >> shift) & high_unit
                    cur = ((cur & mask_low) << 1) ^ (high * poly)
        zero = jnp.zeros((BLOCK,), dtype=jnp.uint32)
        for i in range(m):
            o_ref[i, :] = zero if accs[i] is None else accs[i]

    def fn(d32: jax.Array) -> jax.Array:
        assert d32.shape[0] == k, (d32.shape, k)
        n4 = d32.shape[1]
        assert n4 % BLOCK == 0, (n4, BLOCK)
        grid = (n4 // BLOCK,)
        return pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=[pl.BlockSpec((k, BLOCK), lambda g: (0, g))],
            out_specs=pl.BlockSpec((m, BLOCK), lambda g: (0, g)),
            out_shape=jax.ShapeDtypeStruct((m, n4), jnp.uint32),
            interpret=interpret,
        )(d32)

    return fn


def make_bitmatrix_matmul_pallas(bitmatrix: np.ndarray,
                                 interpret: bool = False,
                                 block: int | None = None):
    """Fused whole-packet XOR kernel for the bit-matrix code family
    (cauchy/liberation/blaum_roth/liber8tion schedules, SHEC shingles —
    the TPU analog of jerasure_schedule_encode,
    reference:src/erasure-code/jerasure/ErasureCodeJerasure.cc:279).

    The XLA version (gf_jax.make_bitmatrix_matmul) re-reads each input
    packet row from HBM once per output that uses it (the [M, K] matrix
    averages ~50% density, so ~M/2 reads per row).  Here each grid step
    DMAs one [K, B] block into VMEM ONCE, XOR-accumulates all M outputs
    in registers, and writes [M, B] back — input traffic drops from
    O(density*M*K*B) to O(K*B), which is the whole game for a kernel
    with zero arithmetic intensity.

    Contract matches the XLA kernel on u32 lanes: packets [K, N4] uint32
    -> [M, N4] uint32, bit-identical bytes (pinned by tests against the
    numpy oracle and the XLA engine).
    """
    from jax.experimental import pallas as pl

    BLOCK = block or globals()["BLOCK"]
    bm = np.asarray(bitmatrix) != 0
    m, k = bm.shape

    def kernel(d_ref, o_ref):
        accs = [None] * m
        for j in range(k):  # each input row is read exactly once
            users = [i for i in range(m) if bm[i, j]]
            if not users:
                continue
            cur = d_ref[j, :]
            for i in users:
                accs[i] = cur if accs[i] is None else accs[i] ^ cur
        zero = jnp.zeros((BLOCK,), dtype=jnp.uint32)
        for i in range(m):
            o_ref[i, :] = zero if accs[i] is None else accs[i]

    def fn(p32: jax.Array) -> jax.Array:
        assert p32.shape[0] == k, (p32.shape, k)
        n4 = p32.shape[1]
        assert n4 % BLOCK == 0, (n4, BLOCK)
        grid = (n4 // BLOCK,)
        return pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=[pl.BlockSpec((k, BLOCK), lambda g: (0, g))],
            out_specs=pl.BlockSpec((m, BLOCK), lambda g: (0, g)),
            out_shape=jax.ShapeDtypeStruct((m, n4), jnp.uint32),
            interpret=interpret,
        )(p32)

    return fn


