"""TPU kernel vs numpy-oracle equivalence for the GF region kernels.

This is the "CPU vs TPU parity bytes" non-regression contract
(SURVEY.md §4 porting lesson f) at the kernel level.
"""

import numpy as np
import pytest

from ceph_tpu.ops import matrices as mx
from ceph_tpu.ops.gf import gf
from ceph_tpu.ops.gf_jax import (
    gf_matmul,
    make_bitmatrix_matmul,
    make_gf_matmul,
)

RNG = np.random.default_rng(99)


@pytest.mark.parametrize(
    "k,m,maker",
    [
        (2, 1, lambda k, m: mx.rs_vandermonde(k, m, 8)),
        (3, 2, lambda k, m: mx.rs_vandermonde(k, m, 8)),
        (8, 3, lambda k, m: mx.rs_vandermonde(k, m, 8)),
        (10, 4, lambda k, m: mx.cauchy_good(k, m, 8)),
        (8, 3, lambda k, m: mx.isa_cauchy(k, m)),
    ],
)
def test_matmul_matches_numpy(k, m, maker):
    G = gf(8)
    M = maker(k, m)
    data = RNG.integers(0, 256, size=(k, 512)).astype(np.uint8)
    want = G.matmul_region(M, data)
    got = np.asarray(gf_matmul(M, data))
    assert np.array_equal(got, want)


def test_random_matrices_match():
    G = gf(8)
    for _ in range(5):
        k = int(RNG.integers(2, 11))
        m = int(RNG.integers(1, 5))
        M = RNG.integers(0, 256, size=(m, k))
        data = RNG.integers(0, 256, size=(k, 256)).astype(np.uint8)
        want = G.matmul_region(M, data)
        fn = make_gf_matmul(M, 8)
        got = np.asarray(fn(data))
        assert np.array_equal(got, want)


def test_all_ones_row_is_xor_parity():
    """m=1 all-ones (RAID-5, ISA-L's region_xor case) needs no kernel of
    its own: the GF matmul's plan for it is the plain XOR of the rows."""
    data = RNG.integers(0, 256, size=(5, 1024)).astype(np.uint8)
    fn = make_gf_matmul(np.ones((1, 5), dtype=np.int64), 8)
    got = np.asarray(fn(data))
    want = data[0].copy()
    for j in range(1, 5):
        want ^= data[j]
    assert np.array_equal(got[0], want)


def test_bitmatrix_matmul():
    G = gf(8)
    k, m, w = 4, 2, 8
    M = mx.cauchy_good(k, m, w)
    B = G.matrix_to_bitmatrix(M)  # [m*w, k*w]
    # packets: each chunk contributes w packets of P bytes
    P = 64
    packets = RNG.integers(0, 256, size=(k * w, P)).astype(np.uint8)
    fn = make_bitmatrix_matmul(B)
    got = np.asarray(fn(packets))
    want = np.zeros((m * w, P), dtype=np.uint8)
    for i in range(m * w):
        for j in range(k * w):
            if B[i, j]:
                want[i] ^= packets[j]
    assert np.array_equal(got, want)


def test_roundtrip_encode_decode_on_device():
    """Erase m rows, rebuild via host-inverted matrix + device matmul."""
    G = gf(8)
    k, m, w = 8, 3, 8
    Pm = mx.rs_vandermonde(k, m, w)
    data = RNG.integers(0, 256, size=(k, 4096)).astype(np.uint8)
    parity = np.asarray(gf_matmul(Pm, data))
    full = np.concatenate([data, parity], axis=0)
    erased = [0, 5, 9]  # two data rows + one parity row
    present = [r for r in range(k + m) if r not in erased][:k]
    R = mx.decode_matrix(Pm, k, w, present)
    rec = np.asarray(gf_matmul(R, full[present]))
    assert np.array_equal(rec, data)
