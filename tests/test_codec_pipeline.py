"""The pipelined u32 GF matmul behind ``MatrixErasureCode``: an input of
two or more whole column slices runs as one launch per slice, anything
else as one launch, and the bytes are the same either way.

Slices here are one Pallas block (16 KiB per row) so the inputs stay
small; the profiler's ``launches`` says which path ran.
"""

import functools

import numpy as np
import pytest

from ceph_tpu.models import instance
from ceph_tpu.models import matrix_codec
from ceph_tpu.ops import gf_pallas
from ceph_tpu.ops.gf import gf
from ceph_tpu.ops.profiler import profiler

SLICE = 4 * gf_pallas.BLOCK  # bytes per row of one slice

# (row bytes, launches per call)
WIDTHS = {
    "one_slice": (SLICE, 1),
    "two_slices": (2 * SLICE, 2),
    "many_slices": (8 * SLICE, 8),
    "uneven": (5 * SLICE // 2, 1),
}


@pytest.fixture
def rs83(monkeypatch):
    monkeypatch.setattr(
        matrix_codec, "_gf_matmul_u32",
        functools.partial(matrix_codec._gf_matmul_u32, slice_bytes=SLICE))
    return instance().factory(
        "isa", {"technique": "reed_sol_van", "k": "8", "m": "3"})


def _one_call(engine, fn):
    """fn() under a fresh profiler window -> (result, launches of its
    one call on ``engine``)."""
    p = profiler()
    p.reset()
    out = fn()
    e = p.dump()["engines"][engine]
    assert e["calls"] == 1, e
    return out, e["launches"]


@pytest.mark.parametrize("case", sorted(WIDTHS))
def test_pipelined_encode_decode_bytes_and_launches(rs83, case):
    row, launches = WIDTHS[case]
    rng = np.random.default_rng(row)
    data = rng.integers(0, 256, size=(8, row), dtype=np.uint8)
    want = gf(8).matmul_region(rs83.matrix, data).astype(np.uint8)
    single = matrix_codec._gf_matmul_u32.func(
        "single", rs83._mkey, 8, data.view(np.uint32),
        slice_bytes=64 * SLICE).view(np.uint8)

    parity, n = _one_call("gf_encode", lambda: rs83.encode_chunks(data))
    assert n == launches
    assert parity.shape == (3, row) and parity.dtype == np.uint8
    assert parity.flags.c_contiguous
    np.testing.assert_array_equal(parity, want)
    np.testing.assert_array_equal(parity, single)

    chunks = np.concatenate([data, parity])
    lost = (0, 5, 9)
    present = tuple(i for i in range(11) if i not in lost)
    rebuilt, n = _one_call(
        "gf_decode",
        lambda: rs83.decode_chunks(present, chunks[list(present)], lost))
    assert n == launches
    np.testing.assert_array_equal(rebuilt, chunks[list(lost)])


def test_slice_must_be_whole_kernel_blocks():
    d32 = np.zeros((2, 4 * gf_pallas.BLOCK), dtype=np.uint32)
    with pytest.raises(ValueError, match="kernel blocks"):
        matrix_codec._gf_matmul_u32("gf_encode", ((1, 1),), 8, d32,
                                    slice_bytes=SLICE + 4)
