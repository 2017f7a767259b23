"""Native C++ engine vs numpy oracle (independent implementations)."""

import numpy as np
import pytest

from ceph_tpu.ops import matrices as mx
from ceph_tpu.ops.gf import gf
from ceph_tpu.utils import native

RNG = np.random.default_rng(5)


def test_native_builds():
    assert native.build().exists()


def test_build_is_keyed_on_source_and_flags(tmp_path, monkeypatch):
    """The .so name hashes the source, the command and the CPU the
    compiler targets: another source or flag set builds another file,
    the same inputs reuse theirs, and no file time is read."""
    import re

    monkeypatch.setattr(native, "_BUILD", tmp_path)
    src = tmp_path / "one.cc"
    src.write_text("extern \"C\" int one() { return 1; }\n")
    cmd = ["g++", "-O1", "-shared", "-fPIC", str(src)]
    so = native.build_so("libone", [src], cmd)
    assert re.fullmatch(r"libone-[0-9a-f]{16}\.so", so.name)
    assert native.build_so("libone", [src], cmd) == so
    assert native.build_so("libone", [src], [*cmd, "-O2"]) != so
    src.write_text("extern \"C\" int one() { return 2; }\n")
    assert native.build_so("libone", [src], cmd) != so


def test_mul_region_matches():
    G = gf(8)
    region = RNG.integers(0, 256, size=4096).astype(np.uint8)
    for c in [0, 1, 2, 0x1D, 97, 255]:
        assert np.array_equal(native.mul_region(c, region), G.mul_region(region, c))


def test_xor_region():
    a = RNG.integers(0, 256, size=1024).astype(np.uint8)
    b = RNG.integers(0, 256, size=1024).astype(np.uint8)
    assert np.array_equal(native.xor_region(a, b), a ^ b)


@pytest.mark.parametrize("k,m", [(2, 1), (8, 3), (10, 4)])
def test_encode_matches_oracle(k, m):
    G = gf(8)
    M = mx.rs_vandermonde(k, m, 8)
    data = RNG.integers(0, 256, size=(k, 8192)).astype(np.uint8)
    want = G.matmul_region(M, data)
    got = native.encode(M, data)
    assert np.array_equal(got, want)


def test_encode_w16_matches_oracle():
    G = gf(16)
    M = mx.rs_vandermonde(4, 2, 16)
    data16 = RNG.integers(0, 1 << 16, size=(4, 2048)).astype("<u2")
    want = G.matmul_region(M, data16)
    got = native.encode(M, data16.view(np.uint8), w=16)
    assert np.array_equal(got.view("<u2"), want)
