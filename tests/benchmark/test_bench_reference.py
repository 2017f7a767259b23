"""The benchmark's yardstick: plain references, peaks and the bytes
model, checked against independent pins and, at small sizes, against
the program."""

import base64
import json
import pathlib

import numpy as np
import pytest

from benchmark import peaks
from benchmark.reference import crush as ref_crush
from benchmark.reference import gf256

GOLDEN = pathlib.Path(__file__).parent.parent / "golden"
ISA = json.loads((GOLDEN / "isa_reference" / "manifest.json").read_text())
RS_CASES = [c for c in ISA["cases"] if c["technique"] == "reed_sol_van"]


@pytest.mark.parametrize("case", RS_CASES,
                         ids=[f"k{c['k']}m{c['m']}len{c['chunk_len']}"
                              for c in RS_CASES])
def test_isa_rs_matches_isa_l_bytes(case):
    """Parity bytes of Intel's own C fallback (ec_base.c), not repo code."""
    k, m = case["k"], case["m"]
    data = np.random.default_rng(case["data_seed"]).integers(
        0, 256, size=(k, case["chunk_len"]), dtype=np.uint8)
    assert np.array_equal(gf256.isa_rs_matrix(k, m),
                          case["matrix_parity_rows"])
    parity = np.stack([np.frombuffer(base64.b64decode(p), dtype=np.uint8)
                       for p in case["parity"]])
    full = gf256.encode({"plugin": "isa", "k": k, "m": m}, data)
    assert np.array_equal(full[:k], data)
    assert np.array_equal(full[k:], parity)


def test_jerasure_matrix_matches_the_jerasure_manual():
    """``reed_sol_01 7 7 8`` in the jerasure manual prints these rows of
    the coding matrix: first row and first column all ones."""
    mat = gf256.jerasure_rs_matrix(7, 7)
    assert list(mat[0]) == [1] * 7
    assert list(mat[1]) == [1, 199, 210, 240, 105, 121, 248]
    assert list(mat[2]) == [1, 70, 91, 245, 56, 142, 167]
    assert list(mat[:, 0]) == [1] * 7


def test_lrc_layers_as_upstream_parse_kml():
    mapping, layers = gf256.lrc_layers(8, 4, 3)
    assert mapping == "DD__" * 4
    assert layers == ["DDc_" * 4, "DDDc" + "____" * 3,
                      "____DDDc" + "____" * 2, "____" * 2 + "DDDc____",
                      "____" * 3 + "DDDc"]
    assert gf256.data_positions({"plugin": "lrc", "k": 8, "m": 4, "l": 3}) \
        == [0, 1, 4, 5, 8, 9, 12, 13]
    with pytest.raises(ValueError):
        gf256.lrc_layers(8, 4, 4)


def test_lrc_local_parity_is_xor_of_its_group():
    data = np.random.default_rng(3).integers(0, 256, (8, 64), dtype=np.uint8)
    full = gf256.encode({"plugin": "lrc", "k": 8, "m": 4, "l": 3}, data)
    for g in range(4):
        assert np.array_equal(full[4 * g + 3], full[4 * g] ^ full[4 * g + 1]
                              ^ full[4 * g + 2])


def test_gf_tables():
    assert gf256.mul(2, 0x80) == 0x1D
    for a in (1, 2, 29, 200, 255):
        assert gf256.mul(a, gf256.div(1, a)) == 1
    assert np.array_equal(gf256.MUL[1], np.arange(256))


def test_isa_rs_matches_program_codec():
    from ceph_tpu.models import registry

    profile = {"plugin": "isa", "technique": "reed_sol_van", "k": "8",
               "m": "3"}
    codec = registry.instance().factory(
        "isa", {k: v for k, v in profile.items() if k != "plugin"})
    data = np.random.default_rng(5).integers(0, 256, (8, 4096),
                                             dtype=np.uint8)
    assert np.array_equal(codec.encode_chunks(data),
                          gf256.encode(profile, data)[8:])


CRUSH = json.loads((pathlib.Path(__file__).parents[2] / "benchmark" /
                    "configs" / "ec-rs83.json").read_text())["crush"]


def test_crush_reference_matches_program_scalar_mapper():
    """The program's scalar mapper is pinned to upstream's C by
    tests/golden/crush_golden.json; the reference must agree with it."""
    from benchmark.surfaces import crush as surface
    from ceph_tpu.crush import mapper

    small = dict(CRUSH, n_osds=96)
    plain = ref_crush.build_map(small)
    cmap, ruleno = surface.program_map(small, plain)
    ws = mapper.Workspace(cmap)
    for x in [*range(40), 2 ** 31 + 5, 2 ** 32 - 1]:
        assert ref_crush.do_rule(plain, x, 11) == mapper.crush_do_rule(
            cmap, ruleno, x, 11, weight=cmap.get_weights(), workspace=ws)


def test_crush_hashes_and_ln():
    from ceph_tpu.crush import hashes, mapper

    for a, b, c in ((0, 0, 0), (1, 2, 3), (2 ** 32 - 1, 7, 2 ** 31)):
        assert ref_crush.hash32_3(a, b, c) == hashes.crush_hash32_3(a, b, c)
        assert ref_crush.hash32_2(a, b) == hashes.crush_hash32_2(a, b)
    for u in (0, 1, 255, 0x7FFF, 0x8000, 0xFFFF):
        assert ref_crush.crush_ln(u) == mapper.crush_ln(u)


def test_peaks_table():
    p = peaks.peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["hbm_bytes"] == 16e9
    assert p["bf16_flops_per_s"] == 197e12
    assert p["int8_ops_per_s"] == 393e12
    with pytest.raises(KeyError, match="no peaks"):
        peaks.peaks("TPU v9 imaginary")


@pytest.mark.parametrize("k,n,want", [(8, 11, 11 << 20), (8, 16, 16 << 20)])
def test_codec_min_bytes(k, n, want):
    assert peaks.codec_min_bytes(8 << 20, k, n) == want


def test_codec_min_bytes_refuses_a_ragged_split():
    with pytest.raises(ValueError):
        peaks.codec_min_bytes(10, 8, 11)
