"""StripeInfo algebra, batched stripe encode/decode, HashInfo, crc32c.

Algebra cases mirror reference:src/test/osd/TestECBackend.cc:22-60
(stripe_info_t with stripe_width=2*chunk, the sub/next offset identities).
"""

import numpy as np
import pytest

from ceph_tpu.models import registry as registry_mod
from ceph_tpu.osd import ec_util
from ceph_tpu.osd.ec_util import HashInfo, StripeInfo
from ceph_tpu.utils import native


def make_codec(k=4, m=2):
    return registry_mod.instance().factory(
        "jerasure",
        {"k": str(k), "m": str(m), "technique": "reed_sol_van"},
    )


class TestStripeInfo:
    def test_algebra(self):
        # mirrors TestECBackend.cc: swidth=4096, ssize=4 -> chunk 1024
        s = StripeInfo(stripe_width=4096, chunk_size=1024)
        assert s.k == 4
        assert s.logical_to_prev_chunk_offset(0) == 0
        assert s.logical_to_prev_chunk_offset(4095) == 0
        assert s.logical_to_prev_chunk_offset(4096) == 1024
        assert s.logical_to_next_chunk_offset(0) == 0
        assert s.logical_to_next_chunk_offset(1) == 1024
        assert s.logical_to_next_chunk_offset(4096) == 1024
        assert s.logical_to_next_chunk_offset(4097) == 2048
        assert s.logical_to_prev_stripe_offset(4095) == 0
        assert s.logical_to_prev_stripe_offset(4096) == 4096
        assert s.logical_to_next_stripe_offset(4095) == 4096
        assert s.logical_to_next_stripe_offset(4096) == 4096
        assert s.aligned_logical_offset_to_chunk_offset(8192) == 2048
        assert s.aligned_chunk_offset_to_logical_offset(2048) == 8192
        assert s.offset_len_to_stripe_bounds(100, 3900) == (0, 4096)
        assert s.offset_len_to_stripe_bounds(100, 4000) == (0, 8192)
        assert s.offset_len_to_stripe_bounds(4096, 4097) == (4096, 8192)

    def test_bad_width(self):
        with pytest.raises(ValueError):
            StripeInfo(stripe_width=4100, chunk_size=1024)

    def test_pad(self):
        s = StripeInfo(4096, 1024)
        assert len(s.pad_to_stripe(b"x" * 100)) == 4096
        assert s.pad_to_stripe(b"x" * 4096) == b"x" * 4096


class TestBatchedStripeMath:
    def test_encode_matches_per_stripe_loop(self):
        """Batched [k, S*chunk] call == reference's stripe-by-stripe loop."""
        codec = make_codec()
        cs = codec.get_chunk_size(4096)
        sinfo = StripeInfo(stripe_width=4 * cs, chunk_size=cs)
        rng = np.random.default_rng(1)
        S = 7
        data = rng.integers(0, 256, size=S * sinfo.stripe_width, dtype=np.uint8)

        batched = ec_util.encode(sinfo, codec, data.tobytes())

        # oracle: encode each stripe separately, append per shard
        per_shard = {i: [] for i in range(6)}
        for s in range(S):
            stripe = data[s * sinfo.stripe_width : (s + 1) * sinfo.stripe_width]
            enc = codec.encode(list(range(6)), stripe.tobytes())
            for i in range(6):
                per_shard[i].append(enc[i])
        for i in range(6):
            expect = np.concatenate(per_shard[i])
            np.testing.assert_array_equal(batched[i], expect, err_msg=f"shard {i}")

    def test_decode_concat_roundtrip(self):
        codec = make_codec()
        cs = codec.get_chunk_size(4096)
        sinfo = StripeInfo(stripe_width=4 * cs, chunk_size=cs)
        rng = np.random.default_rng(2)
        data = rng.integers(0, 256, size=5 * sinfo.stripe_width, dtype=np.uint8)
        shards = ec_util.encode(sinfo, codec, data.tobytes())
        # lose two shards (one data, one parity)
        survivors = {i: v for i, v in shards.items() if i not in (1, 4)}
        out = ec_util.decode_concat(sinfo, codec, survivors)
        assert out == data.tobytes()

    def test_decode_unequal_buffers_rejected(self):
        codec = make_codec()
        cs = codec.get_chunk_size(4096)
        sinfo = StripeInfo(4 * cs, cs)
        with pytest.raises(ValueError):
            ec_util.decode(
                sinfo, codec,
                {0: np.zeros(cs, np.uint8), 1: np.zeros(2 * cs, np.uint8)},
            )


class TestCrc32c:
    def test_known_vectors(self):
        # standard CRC-32C check value, expressed via ceph's raw-seed calling
        # convention: final = ~crc32c(~0, data)
        crc = native.crc32c(0xFFFFFFFF, b"123456789")
        assert (~crc) & 0xFFFFFFFF == 0xE3069283
        # composition across appends
        whole = native.crc32c(0xFFFFFFFF, b"hello world")
        split = native.crc32c(native.crc32c(0xFFFFFFFF, b"hello "), b"world")
        assert whole == split
        assert native.crc32c(123, b"") == 123

    def test_matches_bytewise_reference(self):
        def crc_ref(crc, data):  # bitwise reference implementation
            for b in data:
                crc ^= b
                for _ in range(8):
                    crc = (crc >> 1) ^ (0x82F63B78 if crc & 1 else 0)
            return crc

        rng = np.random.default_rng(3)
        for n in (1, 7, 8, 9, 63, 200):
            buf = rng.integers(0, 256, size=n, dtype=np.uint8).tobytes()
            assert native.crc32c(0xFFFFFFFF, buf) == crc_ref(0xFFFFFFFF, buf)


def _per_chunk_table(shard_bufs, chunk):
    """A stripe-hash table row built one native crc32c call per chunk."""
    return [
        [native.crc32c(0xFFFFFFFF, buf[o:o + chunk])
         for o in range(0, buf.size, chunk)]
        for _, buf in sorted(shard_bufs.items())
    ]


class TestStripeHashesBatched:
    """StripeHashes hashes a shard's chunks in one native call; its
    tables are those of one call per chunk, value for value."""

    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_full_table_matches_per_chunk_calls(self, seed):
        rng = np.random.default_rng(seed)
        bufs = {s: rng.integers(0, 256, 128 * 4096, dtype=np.uint8)
                for s in range(11)}
        sh = ec_util.StripeHashes(11, 4096)
        assert sh.set_range(0, bufs) == (11 * 128, 11)
        assert sh.to_dict() == {"chunk_size": 4096,
                                "crcs": _per_chunk_table(bufs, 4096)}

    def test_partial_range_past_end_zero_fills(self):
        rng = np.random.default_rng(4)
        cs = 512
        sh = ec_util.StripeHashes(3, cs)
        first = {s: rng.integers(0, 256, 2 * cs, dtype=np.uint8)
                 for s in range(3)}
        sh.set_range(0, first)
        later = {s: rng.integers(0, 256, 3 * cs, dtype=np.uint8)
                 for s in range(3)}
        assert sh.set_range(5, later) == (9, 3)
        zero = native.crc32c(0xFFFFFFFF, np.zeros(cs, np.uint8))
        want_first = _per_chunk_table(first, cs)
        want_later = _per_chunk_table(later, cs)
        for s in range(3):
            assert sh.crcs[s] == want_first[s] + [zero] * 3 + want_later[s]

    def test_verify_accepts_true_and_rejects_flipped_byte(self):
        rng = np.random.default_rng(5)
        cs = 4096
        bufs = {s: rng.integers(0, 256, 8 * cs, dtype=np.uint8)
                for s in range(4)}
        sh = ec_util.StripeHashes(4, cs)
        sh.set_range(0, bufs)
        for s, buf in bufs.items():
            assert sh.verify(s, 0, buf)
            assert sh.verify(s, 3, buf[3 * cs:5 * cs])
            bad = buf.copy()
            bad[5 * cs + 17] ^= 0x01
            assert not sh.verify(s, 0, bad)

    def test_partial_chunk_rejected(self):
        sh = ec_util.StripeHashes(1, 512)
        with pytest.raises(ValueError, match="not a multiple"):
            sh.set_range(0, {0: np.zeros(700, np.uint8)})


class TestHashInfo:
    def test_append_and_verify(self):
        hi = HashInfo(3)
        a = {0: np.full(64, 1, np.uint8), 1: np.full(64, 2, np.uint8),
             2: np.full(64, 3, np.uint8)}
        hi.append(0, a)
        assert hi.get_total_chunk_size() == 64
        b = {0: np.full(32, 4, np.uint8), 1: np.full(32, 5, np.uint8),
             2: np.full(32, 6, np.uint8)}
        hi.append(64, b)
        assert hi.get_total_chunk_size() == 96
        # cumulative == crc over the concatenation
        for s in range(3):
            whole = np.concatenate([a[s], b[s]])
            assert hi.get_chunk_hash(s) == native.crc32c(0xFFFFFFFF, whole)

    def test_append_gap_rejected(self):
        hi = HashInfo(2)
        with pytest.raises(ValueError):
            hi.append(10, {0: np.zeros(4, np.uint8), 1: np.zeros(4, np.uint8)})

    def test_roundtrip_dict(self):
        hi = HashInfo(2)
        hi.append(0, {0: np.arange(16, dtype=np.uint8),
                      1: np.arange(16, dtype=np.uint8)})
        hi2 = HashInfo.from_dict(hi.to_dict())
        assert hi2.to_dict() == hi.to_dict()

    def test_clear(self):
        hi = HashInfo(2)
        hi.append(0, {0: np.ones(8, np.uint8), 1: np.ones(8, np.uint8)})
        hi.clear()
        assert hi.get_total_chunk_size() == 0
        assert hi.get_chunk_hash(0) == 0xFFFFFFFF
