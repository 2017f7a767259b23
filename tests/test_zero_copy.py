"""Zero-copy data path: byte identity, aliasing semantics, copy budget.

Pins the PR-6 contract end to end:

- ``BufferList``/``as_u8`` are views (aliasing is the documented price;
  ``substr_copy`` is the escape hatch);
- the messenger's segment frames are bit-identical to the old flat
  frames, decode hands out views of the receive buffer, and the chained
  crc equals the whole-frame crc;
- the vectorized striper extent table equals the per-unit reference
  loop on unaligned offsets and short tails, and striped round trips
  stay bit-exact through a real cluster;
- EC encode/decode through views equals the bytes path (and the
  hardware crc32c equals the software tables);
- the ``data_path.copied_bytes`` budget: a full write+read round trip
  instruments at most 1x the payload per direction.
"""

import asyncio
import os

import numpy as np
import pytest

from ceph_tpu.models import registry
from ceph_tpu.msg import message as msgmod
from ceph_tpu.msg import messages
from ceph_tpu.osd import ec_util
from ceph_tpu.rados import MiniCluster, StripedLayout, StripedObject
from ceph_tpu.utils import buffers, native
from ceph_tpu.utils.buffers import BufferList, as_u8


# -- BufferList ---------------------------------------------------------------


class TestBufferList:
    def test_append_substr_zero_copy_aliasing(self):
        src = bytearray(b"0123456789abcdef")
        bl = BufferList()
        bl.append(memoryview(src)[:8]).append(memoryview(src)[8:])
        assert len(bl) == 16 and bl.nseg == 2
        sub = bl.substr(4, 8)
        assert sub == b"456789ab"
        # mutation-after-slice: the view ALIASES the source — this is
        # the documented hazard, pinned so a silent copy never creeps
        # in to "fix" it (the reference bufferlist aliases identically)
        src[5] = ord("X")
        assert sub == b"4X6789ab"
        # ...and the escape hatch is an independent copy
        frozen = bl.substr_copy(4, 8)
        src[6] = ord("Y")
        assert frozen == b"4X6789ab"
        assert sub == b"4XY789ab"

    def test_substr_across_segments_and_bounds(self):
        bl = BufferList(b"aaa")
        bl.append(b"bbbb").append(b"cc")
        assert bl.substr(0, 9) == b"aaabbbbcc"
        assert bl.substr(2, 3) == b"abb"
        assert bl.substr(3, 4) == b"bbbb"
        assert bl.substr(9, 0) == b""
        with pytest.raises(ValueError):
            bl.substr(8, 2)
        assert bl[2:5] == b"abb"  # slice sugar

    def test_crc_chains_equal_whole(self):
        rng = np.random.default_rng(1)
        raw = rng.integers(0, 256, size=(4096,), dtype=np.uint8).tobytes()
        bl = BufferList()
        for cut in (0, 100, 101, 2048, 4096):
            pass
        bl.append(raw[:100]).append(raw[100:101]).append(raw[101:])
        assert bl.crc32c(0xFFFFFFFF) == native.crc32c(
            0xFFFFFFFF, np.frombuffer(raw, np.uint8)
        )

    def test_flatten_is_counted(self):
        buffers.reset_copies()
        bl = BufferList(b"xy")
        bl.append(b"z")
        assert bl.tobytes() == b"xyz"
        assert buffers.copied_bytes("flatten") == 3
        # single-segment as_u8 is FREE (no flatten count)
        buffers.reset_copies()
        one = BufferList(b"hello")
        arr = one.as_u8()
        assert bytes(arr) == b"hello"
        assert buffers.copied_bytes("flatten") == 0

    def test_eq_and_numpy_append(self):
        a = np.frombuffer(b"abcd", dtype=np.uint8)
        bl = BufferList(a)
        assert bl == b"abcd" and bl == BufferList(b"abcd")
        assert not bl == b"abcx"
        assert not bl == b"abc"


# -- as_u8 --------------------------------------------------------------------


class TestAsU8:
    def test_bytearray_and_memoryview_are_views(self):
        src = bytearray(b"\x01\x02\x03\x04")
        arr = as_u8(src)
        src[0] = 9
        assert arr[0] == 9  # aliases, no copy
        mv = memoryview(src)[1:]
        arr2 = as_u8(mv)
        src[1] = 7
        assert arr2[0] == 7

    def test_bytes_input_no_copy_read_only(self):
        b = b"\x05\x06\x07\x08"
        arr = as_u8(b)
        assert not arr.flags.writeable
        assert bytes(arr) == b

    def test_writable_copies_only_when_needed(self):
        buffers.reset_copies()
        ba = bytearray(b"abcd")
        w = as_u8(ba, writable=True)
        assert buffers.copied_bytes("flatten") == 0  # writable source
        w[0] = 0
        assert ba[0] == 0  # still aliasing
        w2 = as_u8(b"abcd", writable=True)
        assert buffers.copied_bytes("flatten") == 4  # forced by bytes
        w2[0] = 0  # independent

    def test_bufferlist_input(self):
        bl = BufferList(b"ab")
        bl.append(b"cd")
        assert bytes(as_u8(bl)) == b"abcd"


# -- messenger frames ---------------------------------------------------------


class TestFrames:
    def _mk(self, blobs):
        return messages.MOSDOp(
            tid=7, epoch=3, pool="p", oid="o",
            ops=[{"op": "write", "data": 0}], blobs=blobs,
        )

    def test_segment_frame_bit_identical_to_flat(self):
        rng = np.random.default_rng(2)
        payload = rng.integers(0, 256, size=(8192,), dtype=np.uint8)
        for blobs in (
            [payload.tobytes()],
            [payload],                      # ndarray view
            [memoryview(payload.tobytes())],
            [BufferList(payload.tobytes()[:100]).append(
                payload.tobytes()[100:])],  # multi-segment
            [b"", payload.tobytes(), b"x"],
        ):
            msg = self._mk(blobs)
            segs, total, _rel = msgmod.encode_frame_segments(msg, 5)
            flat = b"".join(bytes(s) for s in segs)
            assert len(flat) == total
            assert flat == msgmod.encode_frame(self._mk(blobs), 5)
            out, seq = msgmod.decode_frame(flat)
            assert seq == 5
            got = np.concatenate([
                np.frombuffer(b, np.uint8) if len(b) else
                np.empty(0, np.uint8) for b in out.blobs
            ]) if out.blobs else np.empty(0, np.uint8)
            want = np.concatenate([
                as_u8(b) if len(b) else np.empty(0, np.uint8)
                for b in blobs
            ])
            assert np.array_equal(got, want)

    def test_multidim_view_blob_frames_correctly(self):
        """A 2-D ndarray / multi-dim memoryview blob must frame by
        BYTE count — len() of such a view counts first-dim items and
        would corrupt the length prefix (review finding, PR 6)."""
        arr2d = np.arange(24, dtype=np.uint8).reshape(2, 12)
        for blob in (arr2d, memoryview(arr2d)):
            msg = self._mk([blob])
            segs, total, _rel = msgmod.encode_frame_segments(msg, 3)
            flat = b"".join(bytes(s) for s in segs)
            assert len(flat) == total
            out, _ = msgmod.decode_frame(flat)
            assert bytes(out.blobs[0]) == arr2d.tobytes()

    def test_non_uint8_ndarray_blob_reinterprets_raw_bytes(self):
        """A u32-array blob must carry its raw little-endian bytes —
        exactly what the old bytes(b) copy serialized — never a value
        cast that truncates each lane to its low byte (review finding,
        PR 6)."""
        arr = np.array([0x01020304, 0xAABBCCDD], dtype=np.uint32)
        msg = self._mk([arr])
        segs, total, _rel = msgmod.encode_frame_segments(msg, 4)
        flat = b"".join(bytes(s) for s in segs)
        assert len(flat) == total
        out, _ = msgmod.decode_frame(flat)
        assert bytes(out.blobs[0]) == arr.tobytes()
        assert len(out.blobs[0]) == 8

    def test_bufferlist_eq_does_not_flatten(self):
        """Comparing two BufferLists must not gather either side — a
        flatten would record phantom copied bytes in the audit the
        budget gates read (review finding, PR 6)."""
        a = BufferList(b"abc")
        a.append(b"defgh")
        b = BufferList(b"abcd")
        b.append(b"e").append(b"fgh")
        buffers.reset_copies()
        assert a == b
        assert not a == BufferList(b"abcdefgX")
        assert not a == BufferList(b"abcdefghi")
        assert buffers.copied_bytes() == 0

    def test_decode_blobs_are_views_of_the_frame(self):
        msg = self._mk([b"A" * 4096])
        frame = msgmod.encode_frame(msg, 1)
        out, _ = msgmod.decode_frame(frame)
        blob = out.blobs[0]
        assert isinstance(blob, memoryview)
        assert np.shares_memory(
            np.frombuffer(blob, np.uint8), np.frombuffer(frame, np.uint8)
        )

    def test_decode_counts_no_copies(self):
        msg = self._mk([b"B" * 65536])
        frame = msgmod.encode_frame(msg, 1)
        buffers.reset_copies()
        msgmod.decode_frame(frame)
        assert buffers.copied_bytes() == 0

    def test_corrupt_frames_still_rejected(self):
        frame = bytearray(msgmod.encode_frame(self._mk([b"data"]), 1))
        frame[len(frame) // 2] ^= 0xFF
        with pytest.raises(msgmod.BadFrame):
            msgmod.decode_frame(bytes(frame))


# -- striper ------------------------------------------------------------------


def _extents_reference(lo, offset, length):
    """The pre-vectorization per-unit python loop, kept as oracle."""
    out = []
    pos = offset
    end = offset + length
    while pos < end:
        blockno = pos // lo.stripe_unit
        stripeno = blockno // lo.stripe_count
        stripepos = blockno % lo.stripe_count
        objectsetno = stripeno // lo.stripes_per_object
        objectno = objectsetno * lo.stripe_count + stripepos
        obj_off = (
            (stripeno % lo.stripes_per_object) * lo.stripe_unit
            + pos % lo.stripe_unit
        )
        run = min(lo.stripe_unit - pos % lo.stripe_unit, end - pos)
        if out and out[-1][0] == objectno and (
            out[-1][1] + out[-1][2] == obj_off
        ):
            out[-1] = (objectno, out[-1][1], out[-1][2] + run)
        else:
            out.append((objectno, obj_off, run))
        pos += run
    return out


class TestStriperTable:
    def test_vectorized_extents_equal_reference(self):
        rng = np.random.default_rng(3)
        layouts = [
            StripedLayout(4, 2, 8),
            StripedLayout(16, 3, 64),
            StripedLayout(512, 3, 2048),
            StripedLayout(4096, 1, 1 << 22),
            StripedLayout(4096, 7, 1 << 20),
        ]
        for lo in layouts:
            cases = [(0, 1), (0, lo.stripe_unit), (1, lo.stripe_unit),
                     (lo.stripe_unit - 1, 2), (0, lo.object_size * 3 + 5)]
            cases += [
                (int(rng.integers(0, 1 << 16)), int(rng.integers(1, 1 << 16)))
                for _ in range(40)
            ]
            for off, ln in cases:
                assert lo.extents(off, ln) == _extents_reference(
                    lo, off, ln
                ), (lo.stripe_unit, lo.stripe_count, off, ln)
            assert lo.extents(10, 0) == []

    def test_buf_offsets_cover_payload(self):
        lo = StripedLayout(16, 3, 64)
        obj, ooff, run, boff = lo.extent_table(5, 1000)
        assert int(run.sum()) == 1000
        # buffer offsets tile [0, length) exactly
        order = np.argsort(boff)
        assert boff[order][0] == 0
        assert np.array_equal(
            boff[order][1:], (boff + run)[order][:-1]
        )


# -- EC byte identity through views ------------------------------------------


class TestECViews:
    def _codec(self, k=4, m=2):
        return registry.instance().factory(
            "isa", {"plugin": "isa", "technique": "reed_sol_van",
                    "k": str(k), "m": str(m)},
        )

    def test_encode_from_views_identical(self):
        codec = self._codec()
        cs = 64
        sinfo = ec_util.StripeInfo(stripe_width=cs * 4, chunk_size=cs)
        rng = np.random.default_rng(4)
        raw = rng.integers(
            0, 256, size=(sinfo.stripe_width * 5,), dtype=np.uint8
        ).tobytes()
        ref = ec_util.encode(sinfo, codec, raw)
        for form in (
            memoryview(raw), bytearray(raw),
            np.frombuffer(raw, np.uint8),
        ):
            got = ec_util.encode(sinfo, codec, form)
            for s in ref:
                assert np.array_equal(
                    np.asarray(got[s]), np.asarray(ref[s])
                ), (type(form), s)

    def test_unaligned_view_offset_still_exact(self):
        """A memoryview at an odd offset into a larger buffer (the
        messenger-frame case: blobs start mid-frame) must encode the
        same bytes as an aligned copy."""
        codec = self._codec()
        cs = 64
        sinfo = ec_util.StripeInfo(stripe_width=cs * 4, chunk_size=cs)
        rng = np.random.default_rng(5)
        frame = rng.integers(
            0, 256, size=(sinfo.stripe_width * 3 + 13,), dtype=np.uint8
        ).tobytes()
        view = memoryview(frame)[13:]  # unaligned start
        ref = ec_util.encode(sinfo, codec, bytes(view))
        got = ec_util.encode(sinfo, codec, view)
        for s in ref:
            assert np.array_equal(np.asarray(got[s]), np.asarray(ref[s]))

    def test_decode_concat_round_trip_and_tail(self):
        codec = self._codec()
        cs = 64
        sinfo = ec_util.StripeInfo(stripe_width=cs * 4, chunk_size=cs)
        rng = np.random.default_rng(6)
        # short tail: pad_to_stripe gathers once, bytes stay exact
        raw = rng.integers(
            0, 256, size=(sinfo.stripe_width * 2 + 17,), dtype=np.uint8
        ).tobytes()
        padded = sinfo.pad_to_stripe(memoryview(raw))
        shards = ec_util.encode(sinfo, codec, padded)
        survivors = {s: shards[s] for s in (0, 2, 3, 5)}
        logical = ec_util.decode_concat(sinfo, codec, survivors)
        assert bytes(logical[: len(raw)]) == raw
        assert bytes(logical[len(raw):]) == b"\x00" * (
            len(logical) - len(raw)
        )

    def test_shards_to_logical_matches_numpy_oracle(self):
        rng = np.random.default_rng(7)
        k, S, cs = 3, 4, 8
        rows = [rng.integers(0, 256, size=(S * cs,), dtype=np.uint8)
                for _ in range(k)]
        got = ec_util.shards_to_logical(rows, cs)
        want = np.ascontiguousarray(
            np.stack(rows).reshape(k, S, cs).transpose(1, 0, 2)
        ).tobytes()
        assert bytes(got) == want


# -- native engine: hw crc ----------------------------------------------------


class TestNativeFastPaths:
    def test_hw_crc_equals_table_crc(self):
        if not native.host_engine_active():
            pytest.skip("native engine unavailable")
        import ctypes

        L = native.lib()
        rng = np.random.default_rng(8)
        for n in (0, 1, 7, 8, 9, 63, 255, 4096, 100_001):
            a = rng.integers(0, 256, size=(max(n, 1),), dtype=np.uint8)[:n]
            a = np.ascontiguousarray(a)
            for seed in (0, 0xFFFFFFFF, 0xDEADBEEF):
                hw = native.crc32c(seed, a)
                ptr = native._u8ptr(a) if n else ctypes.cast(
                    0, ctypes.POINTER(ctypes.c_uint8)
                )
                tab = int(L.crc32c_table(
                    ctypes.c_uint32(seed & 0xFFFFFFFF), ptr, n
                ))
                assert hw == tab, (n, seed)


# -- the copy budget, end to end ---------------------------------------------


class TestCopyBudget:
    def test_striped_round_trip_within_budget(self):
        """Full write+read round trip through a real cluster: the
        instrumented ``data_path`` copies must stay <= 1x the payload
        per direction — the write path sends views all the way, the
        read path pays exactly the striper gather."""

        async def main():
            async with MiniCluster(n_osds=3) as cluster:
                client = await cluster.client()
                await client.create_pool("rep", "replicated", size=2)
                io = client.io_ctx("rep")
                so = StripedObject(
                    io, "budget",
                    StripedLayout(stripe_unit=4096, stripe_count=2,
                                  object_size=16384),
                )
                payload = os.urandom(96 * 1024)
                buffers.reset_copies()
                await so.write(payload)
                written_copies = buffers.copied_bytes()
                # write path: zero payload copies (views end to end;
                # only sub-4KiB metadata ops may register)
                assert written_copies <= len(payload) // 8, (
                    f"write path copied {written_copies} bytes "
                    f"of a {len(payload)}-byte payload"
                )
                buffers.reset_copies()
                got = await so.read()
                assert bytes(got) == payload  # bit-exact through views
                read_copies = buffers.copied_bytes()
                # read path: exactly the one striper gather (+ slack
                # for the size-attr metadata read)
                assert read_copies <= len(payload) + 8192, (
                    f"read path copied {read_copies} bytes "
                    f"of a {len(payload)}-byte payload"
                )

        asyncio.run(main())

    def test_ec_object_round_trip_within_budget(self):
        """Direct EC-pool object round trip: encode gathers at most 1x
        on the write, reassembly gathers at most 1x on the read."""

        async def main():
            async with MiniCluster(n_osds=4) as cluster:
                client = await cluster.client()
                await client.create_pool("ecpool", "erasure")
                io = client.io_ctx("ecpool")
                payload = os.urandom(256 * 1024)
                buffers.reset_copies()
                await io.write_full("obj", payload)
                w = buffers.copied_bytes()
                assert w <= len(payload) + 8192, f"write copied {w}"
                buffers.reset_copies()
                got = await io.read("obj", 0, len(payload), copy=False)
                assert bytes(got) == payload
                r = buffers.copied_bytes()
                assert r <= len(payload) + 8192, f"read copied {r}"

        asyncio.run(main())
