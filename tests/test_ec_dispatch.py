"""EC microbatch dispatcher (ceph_tpu.osd.ec_dispatch) tests.

Pins the dispatcher's whole contract:
- bytes identical to per-op ec_util.encode/decode_concat (the numpy
  oracle underneath) across mixed op sizes and bucket-boundary sizes;
- flush-on-threshold vs flush-on-window policy, including the
  no-overshoot rule (a batch never pads past its bucket because one
  more op arrived);
- a cancelled (op-aborted) waiter is dropped without wedging the batch;
- the event loop keeps ticking while a long encode runs (liberation);
- the anti-compile-storm gate: a 50-way size sweep costs at most
  O(#buckets) jit-cache misses, not O(#distinct sizes);
- the OSD wires it in: an EC write on a live cluster lands dispatcher
  counters.
"""

import asyncio
import time

import numpy as np
import pytest

from ceph_tpu.models.matrix_codec import MatrixErasureCode
from ceph_tpu.ops import matrices as mx
from ceph_tpu.ops.profiler import profiler
from ceph_tpu.osd import ec_util
from ceph_tpu.osd.ec_dispatch import ECDispatcher, bucket_stripes


def run(coro):
    return asyncio.run(coro)


CS = 512  # chunk_size; stripe_width = k * CS


def _sinfo(k: int) -> ec_util.StripeInfo:
    return ec_util.StripeInfo(stripe_width=CS * k, chunk_size=CS)


def _codec(k: int = 2, m: int = 1) -> MatrixErasureCode:
    return MatrixErasureCode(k, m, 8, mx.isa_rs_vandermonde(k, m))


def _bufs(sinfo, sizes, seed=0):
    rng = np.random.default_rng(seed)
    return [
        rng.integers(0, 256, size=(s * sinfo.stripe_width,),
                     dtype=np.uint8)
        for s in sizes
    ]


def _assert_same_shards(got, want):
    assert set(got) == set(want)
    for s in want:
        assert np.array_equal(np.asarray(got[s]), np.asarray(want[s])), (
            f"shard {s} diverged"
        )


def test_bucket_stripes_boundaries():
    assert [bucket_stripes(s) for s in (1, 2, 3, 4, 5, 8, 9, 1023)] == \
        [1, 2, 4, 4, 8, 8, 16, 1024]


# -- byte identity vs the per-op oracle --------------------------------------


@pytest.mark.parametrize("bucket", [False, True])
def test_encode_bytes_identical_mixed_sizes(bucket):
    """Coalesced output == per-op ec_util.encode, with and without the
    power-of-two shape bucketing (pad rows sliced back off)."""
    k, m = 2, 1
    sinfo, codec = _sinfo(k), _codec(k, m)
    sizes = [1, 2, 3, 4, 5, 7, 8, 9]
    bufs = _bufs(sinfo, sizes)

    async def main():
        disp = ECDispatcher(window=0.005, max_stripes=1 << 20,
                            bucket=bucket)
        outs = await asyncio.gather(
            *[disp.encode(sinfo, codec, b) for b in bufs]
        )
        await disp.stop()
        return outs

    outs = run(main())
    for b, got in zip(bufs, outs):
        _assert_same_shards(got, ec_util.encode(sinfo, codec, b))


@pytest.mark.parametrize("stripes", [1, 8, 9, 16, 17])
def test_encode_bucket_boundary_sizes(stripes):
    """S=1, S=2^n, S=2^n+1 single-op batches survive the pad+slice."""
    sinfo, codec = _sinfo(2), _codec()
    (buf,) = _bufs(sinfo, [stripes], seed=stripes)

    async def main():
        disp = ECDispatcher(window=0.0, max_stripes=1 << 20)
        out = await disp.encode(sinfo, codec, buf)
        await disp.stop()
        return out

    _assert_same_shards(run(main()), ec_util.encode(sinfo, codec, buf))


@pytest.mark.parametrize("bucket", [False, True])
def test_decode_bytes_identical(bucket):
    """Coalesced decode_concat == per-op ec_util.decode_concat for a
    degraded read (data shard missing) across mixed sizes, with and
    without shape bucketing."""
    k, m = 2, 1
    sinfo, codec = _sinfo(k), _codec(k, m)
    sizes = [1, 2, 4, 5]
    bufs = _bufs(sinfo, sizes, seed=3)
    # survivors: drop data shard 0 everywhere -> same present set, so
    # the requests share one queue key and truly coalesce
    chunk_maps = []
    for b in bufs:
        enc = ec_util.encode(sinfo, codec, b)
        chunk_maps.append({1: enc[1], 2: enc[2]})

    async def main():
        disp = ECDispatcher(window=0.005, max_stripes=1 << 20,
                            bucket=bucket)
        outs = await asyncio.gather(
            *[disp.decode_concat(sinfo, codec, c) for c in chunk_maps]
        )
        st = disp.dump()
        await disp.stop()
        return outs, st

    outs, st = run(main())
    for b, c, got in zip(bufs, chunk_maps, outs):
        assert got == ec_util.decode_concat(sinfo, codec, c)
        assert got == b.tobytes()
    # all four requests coalesced into one launch
    assert st["totals"]["batches"] == 1
    assert st["totals"]["ops"] == 4
    assert st["totals"]["pad_stripes"] == (4 if bucket else 0)  # 12 -> 16


# -- flush policy ------------------------------------------------------------


def test_flush_on_threshold_beats_window():
    sinfo, codec = _sinfo(2), _codec()
    bufs = _bufs(sinfo, [2, 2], seed=1)

    async def main():
        # window absurdly long: only the size threshold can flush
        disp = ECDispatcher(window=30.0, max_stripes=4)
        t0 = time.monotonic()
        outs = await asyncio.gather(
            *[disp.encode(sinfo, codec, b) for b in bufs]
        )
        took = time.monotonic() - t0
        st = disp.dump()
        await disp.stop()
        return outs, st, took

    outs, st, took = run(main())
    assert took < 5.0  # did NOT wait for the 30 s window
    assert st["totals"]["flush_reasons"]["size"] == 1
    assert st["totals"]["flush_reasons"]["window"] == 0
    assert st["totals"]["batches"] == 1 and st["totals"]["ops"] == 2
    for b, got in zip(bufs, outs):
        _assert_same_shards(got, ec_util.encode(sinfo, codec, b))


def test_flush_on_window():
    sinfo, codec = _sinfo(2), _codec()
    (buf,) = _bufs(sinfo, [2], seed=2)

    async def main():
        # threshold unreachable: only the window can flush
        disp = ECDispatcher(window=0.01, max_stripes=1 << 20)
        out = await disp.encode(sinfo, codec, buf)
        st = disp.dump()
        await disp.stop()
        return out, st

    out, st = run(main())
    assert st["totals"]["flush_reasons"]["window"] == 1
    assert st["totals"]["flush_reasons"]["size"] == 0
    _assert_same_shards(out, ec_util.encode(sinfo, codec, buf))


def test_no_bucket_overshoot():
    """An op that would push the batch past the threshold flushes the
    queued ops at their snug bucket first — pad waste stays bounded by
    the bucket below max_stripes, not doubled past it."""
    sinfo, codec = _sinfo(2), _codec()
    # 3+3 stripes fill toward max_stripes=4: admitting the second op
    # would make 6 -> bucket 8 (100% overshoot); instead op 1 launches
    # at bucket 4 and op 2 at bucket 4
    bufs = _bufs(sinfo, [3, 3], seed=4)

    async def main():
        disp = ECDispatcher(window=0.01, max_stripes=4)
        outs = await asyncio.gather(
            *[disp.encode(sinfo, codec, b) for b in bufs]
        )
        st = disp.dump()
        await disp.stop()
        return outs, st

    outs, st = run(main())
    assert st["totals"]["batches"] == 2
    assert set(st["buckets"]) == {"4"}
    assert st["totals"]["pad_stripes"] == 2  # 1 per 3-stripe launch
    for b, got in zip(bufs, outs):
        _assert_same_shards(got, ec_util.encode(sinfo, codec, b))


def test_cancelled_waiter_does_not_wedge_batch():
    """Op abort: a cancelled queued waiter is dropped; the surviving
    ops' batch still launches and answers."""
    sinfo, codec = _sinfo(2), _codec()
    buf_a, buf_b = _bufs(sinfo, [1, 4], seed=5)

    async def main():
        disp = ECDispatcher(window=30.0, max_stripes=4)
        task_a = asyncio.ensure_future(disp.encode(sinfo, codec, buf_a))
        await asyncio.sleep(0)  # let A enqueue
        task_a.cancel()
        await asyncio.sleep(0)  # let the cancellation land on A's future
        out_b = await disp.encode(sinfo, codec, buf_b)  # size-flushes
        with pytest.raises(asyncio.CancelledError):
            await task_a
        st = disp.dump()
        await disp.stop()
        return out_b, st

    out_b, st = run(main())
    assert st["totals"]["cancelled"] == 1
    assert st["totals"]["ops"] == 1  # only B was launched
    _assert_same_shards(out_b, ec_util.encode(sinfo, codec, buf_b))


def test_batch_failure_reaches_every_waiter(monkeypatch):
    """A codec blowing up inside the worker thread rejects all waiters
    instead of wedging them."""
    sinfo, codec = _sinfo(2), _codec()
    bufs = _bufs(sinfo, [1, 2], seed=6)

    def boom(*a, **kw):
        raise RuntimeError("device on fire")

    async def main():
        disp = ECDispatcher(window=0.005, max_stripes=1 << 20)
        monkeypatch.setattr(ec_util, "encode", boom)
        res = await asyncio.gather(
            *[disp.encode(sinfo, codec, b) for b in bufs],
            return_exceptions=True,
        )
        await disp.stop()
        return res

    res = run(main())
    assert len(res) == 2
    assert all(isinstance(r, RuntimeError) for r in res)


def test_batch_task_ended_unanswered_cancels_its_waiters(monkeypatch):
    """A launch that ends by a BaseException the fault fork does not
    catch (here a CancelledError leaking out of the lane) must not
    leave its waiters pending for ever: they are cancelled, so the op
    task ends and the client resends."""
    sinfo, codec = _sinfo(2), _codec()
    (buf,) = _bufs(sinfo, [1], seed=7)

    async def leak(b, ops):
        raise asyncio.CancelledError()

    async def main():
        disp = ECDispatcher(window=0.0, max_stripes=1 << 20)
        monkeypatch.setattr(disp, "_launch", leak)
        with pytest.raises(asyncio.CancelledError):
            await asyncio.wait_for(disp.encode(sinfo, codec, buf), 5.0)
        await disp.stop()

    run(main())


# -- event-loop liberation ---------------------------------------------------


def test_event_loop_survives_long_encode(monkeypatch):
    """The liberation bound: while a (deliberately slow) encode runs in
    the dispatcher's worker thread, the event loop keeps scheduling —
    the heartbeat-tick survival property, measured as max loop stall."""
    sinfo, codec = _sinfo(2), _codec()
    (buf,) = _bufs(sinfo, [2], seed=7)

    real_encode = ec_util.encode

    def slow_encode(*a, **kw):
        time.sleep(0.6)  # a long device call, in the worker thread
        return real_encode(*a, **kw)

    monkeypatch.setattr(ec_util, "encode", slow_encode)

    async def main():
        disp = ECDispatcher(window=0.0, max_stripes=1 << 20)
        gaps = []

        async def ticker():
            last = time.monotonic()
            while True:
                await asyncio.sleep(0.01)
                now = time.monotonic()
                gaps.append(now - last)
                last = now

        t = asyncio.ensure_future(ticker())
        out = await disp.encode(sinfo, codec, buf)
        t.cancel()
        await disp.stop()
        return out, max(gaps)

    out, worst_stall = run(main())
    _assert_same_shards(out, real_encode(sinfo, codec, buf))
    # the encode slept 0.6 s; a blocked loop would show a ~0.6 s gap
    # (threshold leaves headroom for scheduler noise on loaded hosts)
    assert worst_stall < 0.35, (
        f"event loop stalled {worst_stall:.3f}s behind the encode"
    )


# -- the anti-compile-storm gate ---------------------------------------------


def test_size_sweep_jit_misses_bounded_by_buckets():
    """50 distinct op sizes through the dispatcher cost at most
    #buckets jit-cache signatures (the KernelProfiler's first-sighting
    misses), not 50 — the compile-storm fix the bucketing exists for."""
    # a geometry no other test uses, so profiler signatures are fresh
    k, m = 5, 2
    sinfo = ec_util.StripeInfo(stripe_width=256 * k, chunk_size=256)
    codec = _codec(k, m)
    sizes = list(range(1, 51))
    bufs = _bufs(sinfo, sizes, seed=8)

    def _misses():
        eng = profiler().dump()["engines"].get("ec_shards")
        return eng["jit_cache"]["misses"] if eng else 0

    before = _misses()

    async def main():
        # window 0 + per-op awaits: every op launches its own batch, so
        # the SWEEP (not coalescing) is what exercises the bucket table
        disp = ECDispatcher(window=0.0, max_stripes=1 << 20)
        for b in bufs:
            await disp.encode(sinfo, codec, b)
        st = disp.dump()
        await disp.stop()
        return st

    st = run(main())
    n_buckets = len({bucket_stripes(s) for s in sizes})  # 1..64 -> 7
    misses = _misses() - before
    assert 1 <= misses <= n_buckets, (
        f"{misses} jit signatures for {len(sizes)} sizes "
        f"(bucket count {n_buckets})"
    )
    assert set(int(b) for b in st["buckets"]) <= \
        {bucket_stripes(s) for s in sizes}
    assert st["totals"]["pad_stripes"] > 0  # bucketing actually padded


# -- perf-counter wiring -----------------------------------------------------


def test_perf_counters_and_histogram_land():
    from ceph_tpu.common.perf_counters import (
        PerfCounters, PerfHistogramAxis,
    )

    pec = PerfCounters("ec")
    pec.add_gauge("encode_gbps").add_gauge("decode_gbps")
    pec.add_counter("dispatch_batches").add_counter("dispatch_ops")
    pec.add_counter("dispatch_cancelled")
    pec.add_counter("dispatch_flush_size")
    pec.add_counter("dispatch_flush_window")
    pec.add_counter("dispatch_flush_stop")
    pec.add_counter("dispatch_pad_stripes")
    pec.add_counter("dispatch_pad_bytes")
    pec.add_avg("dispatch_occupancy")
    pec.add_histogram(
        "dispatch_batch_size_histogram",
        axes=[PerfHistogramAxis("ops", min=1.0, buckets=12)],
    )
    # per-lane split (ISSUE 8): the device lane feeds its own series
    pec.add_counter("dispatch_batches_device")
    pec.add_counter("dispatch_ops_device")
    pec.add_counter("dispatch_pad_stripes_device")
    pec.add_counter("dispatch_pad_bytes_device")
    pec.add_avg("dispatch_occupancy_device")
    pec.add_histogram(
        "dispatch_batch_size_device_histogram",
        axes=[PerfHistogramAxis("ops", min=1.0, buckets=12)],
    )
    sinfo, codec = _sinfo(2), _codec()
    bufs = _bufs(sinfo, [3, 5], seed=9)

    async def main():
        disp = ECDispatcher(perf=pec, window=0.005, max_stripes=8)
        await asyncio.gather(
            *[disp.encode(sinfo, codec, b) for b in bufs]
        )
        await disp.stop()

    run(main())
    d = pec.dump()
    assert d["dispatch_batches"] == 1
    assert d["dispatch_ops"] == 2
    assert d["dispatch_flush_size"] == 1
    assert d["dispatch_pad_stripes"] == 0  # 3+5 = 8, an exact bucket
    assert d["dispatch_occupancy"]["avgcount"] == 1
    assert d["dispatch_batch_size_histogram"]["histogram"]["count"] == 1
    # the per-lane split attributes the launch to the device route
    assert d["dispatch_batches_device"] == 1
    assert d["dispatch_ops_device"] == 2
    assert d["dispatch_occupancy_device"]["avgcount"] == 1
    assert (d["dispatch_batch_size_device_histogram"]["histogram"]
            ["count"] == 1)


@pytest.mark.parametrize("op", ["encode", "decode"])
@pytest.mark.parametrize("k,m", [(2, 1), (4, 2), (8, 3)])
def test_default_dispatcher_serves_from_device_lane(k, m, op):
    """On the CPU backend, with nothing patched, a default dispatcher
    serves concurrent encodes and a reconstruct from its device lane —
    the lane a chip serves — with the bytes of the codec's host oracle."""
    sinfo, codec = _sinfo(k), _codec(k, m)
    bufs = _bufs(sinfo, [1, 2, 3], seed=11 + k)
    lost = list(range(m))  # m data shards: a full-strength reconstruct
    chunk_maps = [
        {s: v for s, v in ec_util.encode(sinfo, codec, b).items()
         if s not in lost}
        for b in bufs
    ]

    async def main():
        disp = ECDispatcher()
        if op == "encode":
            outs = await asyncio.gather(
                *[disp.encode(sinfo, codec, b) for b in bufs])
        else:
            outs = await asyncio.gather(
                *[disp.decode_concat(sinfo, codec, c) for c in chunk_maps])
        st = disp.dump()
        await disp.stop()
        return outs, st

    outs, st = run(main())
    totals = st["totals"]
    assert totals["lanes"]["device"]["batches"] >= 1
    assert totals["lanes"]["device"]["ops"] == len(bufs)
    assert totals["fallback_direct"] == 0
    assert not any("native" in key for key in totals)  # no host lane
    for b, c, got in zip(bufs, chunk_maps, outs):
        data = np.ascontiguousarray(
            b.reshape(-1, k, CS).transpose(1, 0, 2)).reshape(k, -1)
        if op == "encode":
            parity = codec.encode_chunks_host(data)
            for s in range(k):
                assert np.array_equal(np.asarray(got[s]), data[s])
            for j in range(m):
                assert np.array_equal(np.asarray(got[k + j]), parity[j])
            continue
        present = sorted(c)
        rebuilt = codec.decode_chunks_host(
            present, np.stack([c[s] for s in present]), lost)
        assert np.array_equal(rebuilt, data[lost])
        assert bytes(got) == b.tobytes()


# -- OSD integration ---------------------------------------------------------


def test_osd_routes_ec_writes_through_dispatcher():
    """An EC write on a live mini-cluster lands dispatcher activity on
    the primary's ec counters."""
    from ceph_tpu.rados import MiniCluster

    async def main():
        cluster = MiniCluster(n_osds=4)
        await cluster.start()
        try:
            cl = await cluster.client()
            await cl.create_pool("ec", "erasure")
            io = cl.io_ctx("ec")
            payload = bytes(range(256)) * 64  # 16 KiB
            await io.write_full("obj", payload)
            assert await io.read("obj") == payload
            served = 0
            for osd in cluster.osds.values():
                pec = osd.perf.get("ec")
                served += pec.get("dispatch_ops")
                # admin surface serves the dispatcher dump
                assert "totals" in osd.ec_dispatch.dump()
            assert served > 0, "no EC op went through the dispatcher"
        finally:
            await cluster.stop()

    run(main())


def test_cluster_ec_ops_ride_the_device_lane():
    """Tier-1's default path is the chip's path: on the CPU backend,
    with nothing patched, an EC pool's write_full and a degraded read
    are served by the dispatcher's device lane, never the host
    fallback, and read back byte for byte."""
    from ceph_tpu.rados import MiniCluster

    def device_ops(cluster):
        ops = 0
        for osd in cluster.osds.values():
            totals = osd.ec_dispatch.dump()["totals"]
            assert totals["fallback_direct"] == 0
            assert totals["failovers"] == 0
            ops += totals["lanes"]["device"]["ops"]
        return ops

    async def main():
        async with MiniCluster(n_osds=4) as cluster:
            cl = await cluster.client()
            await cl.create_pool("ec", "erasure")
            io = cl.io_ctx("ec")
            payload = np.random.default_rng(12).integers(
                0, 256, size=48 << 10, dtype=np.uint8).tobytes()
            await io.write_full("obj", payload)
            written = device_ops(cluster)
            assert written > 0, "the write was not served by the device lane"
            pool = cl.osdmap.lookup_pool("ec")
            _, acting, primary = cl.osdmap.object_to_acting("obj", pool.id)
            # a data shard (the default profile is k=2) other than the
            # primary's: the read must reconstruct it
            victim = next(o for o in acting[:2] if o != primary)
            await cluster.kill_osd(victim)
            await cluster.wait_for_osd_down(victim)
            assert await io.read("obj") == payload
            assert device_ops(cluster) > written, (
                "the degraded read was not served by the device lane")

    run(main())
