"""The mesh dispatcher lane (ISSUE 8): multi-chip EC as a first-class
route through the cross-op microbatch dispatcher — byte identity vs the
per-op oracle across bucket boundaries / uneven mesh remainders / w=16
codecs / mid-batch cancellation, the prime-k reconstruct fallback, the
mesh-lane anti-compile-storm gate (<= #buckets x #mesh-slices
compiles), per-lane observability, and the live fault matrix (injected
device loss mid-mesh-batch replays on the host fallback with zero
failed client ops)."""

import asyncio

import numpy as np
import pytest

import jax

from ceph_tpu.models.matrix_codec import MatrixErasureCode
from ceph_tpu.ops import matrices as mx
from ceph_tpu.ops.profiler import profiler
from ceph_tpu.osd import ec_util
from ceph_tpu.osd.ec_dispatch import (
    ECDispatcher,
    bucket_stripes,
    bucket_stripes_aligned,
)
from ceph_tpu.parallel.engine import MeshEcEngine


def run(coro):
    return asyncio.run(coro)


CS = 512  # chunk_size; stripe_width = k * CS


def _sinfo(k: int, cs: int = CS) -> ec_util.StripeInfo:
    return ec_util.StripeInfo(stripe_width=cs * k, chunk_size=cs)


def _codec(k: int = 2, m: int = 1, w: int = 8) -> MatrixErasureCode:
    if w == 16:
        return MatrixErasureCode(k, m, 16, mx.rs_vandermonde(k, m, 16))
    return MatrixErasureCode(k, m, 8, mx.isa_rs_vandermonde(k, m))


def _bufs(sinfo, sizes, seed=0):
    rng = np.random.default_rng(seed)
    return [
        rng.integers(0, 256, size=(s * sinfo.stripe_width,),
                     dtype=np.uint8)
        for s in sizes
    ]


_ENGINES: dict = {}


def _engine(n: int | None = None) -> MeshEcEngine:
    """One shared engine per device count: the tests exercise many
    overlapping (codec, shape) programs, and a fresh engine per test
    would re-jit every one of them — pure CI wall time on a throttled
    box, no extra coverage."""
    eng = _ENGINES.get(n)
    if eng is None:
        devs = jax.devices()
        eng = _ENGINES[n] = MeshEcEngine(
            devices=devs[:n] if n else devs
        )
    return eng


def _assert_same_shards(got, want):
    assert set(got) == set(want)
    for s in want:
        assert np.array_equal(np.asarray(got[s]), np.asarray(want[s])), (
            f"shard {s} diverged"
        )


# -- aligned bucketing --------------------------------------------------------


def test_bucket_stripes_aligned_rule():
    # quantum 8 (an 8-chip mesh): units bucket to powers of two
    assert [bucket_stripes_aligned(s, 8) for s in
            (1, 8, 9, 16, 17, 33)] == [8, 8, 16, 16, 32, 64]
    # bucketing off still mesh-aligns (shards must stay balanced)
    assert [bucket_stripes_aligned(s, 8, bucket=False) for s in
            (1, 8, 9, 17)] == [8, 8, 16, 24]
    # quantum 1 degenerates to the plain power-of-two bucket
    assert all(
        bucket_stripes_aligned(s, 1) == bucket_stripes(s)
        for s in range(1, 40)
    )


# -- mesh-lane byte identity --------------------------------------------------


class TestMeshLaneBytes:
    """Dispatcher mesh-lane outputs bit-identical to the per-op oracle
    (ec_util) across bucket boundaries, uneven ΣS % mesh_size
    remainders, and w=16 codecs."""

    @pytest.mark.parametrize("sizes", [
        [1, 2],          # ΣS=3: uneven remainder vs any mesh size
        [5, 3],          # ΣS=8: snug on an 8-chip mesh
        [7, 6, 4],       # ΣS=17: crosses the 16-stripe bucket boundary
    ])
    def test_encode_identical_mixed_sizes(self, sizes):
        sinfo, codec = _sinfo(2), _codec(2, 1)
        bufs = _bufs(sinfo, sizes, seed=21)
        eng = _engine()

        async def main():
            disp = ECDispatcher(window=0.005, max_stripes=1 << 20,
                                mesh_engine=eng)
            outs = await asyncio.gather(
                *[disp.encode(sinfo, codec, b) for b in bufs]
            )
            st = disp.dump()
            await disp.stop()
            return outs, st

        outs, st = run(main())
        assert st["totals"]["lanes"]["mesh"]["batches"] >= 1
        assert st["totals"]["lanes"]["device"]["batches"] == 0
        # every mesh-lane launch was mesh-size aligned
        quantum = np.prod(eng.mesh_key(2))
        assert all(int(b) % quantum == 0 for b in st["mesh_buckets"])
        for b, got in zip(bufs, outs):
            _assert_same_shards(got, ec_util.encode(sinfo, codec, b))

    def test_decode_identical_through_mesh_lane(self):
        k, m = 2, 1
        sinfo, codec = _sinfo(k), _codec(k, m)
        bufs = _bufs(sinfo, [3, 5], seed=22)
        shard_sets = []
        for b in bufs:
            full = ec_util.encode(sinfo, codec, b)
            shard_sets.append(
                {s: np.asarray(v) for s, v in full.items() if s != 0}
            )

        async def main():
            disp = ECDispatcher(window=0.005, max_stripes=1 << 20,
                                mesh_engine=_engine())
            outs = await asyncio.gather(
                *[disp.decode_concat(sinfo, codec, sv)
                  for sv in shard_sets]
            )
            st = disp.dump()
            await disp.stop()
            return outs, st

        outs, st = run(main())
        assert st["totals"]["lanes"]["mesh"]["batches"] >= 1
        for b, got in zip(bufs, outs):
            assert bytes(got) == b.tobytes()

    def test_decode_without_missing_rows_skips_mesh(self):
        """All wanted rows present -> no reconstruct -> the mesh lane
        does not apply (the old router's gate, kept)."""
        sinfo, codec = _sinfo(2), _codec(2, 1)
        (buf,) = _bufs(sinfo, [2], seed=23)
        full = ec_util.encode(sinfo, codec, buf)
        present = {s: np.asarray(v) for s, v in full.items()}

        async def main():
            disp = ECDispatcher(window=0.001, max_stripes=1 << 20,
                                mesh_engine=_engine())
            out = await disp.decode_concat(sinfo, codec, present)
            st = disp.dump()
            await disp.stop()
            return out, st

        out, st = run(main())
        assert bytes(out) == buf.tobytes()
        assert st["totals"]["lanes"]["mesh"]["batches"] == 0

    def test_w16_codec_identical(self):
        k, m = 4, 2
        sinfo, codec = _sinfo(k), _codec(k, m, w=16)
        bufs = _bufs(sinfo, [2, 3], seed=24)
        eng = _engine()
        assert eng.routes(sinfo, codec)

        async def main():
            disp = ECDispatcher(window=0.005, max_stripes=1 << 20,
                                mesh_engine=eng)
            outs = await asyncio.gather(
                *[disp.encode(sinfo, codec, b) for b in bufs]
            )
            # degraded read through the mesh reconstruct
            sv = {s: np.asarray(v) for s, v in outs[0].items() if s > 1}
            dec = await disp.decode_concat(sinfo, codec, sv)
            await disp.stop()
            return outs, dec

        outs, dec = run(main())
        for b, got in zip(bufs, outs):
            _assert_same_shards(got, ec_util.encode(sinfo, codec, b))
        assert bytes(dec) == bufs[0].tobytes()

    def test_mesh_lane_outranks_device_lane(self):
        """osd_ec_mesh is an explicit operator opt-in: a request the
        mesh routes never takes the single-device lane."""
        sinfo, codec = _sinfo(2), _codec(2, 1)
        (buf,) = _bufs(sinfo, [2], seed=25)

        async def main():
            disp = ECDispatcher(window=0.001, max_stripes=1 << 20,
                                mesh_engine=_engine())
            out = await disp.encode(sinfo, codec, buf)
            st = disp.dump()
            await disp.stop()
            return out, st

        out, st = run(main())
        assert st["totals"]["lanes"]["mesh"]["batches"] == 1
        assert st["totals"]["lanes"]["device"]["batches"] == 0
        _assert_same_shards(out, ec_util.encode(sinfo, codec, buf))

    def test_unaligned_chunk_size_stays_off_the_mesh(self):
        eng = _engine()
        codec = _codec(2, 1)
        assert eng.routes(_sinfo(2), codec)
        assert not eng.routes(
            ec_util.StripeInfo(stripe_width=12, chunk_size=6), codec
        )


# -- mid-batch cancellation on the mesh route ---------------------------------


def test_cancelled_waiter_does_not_wedge_mesh_batch():
    sinfo, codec = _sinfo(2), _codec(2, 1)
    buf_a, buf_b = _bufs(sinfo, [1, 4], seed=26)

    async def main():
        disp = ECDispatcher(window=30.0, max_stripes=4,
                            mesh_engine=_engine())
        task_a = asyncio.ensure_future(disp.encode(sinfo, codec, buf_a))
        await asyncio.sleep(0)  # let A enqueue
        task_a.cancel()
        await asyncio.sleep(0)  # let the cancellation land on A
        out_b = await disp.encode(sinfo, codec, buf_b)  # size-flushes
        with pytest.raises(asyncio.CancelledError):
            await task_a
        st = disp.dump()
        await disp.stop()
        return out_b, st

    out_b, st = run(main())
    assert st["totals"]["cancelled"] == 1
    assert st["totals"]["lanes"]["mesh"]["ops"] == 1  # only B launched
    _assert_same_shards(out_b, ec_util.encode(sinfo, codec, buf_b))


# -- prime-k reconstruct fallback ---------------------------------------------


class TestPrimeKFallback:
    """gcd(k, n_devices) == 1: the 'shard' axis degenerates to 1 and
    the reconstruct must gather over 'pg' instead of silently
    serializing (ISSUE 8 satellite; k=7 on 4 devices)."""

    def test_k7_on_4_devices_reconstructs(self):
        k, m = 7, 2
        eng = _engine(4)
        _mesh, pg, shard = eng.mesh_for(k)
        assert (pg, shard) == (4, 1)
        assert eng.reconstruct_axis(k) == "pg"
        codec = _codec(k, m)
        sinfo = _sinfo(k)
        (buf,) = _bufs(sinfo, [3], seed=27)
        full = ec_util.encode(sinfo, codec, buf)
        # two erasures: one data, one parity survivor mix
        surv = {s: np.asarray(v) for s, v in full.items()
                if s not in (0, 8)}
        host = ec_util.decode_concat(sinfo, codec, surv)
        mesh = eng.decode_concat(sinfo, codec, surv)
        assert bytes(host) == bytes(mesh) == buf.tobytes()

    def test_k7_encode_matches_oracle(self):
        k, m = 7, 2
        eng = _engine(4)
        codec, sinfo = _codec(k, m), _sinfo(k)
        (buf,) = _bufs(sinfo, [5], seed=28)
        _assert_same_shards(
            eng.encode(sinfo, codec, buf),
            ec_util.encode(sinfo, codec, buf),
        )

    def test_even_k_keeps_shard_axis(self):
        eng = _engine(4)
        assert eng.reconstruct_axis(8) == "shard"


# -- the anti-compile-storm gate on the mesh lane -----------------------------


def test_mesh_size_sweep_jit_misses_bounded():
    """50 distinct op sizes through the mesh lane cost at most
    #buckets x #mesh-slices mesh_encode jit signatures — the
    mesh_size x bucket alignment rule (tier-1, acceptance #3)."""
    # a geometry no other test uses, so profiler signatures are fresh
    k, m = 3, 2
    sinfo = ec_util.StripeInfo(stripe_width=256 * k, chunk_size=256)
    codec = _codec(k, m)
    sizes = list(range(1, 51))
    bufs = _bufs(sinfo, sizes, seed=29)
    eng = _engine()
    quantum = int(np.prod(eng.mesh_key(k)))

    def _misses():
        e = profiler().dump().get("engines", {}).get("mesh_encode")
        return e["jit_cache"]["misses"] if e else 0

    before = _misses()

    async def main():
        # window 0 + per-op awaits: every op launches its own batch, so
        # the SWEEP (not coalescing) is what exercises the bucket table
        disp = ECDispatcher(window=0.0, max_stripes=1 << 20,
                            mesh_engine=eng)
        for b in bufs:
            await disp.encode(sinfo, codec, b)
        st = disp.dump()
        await disp.stop()
        return st

    st = run(main())
    n_buckets = len({
        bucket_stripes_aligned(s, quantum) for s in sizes
    })
    mesh_slices = 1  # one codec, one geometry -> one (pg, shard) slice
    misses = _misses() - before
    assert 1 <= misses <= n_buckets * mesh_slices, (
        f"{misses} mesh jit signatures for {len(sizes)} sizes "
        f"(bound {n_buckets} x {mesh_slices})"
    )
    assert all(int(b) % quantum == 0 for b in st["mesh_buckets"])
    assert st["totals"]["lanes"]["mesh"]["pad_stripes"] > 0


# -- profiler visibility ------------------------------------------------------


def test_mesh_programs_distinct_in_kernel_profile():
    k, m = 2, 1
    sinfo, codec = _sinfo(k), _codec(k, m)
    (buf,) = _bufs(sinfo, [4], seed=30)
    eng = _engine()
    out = eng.encode(sinfo, codec, buf)
    surv = {s: np.asarray(v) for s, v in out.items() if s != 0}
    eng.decode_concat(sinfo, codec, surv)
    dump = profiler().dump()
    assert "mesh_encode" in dump["engines"]
    assert "mesh_reconstruct" in dump["engines"]
    enc = dump["engines"]["mesh_encode"]
    assert enc["calls"] >= 1
    # the compile is visible — AOT-split (counted apart from calls)
    # or folded into the first call, either way a recorded miss
    assert enc["jit_cache"]["misses"] >= 1
    # the prefix filter serves the mesh family alone (bench mesh phase)
    only = profiler().dump(prefix="mesh")["engines"]
    assert only and all(n.startswith("mesh") for n in only)
    # ...and the per-engine histograms ride dump_histograms like every
    # other engine family
    assert "mesh_encode" in profiler().dump_histograms()


def test_gather_probe_reports_own_engine():
    eng = _engine()
    n = len(eng.devices)
    eng.probe_gather(8, 4 * n * 8)
    assert "mesh_gather" in profiler().dump()["engines"]


# -- mesh-lane failover (deterministic, dispatcher level) ---------------------


def test_mesh_lane_failover_replays_bit_identical():
    """A fatal device error mid-mesh-batch replays the whole batch on
    the host fallback (no waiter sees the error, bytes identical) and
    the supervisor attributes the fatal to the mesh lane."""
    from ceph_tpu.osd.ec_failover import EngineSupervisor

    sinfo, codec = _sinfo(2), _codec(2, 1)
    bufs = _bufs(sinfo, [2, 3], seed=31)
    sup = EngineSupervisor(enabled=True, probe_interval=30.0)

    async def main():
        disp = ECDispatcher(window=0.005, max_stripes=1 << 20,
                            mesh_engine=_engine(), supervisor=sup)
        disp.inject_engine_failure = 1  # every device launch dies
        outs = await asyncio.gather(
            *[disp.encode(sinfo, codec, b) for b in bufs]
        )
        st = disp.dump()
        await disp.stop()
        return outs, st

    outs, st = run(main())
    assert st["totals"]["failovers"] >= 1
    assert st["totals"]["replayed_ops"] == 2
    for b, got in zip(bufs, outs):
        _assert_same_shards(got, ec_util.encode(sinfo, codec, b))
    assert sup.last_failure_lane == "mesh"
    assert sup.totals["mesh_fatal_errors"] >= 1


# -- live fault matrix: device loss mid-mesh-batch ----------------------------


class TestMeshFaultMatrix:
    def test_injected_loss_mid_mesh_batch_zero_failed_ops(self):
        """ISSUE 8 acceptance: injected device loss mid-mesh-batch
        replays on the host fallback with ZERO failed client ops on a
        live MiniCluster; the supervisor attributes the trip to the
        mesh lane and the canary re-promotes the mesh after the
        injection lifts."""
        from ceph_tpu.osd.ec_failover import HEALTHY
        from ceph_tpu.rados import MiniCluster

        async def main():
            async with MiniCluster(
                n_osds=4,
                config_overrides={
                    "osd_ec_mesh": True,
                    "osd_ec_probe_interval": 0.05,
                },
            ) as cluster:
                cl = await cluster.client()
                await cl.create_pool("ec", "erasure")  # isa k2m1
                io = cl.io_ctx("ec")
                model: dict[str, bytes] = {}

                async def storm(round_no: int, n: int = 8):
                    async def put(i):
                        data = bytes([round_no, i]) * (400 + 97 * i)
                        await io.write_full(f"o{i}", data)
                        model[f"o{i}"] = data
                    await asyncio.gather(*[put(i) for i in range(n)])

                def counters(key):
                    return sum(
                        osd.perf.get("ec").get(key)
                        for osd in cluster.osds.values()
                    )

                await storm(0)  # baseline: the mesh lane serves
                assert counters("mesh_batches") > 0
                assert counters("mesh_encode_calls") > 0

                for osd in cluster.osds.values():
                    osd.config.set("ec_inject_engine_failure", 1)
                await storm(1)  # NO op may fail
                assert counters("engine_failovers") > 0
                assert counters("replayed_ops") > 0
                # the replayed bytes read back bit-identical
                for name, want in model.items():
                    assert await io.read(name) == want, name
                # (lane attribution is pinned deterministically by
                # test_mesh_lane_failover_replays_bit_identical — here
                # RMW read-decodes on the device lane race the mesh
                # encodes for the breaker's "last failure" slot)
                # lift the injection: the canary probes the lane that
                # tripped and re-promotes
                for osd in cluster.osds.values():
                    osd.config.set("ec_inject_engine_failure", 0)
                async with asyncio.timeout(20):
                    while any(
                        osd.ec_supervisor.state != HEALTHY
                        for osd in cluster.osds.values()
                    ):
                        await asyncio.sleep(0.05)
                # recovered: a fresh storm runs clean on the mesh lane
                before = counters("engine_failovers")
                mesh_before = counters("mesh_batches")
                await storm(2)
                assert counters("engine_failovers") == before
                assert counters("mesh_batches") > mesh_before
                for name, want in model.items():
                    assert await io.read(name) == want, name

        run(main())
