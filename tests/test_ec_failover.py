"""Accelerator fault domain (ISSUE 7 acceptance): EC engine failover,
circuit breaker, launch deadline, and the device-fault injection matrix.

Pins the whole contract:
- failure classification: device-lost/XLA/OOM/compile errors are fatal
  (trip + replay), data-shape errors surface to the caller;
- host fallback engines are bit-identical to the device engines
  (matrix w=8/w=16 and bitmatrix codecs);
- a fatal error mid-batch replays the in-flight batch on the fallback —
  no waiter ever sees a device error — and advances the breaker
  HEALTHY -> SUSPECT -> TRIPPED;
- while TRIPPED, requests route around the device, the QoS scheduler
  squeezes background pacing to reservation, and the canary probe
  re-promotes once the fault lifts;
- a HUNG launch (ec_inject_launch_hang) fails over at
  osd_ec_launch_deadline and keeps the wedged thread on the
  HeartbeatMap clock;
- the fault matrix on a live MiniCluster: with injection firing
  mid-batch (error and hang variants) no client op fails, bytes stay
  identical, ec.engine_failovers increments, ACCEL_DEGRADED raises at
  the mgr and clears after re-promotion.
"""

import asyncio
import time

import numpy as np
import pytest

from ceph_tpu.common.heartbeat_map import HeartbeatMap
from ceph_tpu.models.matrix_codec import (
    BitmatrixErasureCode,
    EngineFault,
    MatrixErasureCode,
    classify_engine_error,
)
from ceph_tpu.ops import matrices as mx
from ceph_tpu.osd import ec_util
from ceph_tpu.osd.ec_dispatch import ECDispatcher
from ceph_tpu.osd.ec_failover import (
    HEALTHY,
    PROBING,
    SUSPECT,
    TRIPPED,
    EngineSupervisor,
)


def run(coro):
    return asyncio.run(coro)


def _sinfo(k: int, cs: int = 512) -> ec_util.StripeInfo:
    return ec_util.StripeInfo(stripe_width=cs * k, chunk_size=cs)


def _codec(k: int = 2, m: int = 1) -> MatrixErasureCode:
    return MatrixErasureCode(k, m, 8, mx.isa_rs_vandermonde(k, m))


def _buf(sinfo, stripes: int, seed: int = 0) -> np.ndarray:
    rng = np.random.default_rng(seed)
    return rng.integers(0, 256, size=(stripes * sinfo.stripe_width,),
                        dtype=np.uint8)


def _same_shards(got, want):
    assert set(got) == set(want)
    for s in want:
        assert np.array_equal(np.asarray(got[s]), np.asarray(want[s])), s


# -- failure classification ---------------------------------------------------


class TestClassification:
    def test_data_errors_surface(self):
        for exc in (ValueError("shape"), TypeError("t"),
                    IOError("cannot decode: 1 chunks available"),
                    KeyError("k"), IndexError("i")):
            assert classify_engine_error(exc) == "data", exc

    def test_device_errors_are_fatal(self):
        class XlaRuntimeError(RuntimeError):
            """The jaxlib runtime error shape (matched by NAME, so the
            real class needs no import here)."""

        for exc in (XlaRuntimeError("INTERNAL: device lost"),
                    XlaRuntimeError("RESOURCE_EXHAUSTED: OOM"),
                    EngineFault("injected"),
                    RuntimeError("compile failed"),
                    MemoryError()):
            assert classify_engine_error(exc) == "fatal", exc


# -- host fallback bit-identity ----------------------------------------------


class TestHostFallbackEngine:
    def test_matrix_w8_encode_decode_identical(self):
        sinfo, codec = _sinfo(2), _codec()
        buf = _buf(sinfo, 5, seed=1)
        want = ec_util.encode(sinfo, codec, buf)
        _same_shards(ec_util.encode_fallback(sinfo, codec, buf), want)
        chunks = {1: want[1], 2: want[2]}  # degraded: shard 0 missing
        assert bytes(
            ec_util.decode_concat_fallback(sinfo, codec, chunks)
        ) == bytes(ec_util.decode_concat(sinfo, codec, chunks))

    def test_matrix_w16_host_oracle_identical(self):
        c = MatrixErasureCode(3, 2, 16, mx.rs_vandermonde(3, 2, 16))
        rng = np.random.default_rng(2)
        data = rng.integers(0, 256, size=(3, 512), dtype=np.uint8)
        want = np.asarray(c.encode_chunks(data))
        assert np.array_equal(want, c.encode_chunks_host(data))
        full = np.concatenate([data, want], axis=0)
        present = [1, 2, 3, 4]
        got_dev = np.asarray(c.decode_chunks(present, full[present], [0]))
        got_host = np.asarray(
            c.decode_chunks_host(present, full[present], [0])
        )
        assert np.array_equal(got_dev, got_host)

    def test_bitmatrix_host_oracle_identical(self):
        bc = BitmatrixErasureCode(2, 1, 4, mx.cauchy_good(2, 1, 4), 8)
        bs = ec_util.StripeInfo(stripe_width=2 * 64, chunk_size=64)
        buf = _buf(bs, 3, seed=3)
        want = ec_util.encode(bs, bc, buf)
        _same_shards(ec_util.encode_fallback(bs, bc, buf), want)
        chunks = {1: want[1], 2: want[2]}
        assert bytes(
            ec_util.decode_concat_fallback(bs, bc, chunks)
        ) == bytes(ec_util.decode_concat(bs, bc, chunks))

    def test_fallback_rejects_bad_shapes_like_the_device_path(self):
        sinfo, codec = _sinfo(2), _codec()
        with pytest.raises(ValueError):
            ec_util.encode_fallback(sinfo, codec, b"x" * 100)

    def test_lrc_host_oracle_identical_and_device_free(self):
        """A layered LRC codec must replay on its inner HOST oracles —
        a fallback that re-entered the device jit would re-raise the
        fault it is recovering from."""
        from ceph_tpu.models.registry import instance

        c = instance().factory("lrc", {
            "k": "4", "m": "2", "l": "3",
            "crush-failure-domain": "host",
        })
        rng = np.random.default_rng(4)
        data = rng.integers(0, 256, size=(4, 256), dtype=np.uint8)
        want = np.asarray(c.encode_chunks(data))
        assert np.array_equal(want, c.encode_chunks_host(data))
        n = c.get_chunk_count()
        full = np.zeros((n, 256), dtype=np.uint8)
        full[c.chunk_mapping] = data
        data_pos = set(c.chunk_mapping)
        full[[i for i in range(n) if i not in data_pos]] = want
        missing = [c.chunk_mapping[0]]
        present = [i for i in range(n) if i not in missing]
        got_dev = np.asarray(c.decode_chunks(present, full[present],
                                             missing))
        got_host = np.asarray(
            c.decode_chunks_host(present, full[present], missing)
        )
        assert np.array_equal(got_dev, got_host)
        # ...and the host route really never enters a device engine
        from ceph_tpu.models import matrix_codec as mc

        def no_device(*a, **kw):
            raise AssertionError("host oracle entered the jit engine")

        real = mc._jit_matmul
        mc._jit_matmul = no_device
        try:
            c.encode_chunks_host(data)
            c.decode_chunks_host(present, full[present], missing)
        finally:
            mc._jit_matmul = real

    def test_shec_host_oracle_uses_the_span_solve(self):
        """SHEC is non-MDS: its host reconstruct must run the SAME span
        solve as the device path, not the inherited MDS recovery
        matrix."""
        from ceph_tpu.models.registry import instance

        c = instance().factory("shec", {"k": "4", "m": "3", "c": "2"})
        rng = np.random.default_rng(5)
        data = rng.integers(0, 256, size=(4, 256), dtype=np.uint8)
        want = np.asarray(c.encode_chunks(data))
        assert np.array_equal(want, c.encode_chunks_host(data))
        full = np.concatenate([data, want], axis=0)
        present = [1, 2, 3, 4, 5, 6]
        got_dev = np.asarray(c.decode_chunks(present, full[present], [0]))
        got_host = np.asarray(
            c.decode_chunks_host(present, full[present], [0])
        )
        assert np.array_equal(got_dev, got_host)


# -- dispatcher failover ------------------------------------------------------


class TestDispatcherFailover:
    def test_fatal_error_mid_batch_replays_no_waiter_fails(self):
        """The acceptance core: injection fires mid-batch, every waiter
        still gets oracle-identical bytes; failovers/replayed_ops
        count; the breaker walks HEALTHY -> SUSPECT -> TRIPPED."""
        sinfo, codec = _sinfo(2), _codec()
        bufs = [_buf(sinfo, s, seed=s) for s in (2, 3)]
        wants = [ec_util.encode(sinfo, codec, b) for b in bufs]

        async def main():
            sup = EngineSupervisor(probe_interval=30.0)  # no re-promote
            disp = ECDispatcher(window=0.005, max_stripes=1 << 20,
                                supervisor=sup)
            disp.inject_engine_failure = 1
            outs = await asyncio.gather(
                *[disp.encode(sinfo, codec, b) for b in bufs]
            )
            assert sup.state == SUSPECT  # first fatal: half-open
            out2 = await disp.encode(sinfo, codec, bufs[0])
            assert sup.state == TRIPPED  # second within the window
            st = disp.dump()
            # tripped: the fallback-direct lane serves (no device call,
            # hence no further failover events)
            out3 = await disp.encode(sinfo, codec, bufs[1])
            st2 = disp.dump()
            await disp.stop()
            return outs, out2, out3, st, st2

        outs, out2, out3, st, st2 = run(main())
        for got, want in zip(outs, wants):
            _same_shards(got, want)
        _same_shards(out2, wants[0])
        _same_shards(out3, wants[1])
        assert st["totals"]["failovers"] == 2
        assert st["totals"]["replayed_ops"] == 3  # 2 coalesced + 1
        assert st2["totals"]["failovers"] == 2  # lane change, no new
        assert st2["totals"]["fallback_direct"] == 1
        assert st2["engine_health"]["state"] == "tripped"

    def test_data_error_surfaces_and_breaker_stays_closed(self):
        """A shape bug is the CALLER's: it must raise (not replay) and
        must not move the breaker."""
        sinfo, codec = _sinfo(2), _codec()
        buf = _buf(sinfo, 2, seed=5)

        def bad_encode(*a, **kw):
            raise ValueError("batch alignment")

        async def main():
            sup = EngineSupervisor(probe_interval=30.0)
            disp = ECDispatcher(window=0.0, max_stripes=1 << 20,
                                supervisor=sup)
            with pytest.raises(ValueError):
                real = ec_util.encode
                ec_util.encode = bad_encode
                try:
                    await disp.encode(sinfo, codec, buf)
                finally:
                    ec_util.encode = real
            assert sup.state == HEALTHY
            assert sup.totals["data_errors"] == 1
            assert disp._totals["failovers"] == 0
            await disp.stop()

        run(main())

    def test_live_disable_restores_fail_fast(self):
        """osd_ec_engine_failover=false (live): fatal errors surface to
        the waiters — the pre-failover contract."""
        sinfo, codec = _sinfo(2), _codec()
        buf = _buf(sinfo, 2, seed=6)

        async def main():
            sup = EngineSupervisor(enabled=False, probe_interval=30.0)
            disp = ECDispatcher(window=0.0, max_stripes=1 << 20,
                                supervisor=sup)
            disp.inject_engine_failure = 1
            with pytest.raises(EngineFault):
                await disp.encode(sinfo, codec, buf)
            await disp.stop()

        run(main())

    def test_live_disable_while_tripped_clears_degraded(self):
        """Disabling the failover while TRIPPED must restore the
        pre-failover world completely: state back to HEALTHY (gauge
        clears -> ACCEL_DEGRADED drops) and the QoS capacity squeeze
        released — a breaker the operator turned off must not keep
        throttling the cluster."""
        sinfo, codec = _sinfo(2), _codec()

        async def main():
            degraded = []
            sup = EngineSupervisor(probe_interval=30.0,
                                   on_degraded=degraded.append)
            disp = ECDispatcher(window=0.0, max_stripes=1 << 20,
                                supervisor=sup)
            disp.inject_engine_failure = 1
            for seed in (20, 21):  # two fatals: SUSPECT then TRIPPED
                await disp.encode(sinfo, codec, _buf(sinfo, 2, seed=seed))
            assert sup.state == TRIPPED and degraded == [True]
            sup.set_enabled(False)
            assert sup.state == HEALTHY
            assert degraded == [True, False]
            # fail-fast contract is back, and the inline lanes follow
            with pytest.raises(EngineFault):
                await disp.encode(sinfo, codec, _buf(sinfo, 2, seed=22))
            await disp.stop()

        run(main())

    def test_inline_shutdown_lane_routes_around_a_tripped_device(self):
        """The _stopping inline path runs ON the event loop: with the
        breaker TRIPPED it must use the host fallback — an inline
        device call there would have no deadline, no watchdog pin, and
        would stall the heartbeat tasks themselves."""
        sinfo, codec = _sinfo(2), _codec()
        buf = _buf(sinfo, 2, seed=23)

        async def main():
            sup = EngineSupervisor(probe_interval=30.0)
            disp = ECDispatcher(window=0.0, max_stripes=1 << 20,
                                supervisor=sup)
            disp.inject_engine_failure = 1
            for seed in (24, 25):
                await disp.encode(sinfo, codec, _buf(sinfo, 2, seed=seed))
            assert sup.state == TRIPPED
            await disp.stop()  # the inline lane is now the ONLY lane

            def device_wedges(*a, **kw):
                raise AssertionError("tripped inline lane hit the device")

            real = ec_util.encode
            ec_util.encode = device_wedges
            try:
                out = await disp.encode(sinfo, codec, buf)
            finally:
                ec_util.encode = real
            want = ec_util.encode_fallback(sinfo, codec, buf)
            assert all(
                np.array_equal(np.asarray(out[s]), np.asarray(want[s]))
                for s in want
            )

        run(main())

    def test_fallback_failure_surfaces_the_fallback_error(
        self, monkeypatch
    ):
        """If the replay itself fails, THAT error reaches the waiters
        (it describes the actual state of the bytes)."""
        sinfo, codec = _sinfo(2), _codec()
        buf = _buf(sinfo, 2, seed=7)

        def bad_fallback(*a, **kw):
            raise ValueError("host engine also broken")

        monkeypatch.setattr(ec_util, "encode_fallback", bad_fallback)

        async def main():
            sup = EngineSupervisor(probe_interval=30.0)
            disp = ECDispatcher(window=0.0, max_stripes=1 << 20,
                                supervisor=sup)
            disp.inject_engine_failure = 1
            with pytest.raises(ValueError, match="host engine"):
                await disp.encode(sinfo, codec, buf)
            await disp.stop()

        run(main())

    def test_decode_replays_too(self):
        sinfo, codec = _sinfo(2), _codec()
        buf = _buf(sinfo, 4, seed=8)
        enc = ec_util.encode(sinfo, codec, buf)
        chunks = {1: enc[1], 2: enc[2]}

        async def main():
            sup = EngineSupervisor(probe_interval=30.0)
            disp = ECDispatcher(window=0.0, max_stripes=1 << 20,
                                supervisor=sup)
            disp.inject_engine_failure = 1
            out = await disp.decode_concat(sinfo, codec, chunks)
            st = disp.dump()
            await disp.stop()
            return out, st

        out, st = run(main())
        assert bytes(out) == buf.tobytes()
        assert st["totals"]["failovers"] == 1


# -- launch deadline + HeartbeatMap -------------------------------------------


class TestLaunchDeadline:
    def test_hang_fails_over_at_deadline_and_pins_watchdog(self):
        """ec_inject_launch_hang: the waiters fail over at
        osd_ec_launch_deadline (far before the hang resolves), the
        breaker trips, launch_deadline_timeouts counts, and the wedged
        thread stays pinned on the HeartbeatMap handle — grace blows
        while it is stuck, clears when it returns."""
        sinfo, codec = _sinfo(2), _codec()
        buf = _buf(sinfo, 2, seed=9)
        want = ec_util.encode(sinfo, codec, buf)

        async def main():
            hb = HeartbeatMap("t")
            handle = hb.add_worker("ec_device_launch", 0.3, 0.0)
            sup = EngineSupervisor(probe_interval=30.0)
            disp = ECDispatcher(window=0.0, max_stripes=1 << 20,
                                supervisor=sup, launch_deadline=0.2,
                                hb_handle=handle)
            disp.inject_launch_hang = 0.9
            t0 = time.monotonic()
            out = await disp.encode(sinfo, codec, buf)
            took = time.monotonic() - t0
            assert took < 0.7  # failed over at the deadline, not the hang
            assert sup.state == TRIPPED
            assert disp._totals["deadline_timeouts"] == 1
            # the wedged thread is still on the clock...
            assert handle.timeout != 0.0
            await asyncio.sleep(0.2)
            assert not hb.is_healthy()  # grace blown -> health warn
            # ...until it finally returns, which unpins it
            await asyncio.sleep(1.0)
            assert handle.timeout == 0.0
            assert hb.is_healthy()
            # the executor was respawned: the dispatcher still serves
            out2 = await disp.encode(sinfo, codec, buf)
            await disp.stop()
            return out, out2

        out, out2 = run(main())
        _same_shards(out, want)
        _same_shards(out2, want)


    def test_wedged_canaries_never_starve_the_fallback_lane(self):
        """Review finding: while the device stays wedged, every canary
        probe times out too — each one must respawn the executor like a
        launch does, or two wedged probes eat both worker slots and the
        fallback serving lane deadlocks (exactly the silent freeze the
        feature exists to prevent)."""
        sinfo, codec = _sinfo(2), _codec()
        buf = _buf(sinfo, 2, seed=11)
        want = ec_util.encode(sinfo, codec, buf)

        async def main():
            sup = EngineSupervisor(probe_interval=0.05)
            disp = ECDispatcher(window=0.0, max_stripes=1 << 20,
                                supervisor=sup, launch_deadline=0.1,
                                max_workers=2)
            disp.inject_launch_hang = 5.0  # wedged until far past test
            out = await disp.encode(sinfo, codec, buf)  # trips
            assert sup.state == TRIPPED
            # let several canaries wedge and time out
            await asyncio.sleep(0.5)
            assert sup.totals["probes"] >= 2
            # the fallback lane must still serve promptly: if the
            # wedged probes kept their worker slots this would hang
            t0 = time.monotonic()
            outs = await asyncio.wait_for(
                asyncio.gather(*[
                    disp.encode(sinfo, codec, buf) for _ in range(4)
                ]),
                timeout=5.0,
            )
            assert time.monotonic() - t0 < 3.0
            disp.inject_launch_hang = 0.0
            await disp.stop()
            return out, outs

        out, outs = run(main())
        _same_shards(out, want)
        for o in outs:
            _same_shards(o, want)


# -- canary re-promotion ------------------------------------------------------


class TestRepromotion:
    def test_probe_repromotes_after_injection_lifts(self):
        from ceph_tpu.common.perf_counters import PerfCounters

        pec = PerfCounters("ec")
        pec.add_gauge("engine_state")
        pec.add_counter("engine_failovers")
        pec.add_counter("replayed_ops")
        pec.add_counter("launch_deadline_timeouts")
        sinfo, codec = _sinfo(2), _codec()
        buf = _buf(sinfo, 2, seed=10)
        want = ec_util.encode(sinfo, codec, buf)

        async def main():
            degraded_edges = []
            sup = EngineSupervisor(
                probe_interval=0.03, perf=pec,
                on_degraded=degraded_edges.append,
            )
            disp = ECDispatcher(perf=pec, window=0.0,
                                max_stripes=1 << 20, supervisor=sup)
            disp.inject_engine_failure = 1
            await disp.encode(sinfo, codec, buf)  # SUSPECT
            await disp.encode(sinfo, codec, buf)  # TRIPPED
            assert pec.get("engine_state") == TRIPPED
            assert degraded_edges == [True]
            # probes keep failing while injection is armed
            await asyncio.sleep(0.15)
            assert sup.state in (TRIPPED, PROBING)
            assert sup.totals["probes"] >= 1
            disp.inject_engine_failure = 0  # lift the fault
            async with asyncio.timeout(10):
                while sup.state != HEALTHY:
                    await asyncio.sleep(0.02)
            assert degraded_edges == [True, False]
            assert pec.get("engine_state") == HEALTHY
            assert sup.totals["promotions"] == 1
            # back on the device path: no new failover events
            before = disp._totals["failovers"]
            out = await disp.encode(sinfo, codec, buf)
            assert disp._totals["failovers"] == before
            assert disp._totals["fallback_direct"] == 0
            await disp.stop()
            return out

        _same_shards(run(main()), want)
        assert pec.get("engine_failovers") == 2
        assert pec.get("replayed_ops") == 2

    def test_decode_trip_canary_probes_the_reconstruct_program(
        self, monkeypatch
    ):
        """A breaker tripped by DECODE failures must re-promote on a
        decode canary: a device whose reconstruct program is broken
        but whose encode works would otherwise flap TRIPPED->HEALTHY->
        TRIPPED forever."""
        sinfo, codec = _sinfo(2), _codec()
        shards = ec_util.encode_fallback(sinfo, codec,
                                         _buf(sinfo, 2, seed=11))
        survivors = {1: shards[1], 2: shards[2]}
        probed = {"dec": 0, "enc": 0}
        real_dec, real_enc = ec_util.decode_concat, ec_util.encode

        def spy_dec(*a, **kw):
            probed["dec"] += 1
            return real_dec(*a, **kw)

        def spy_enc(*a, **kw):
            probed["enc"] += 1
            return real_enc(*a, **kw)

        async def main():
            sup = EngineSupervisor(probe_interval=0.03)
            disp = ECDispatcher(window=0.0, max_stripes=1 << 20,
                                supervisor=sup)
            disp.inject_engine_failure = 1
            for _ in range(2):  # two fatal DECODE launches: TRIPPED
                await disp.decode_concat(sinfo, codec, survivors)
            assert sup.state == TRIPPED
            assert disp._last_trip[0] == "dec"
            disp.inject_engine_failure = 0
            monkeypatch.setattr(ec_util, "decode_concat", spy_dec)
            monkeypatch.setattr(ec_util, "encode", spy_enc)
            async with asyncio.timeout(10):
                while sup.state != HEALTHY:
                    await asyncio.sleep(0.02)
            await disp.stop()

        run(main())
        assert probed["dec"] >= 1  # the canary drove the RECONSTRUCT
        assert probed["enc"] == 0  # ...not an encode stand-in

    def test_wedged_canary_does_not_retrip(self):
        """A canary that blows the launch deadline while PROBING must
        route back to TRIPPED without re-tripping: no inflated trip
        totals, no re-fired on_degraded edge, no reset since_s."""
        degraded = []
        sup = EngineSupervisor(probe_interval=30.0,
                               on_degraded=degraded.append)
        sup.record_failure(EngineFault("x"))
        sup.record_failure(EngineFault("x"))
        assert sup.state == TRIPPED and sup.totals["trips"] == 1
        t_trip = sup.last_transition
        sup.state = PROBING  # what _probe_loop sets around the canary
        sup.record_timeout(0.5)  # the canary wedged
        assert sup.totals["trips"] == 1  # still the ONE real trip
        assert sup.totals["timeouts"] == 1
        assert degraded == [True]  # no duplicate degraded edge
        assert sup.last_transition == t_trip

    def test_engine_state_gauge_survives_perf_reset(self):
        """An admin `perf reset` zeroes gauges; refresh_gauge (run off
        the OSD report tick) must re-assert engine_state or a TRIPPED
        OSD would read healthy at the mgr and silently clear
        ACCEL_DEGRADED."""
        from ceph_tpu.common.perf_counters import PerfCounters

        pec = PerfCounters("ec")
        pec.add_gauge("engine_state")
        sup = EngineSupervisor(probe_interval=30.0, perf=pec)
        sup.record_failure(EngineFault("x"))
        sup.record_failure(EngineFault("x"))
        assert pec.get("engine_state") == TRIPPED
        pec.reset()
        assert pec.get("engine_state") == HEALTHY  # the lie
        sup.refresh_gauge()
        assert pec.get("engine_state") == TRIPPED


# -- QoS capacity squeeze -----------------------------------------------------


class TestQosSqueeze:
    def test_degraded_capacity_paces_at_reservation(self):
        """capacity_degraded squeezes ec_background pacing to the
        reservation rate even with NO client queued — the same squeeze
        client contention triggers (PR 5)."""
        from ceph_tpu.osd.scheduler import OpScheduler, QosSpec

        async def main():
            sched = OpScheduler({
                "ec_background": QosSpec(reservation=10.0, weight=1.0,
                                         limit=1000.0),
            })
            # healthy: limit-rate pacing, 5 units ~ 5ms of tag
            await sched.pace("ec_background", cost=5.0)
            healthy_tag = sched._state["ec_background"].pace_tag \
                - time.monotonic()
            sched._state["ec_background"].pace_tag = 0.0  # reset
            sched.capacity_degraded = True
            await sched.pace("ec_background", cost=5.0)
            degraded_tag = sched._state["ec_background"].pace_tag \
                - time.monotonic()
            # 5 units at res=10/s books ~0.5s of tag vs ~5ms at limit
            assert degraded_tag > healthy_tag * 10
            assert degraded_tag > 0.3
            assert sched.dump()["capacity_degraded"] is True
            sched.stop()

        run(main())


# -- the live fault matrix ----------------------------------------------------


async def _mgr_health(client):
    from ceph_tpu.tools.ceph_cli import _mgr_command

    rc, out = await _mgr_command(client, {"prefix": "health"})
    assert rc == 0
    return out


class TestFaultMatrixLive:
    def test_error_and_hang_injection_on_a_live_cluster(self):
        """ISSUE 7 acceptance: with ec_inject_engine_failure (error and
        hang variants) firing mid-batch on a live MiniCluster, no
        client op fails — in-flight ops replay bit-identically,
        ec.engine_failovers increments, ACCEL_DEGRADED raises at the
        mgr and clears, and the engine re-promotes after the injection
        is lifted."""
        from ceph_tpu.rados import MiniCluster

        async def main():
            async with MiniCluster(
                n_osds=4,
                config_overrides={
                    "osd_ec_probe_interval": 0.05,
                    "osd_mgr_report_interval": 0.05,
                },
            ) as cluster:
                await cluster.start_mgr()
                await cluster.wait_for_active_mgr()
                cl = await cluster.client()
                await cl.create_pool("ec", "erasure")  # k2m1
                io = cl.io_ctx("ec")
                model: dict[str, bytes] = {}

                async def storm(round_no: int, n: int = 8):
                    async def put(i):
                        data = bytes([round_no, i]) * (400 + 97 * i)
                        await io.write_full(f"o{i}", data)
                        model[f"o{i}"] = data
                    await asyncio.gather(*[put(i) for i in range(n)])

                await storm(0)  # baseline, engines healthy

                def counters(key):
                    return sum(
                        osd.perf.get("ec").get(key)
                        for osd in cluster.osds.values()
                    )

                # ---- error variant ----------------------------------
                for osd in cluster.osds.values():
                    osd.config.set("ec_inject_engine_failure", 1)
                await storm(1)  # NO op may fail
                assert counters("engine_failovers") > 0
                assert counters("replayed_ops") > 0
                # reads see the replayed bytes, bit-identical
                for name, want in model.items():
                    assert await io.read(name) == want, name
                # breakers tripped (every OSD took >= 2 fatal launches)
                tripped = [
                    osd for osd in cluster.osds.values()
                    if osd.ec_supervisor.state in (TRIPPED, PROBING)
                ]
                assert tripped, "no breaker tripped under 100% injection"
                # ...and the tripped OSDs squeezed background capacity
                assert all(
                    osd.scheduler.capacity_degraded for osd in tripped
                )
                # ACCEL_DEGRADED raises cluster-wide via the mgr
                async with asyncio.timeout(15):
                    while True:
                        st = await _mgr_health(cl)
                        codes = {c["code"] for c in st["checks"]}
                        if "ACCEL_DEGRADED" in codes:
                            break
                        await asyncio.sleep(0.05)
                # lift the injection: canaries verify, engines re-promote
                for osd in cluster.osds.values():
                    osd.config.set("ec_inject_engine_failure", 0)
                async with asyncio.timeout(15):
                    while any(
                        osd.ec_supervisor.state != HEALTHY
                        for osd in cluster.osds.values()
                    ):
                        await asyncio.sleep(0.05)
                # ...and the health check clears
                async with asyncio.timeout(15):
                    while True:
                        st = await _mgr_health(cl)
                        if not any(c["code"] == "ACCEL_DEGRADED"
                                   for c in st["checks"]):
                            break
                        await asyncio.sleep(0.05)

                # ---- hang variant -----------------------------------
                for osd in cluster.osds.values():
                    osd.config.set("osd_ec_launch_deadline", 0.2)
                    osd.config.set("ec_inject_launch_hang", 0.8)
                t0 = time.monotonic()
                await storm(2)  # ops fail over at the deadline
                assert counters("launch_deadline_timeouts") > 0
                for name, want in model.items():
                    assert await io.read(name) == want, name
                # no op waited out the full hang chain
                assert time.monotonic() - t0 < 10.0
                for osd in cluster.osds.values():
                    osd.config.set("ec_inject_launch_hang", 0.0)
                    osd.config.set("osd_ec_launch_deadline", 30.0)
                async with asyncio.timeout(20):
                    while any(
                        osd.ec_supervisor.state != HEALTHY
                        for osd in cluster.osds.values()
                    ):
                        await asyncio.sleep(0.05)
                # recovered: a fresh storm runs clean on the device path
                before = counters("engine_failovers")
                await storm(3)
                assert counters("engine_failovers") == before
                for name, want in model.items():
                    assert await io.read(name) == want, name

        run(main())

    def test_dump_engine_health_admin_command(self, tmp_path):
        """The operator surface: dump_engine_health serves breaker
        state + failover totals over the admin socket."""
        from ceph_tpu.common.admin_socket import admin_command
        from ceph_tpu.rados import MiniCluster

        async def main():
            async with MiniCluster(
                n_osds=3,
                config_overrides={
                    "admin_socket": str(tmp_path / "{name}.asok"),
                    "osd_ec_probe_interval": 30.0,
                },
            ) as cluster:
                cl = await cluster.client()
                await cl.create_pool("ec", "erasure")
                io = cl.io_ctx("ec")
                for osd in cluster.osds.values():
                    osd.config.set("ec_inject_engine_failure", 1)
                await io.write_full("x", bytes(range(256)) * 16)
                hit = None
                for osd in cluster.osds.values():
                    d = await admin_command(
                        str(tmp_path / f"{osd.name}.asok"),
                        "dump_engine_health",
                    )
                    assert d["state"] in ("healthy", "suspect",
                                          "tripped", "probing")
                    if d["dispatcher"]["failovers"] > 0:
                        hit = d
                assert hit is not None
                assert hit["totals"]["fatal_errors"] > 0
                assert hit["dispatcher"]["replayed_ops"] > 0

        run(main())
