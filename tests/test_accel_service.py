"""Shared EC accelerator service (ISSUE 10 acceptance).

Pins the whole contract:

- **remote-lane byte identity**: coalesced batches shipped over a real
  loopback messenger to a standalone :class:`AccelDaemon` produce
  bytes identical to the ``ec_util`` oracle — mixed sizes, bucket
  boundaries, w=8 and w=16 codecs, decode-with-erasure, and a
  cancellation mid-flight never corrupts its batch peers;
- **routing policy**: ``osd_ec_accel_mode`` off/prefer/require, the
  no-wire-profile gate, and beacon-driven re-routing — a TRIPPED or
  saturated beacon sends the NEXT batch to the local lanes with no
  timeout chain, and the re-route is counted;
- **failover**: accelerator death mid-batch (the SIGKILL analog) is
  classified like device death — the in-flight batch replays on the
  LOCAL fallback engine bit-identically, the flight-recorder record
  says ``served=fallback origin=remote``, and the remote's faults
  never advance the LOCAL device breaker;
- **live MiniCluster fault matrix**: ≥3 OSDs routed through one
  accelerator pass the EC read/write suite bit-identically; killing
  the accelerator mid-write-storm yields ZERO failed client ops,
  ``ACCEL_UNREACHABLE`` raises at the mgr and clears after a restart;
  ``ms_inject_socket_failures`` severing the accel links mid-frame
  loses no ops either; lane-attributed counters tell the story.
"""

import asyncio

import numpy as np

from ceph_tpu.accel import AccelClient, AccelDaemon
from ceph_tpu.models import registry
from ceph_tpu.msg import AsyncMessenger, Dispatcher
from ceph_tpu.osd import ec_util
from ceph_tpu.osd.ec_dispatch import ECDispatcher


def run(coro):
    return asyncio.run(coro)


def _isa_codec(k: int = 2, m: int = 1):
    return registry.instance().factory(
        "isa", {"plugin": "isa", "technique": "reed_sol_van",
                "k": str(k), "m": str(m)},
    )


def _w16_codec(k: int = 2, m: int = 1):
    return registry.instance().factory(
        "jerasure", {"plugin": "jerasure", "technique": "reed_sol_van",
                     "k": str(k), "m": str(m), "w": "16"},
    )


def _sinfo(codec, cs: int = 128) -> ec_util.StripeInfo:
    k = codec.get_data_chunk_count()
    return ec_util.StripeInfo(stripe_width=cs * k, chunk_size=cs)


class _Feeder(Dispatcher):
    """A simulated OSD: messenger + dispatcher with a remote lane."""

    def __init__(self, name: str, addr: str, *, mode: str = "prefer",
                 deadline: float = 20.0, window: float = 0.001):
        self.messenger = AsyncMessenger(name, self)
        self.client = AccelClient(self.messenger, addr=addr, mode=mode,
                                  deadline=deadline,
                                  retry_interval=0.05)
        self.dispatch = ECDispatcher(window=window, remote=self.client)

    async def ms_dispatch(self, conn, msg):
        self.client.handle(msg)

    def ms_handle_reset(self, conn):
        self.client.on_reset(conn)

    async def stop(self):
        await self.dispatch.stop()
        await self.messenger.shutdown()


async def _with_service(body, **daemon_kw):
    acc = AccelDaemon("accel.t", **daemon_kw)
    await acc.start()
    feeder = _Feeder("osd.0", acc.addr)
    try:
        await body(acc, feeder)
    finally:
        await feeder.stop()
        await acc.stop()


def _assert_shards_equal(got, want, ctx=""):
    assert set(got) == set(want), ctx
    for s in want:
        assert np.array_equal(np.asarray(got[s]), np.asarray(want[s])), \
            f"{ctx} shard {s}"


class TestRemoteLaneIdentity:
    def test_encode_identity_mixed_sizes_and_buckets(self):
        """Remote-lane encodes across bucket boundaries match the
        local oracle byte for byte (w=8 matrix codec)."""
        codec = _isa_codec()
        sinfo = _sinfo(codec)
        rng = np.random.default_rng(5)
        sizes = [1, 2, 3, 4, 5, 7, 8, 9, 15, 16, 17, 31, 32, 33]
        bufs = [rng.integers(0, 256, size=(s * sinfo.stripe_width,),
                             dtype=np.uint8) for s in sizes]

        async def body(acc, feeder):
            outs = await asyncio.gather(*[
                feeder.dispatch.encode(sinfo, codec, b) for b in bufs
            ])
            for b, out in zip(bufs, outs):
                _assert_shards_equal(out, ec_util.encode(sinfo, codec, b))
            # the batches actually took the remote lane
            lanes = feeder.dispatch.dump()["totals"]["lanes"]
            assert lanes["remote"]["ops"] == len(bufs)
            assert lanes["device"]["ops"] == 0
            # ...and the accelerator attributed them to this client
            assert "osd.0" in acc.client_table()
            # reply piggyback: the client-side flight record names the
            # engine the ACCELERATOR served from, and its
            # device_wall_s is the accel's launch time, not the RTT
            recs = feeder.dispatch.flight.dump()["launches"]
            assert recs and all(
                r.get("remote_served") in
                ("device", "mesh", "fallback", "fallback_direct")
                for r in recs if r.get("lane") == "remote"
            ), recs

        run(_with_service(body))

    def test_encode_identity_w16(self):
        """w=16 codecs ride the remote lane bit-identically (the u16
        reinterpret path on the accelerator side)."""
        codec = _w16_codec()
        sinfo = _sinfo(codec)
        rng = np.random.default_rng(6)
        bufs = [rng.integers(0, 256, size=(s * sinfo.stripe_width,),
                             dtype=np.uint8) for s in (1, 3, 8)]

        async def body(acc, feeder):
            outs = await asyncio.gather(*[
                feeder.dispatch.encode(sinfo, codec, b) for b in bufs
            ])
            for b, out in zip(bufs, outs):
                _assert_shards_equal(out, ec_util.encode(sinfo, codec, b))

        run(_with_service(body))

    def test_decode_identity_with_erasure(self):
        """Remote reconstructs (one data shard missing) match
        decode_concat, mixed sizes coalesced into one batch."""
        codec = _isa_codec()
        sinfo = _sinfo(codec)
        rng = np.random.default_rng(7)
        bufs = [rng.integers(0, 256, size=(s * sinfo.stripe_width,),
                             dtype=np.uint8) for s in (2, 5, 8)]
        survivors = []
        for b in bufs:
            full = ec_util.encode(sinfo, codec, b)
            survivors.append({s: np.asarray(v) for s, v in full.items()
                              if s != 0})

        async def body(acc, feeder):
            outs = await asyncio.gather(*[
                feeder.dispatch.decode_concat(sinfo, codec, surv)
                for surv in survivors
            ])
            for b, got in zip(bufs, outs):
                assert bytes(got) == bytes(b)
            assert feeder.dispatch.dump()[
                "totals"]["lanes"]["remote"]["ops"] == len(bufs)

        run(_with_service(body))

    def test_cancellation_mid_flight(self):
        """A waiter cancelled before its batch flushes is dropped; its
        peers' bytes are untouched (the abort contract, remote lane)."""
        codec = _isa_codec()
        sinfo = _sinfo(codec)
        rng = np.random.default_rng(8)
        bufs = [rng.integers(0, 256, size=(s * sinfo.stripe_width,),
                             dtype=np.uint8) for s in (2, 3, 4)]

        async def body(acc, feeder):
            feeder.dispatch.window = 0.05  # hold the batch open
            tasks = [asyncio.ensure_future(
                feeder.dispatch.encode(sinfo, codec, b)) for b in bufs]
            await asyncio.sleep(0)
            tasks[1].cancel()
            done = await asyncio.gather(*tasks, return_exceptions=True)
            assert isinstance(done[1], asyncio.CancelledError)
            for i in (0, 2):
                _assert_shards_equal(
                    done[i], ec_util.encode(sinfo, codec, bufs[i]))
            assert feeder.dispatch.dump()["totals"]["cancelled"] == 1

        run(_with_service(body))


class TestRoutingPolicy:
    def _client(self, mode="prefer", addr="127.0.0.1:1"):
        return AccelClient(AsyncMessenger("osd.t", Dispatcher()),
                           addr=addr, mode=mode)

    def test_off_and_missing_profile_never_route(self):
        codec = _isa_codec()
        assert not self._client(mode="off").routes(codec)
        assert not self._client(addr="").routes(codec)
        # a hand-built codec has no wire profile to rebuild from
        from ceph_tpu.models.matrix_codec import MatrixErasureCode
        from ceph_tpu.ops import matrices as mx

        bare = MatrixErasureCode(2, 1, 8, mx.isa_rs_vandermonde(2, 1))
        assert not self._client().routes(bare)

    def test_tripped_beacon_routes_away_without_timeout(self):
        """A TRIPPED beacon re-routes instantly (no connection attempt,
        no deadline wait) and the re-route is counted."""
        from ceph_tpu.msg import messages

        codec = _isa_codec()
        cl = self._client()
        assert cl.routes(codec)
        cl.handle(messages.MAccelBeacon(
            name="accel.t", engine_state=2, queue_depth=0, capacity=8))
        assert not cl.routes(codec)
        assert cl.totals["routed_away"] == 1
        # a healthy beacon routes back
        cl.handle(messages.MAccelBeacon(
            name="accel.t", engine_state=0, queue_depth=0, capacity=8))
        assert cl.routes(codec)

    def test_saturated_beacon_routes_away(self):
        from ceph_tpu.msg import messages

        codec = _isa_codec()
        cl = self._client()
        cl.handle(messages.MAccelBeacon(
            name="accel.t", engine_state=0, queue_depth=99, capacity=8))
        assert not cl.routes(codec)

    def test_require_routes_even_when_down(self):
        codec = _isa_codec()
        cl = self._client(mode="require")
        cl._mark_down()
        assert cl.routes(codec)
        # prefer backs off instead
        cl2 = self._client()
        cl2._mark_down()
        assert not cl2.routes(codec)
        assert cl2.unreachable

    def test_live_retarget_resets_health(self):
        cl = self._client()
        cl._mark_down()
        cl.remote_state = 2
        cl.set_addr("127.0.0.1:2")
        assert not cl.unreachable
        assert cl.remote_state == 0

    def test_unreachable_is_sticky_until_heard_from(self):
        """The backoff expiring does NOT clear unreachable (the mgr
        check must not flap while the accelerator is still dead);
        traffic may re-probe, and only an actual beacon/reply clears."""
        from ceph_tpu.msg import messages

        cl = self._client()
        cl.retry_interval = 0.0  # backoff expires immediately
        cl._mark_down()
        assert cl.unreachable
        assert cl.available()  # due a re-probe...
        assert cl.unreachable  # ...but still DOWN until heard from
        cl.handle(messages.MAccelBeacon(
            name="accel.t", engine_state=0, queue_depth=0, capacity=8))
        assert not cl.unreachable

    def test_mode_off_clears_unreachable(self):
        """Turning the lane off clears the sticky down state — a
        disabled lane must not keep ACCEL_UNREACHABLE raised forever
        (no traffic or beacon could ever clear it otherwise)."""
        cl = self._client()
        cl._mark_down()
        assert cl.unreachable
        cl.set_mode("off")
        assert not cl.unreachable

    def test_stale_connection_health_is_ignored(self):
        """After a live retarget, the OLD accelerator's still-open
        connection keeps beaconing; its healthy beacons must not mark
        the NEW endpoint reachable."""
        from ceph_tpu.msg import messages

        class _Conn:
            def __init__(self, peer_addr):
                self.peer_addr = peer_addr

        cl = self._client(addr="127.0.0.1:2")
        cl._mark_down()
        beacon = messages.MAccelBeacon(
            name="accel.old", engine_state=0, queue_depth=0, capacity=8)
        cl.handle(beacon, _Conn("127.0.0.1:1"))  # the OLD endpoint
        assert cl.unreachable  # not fooled
        cl.handle(beacon, _Conn("127.0.0.1:2"))  # the CURRENT one
        assert not cl.unreachable


class TestRemoteFailover:
    def test_accel_death_mid_batch_replays_locally(self):
        """Crash-stop the accelerator with a batch in flight: the
        waiters are served bit-identically by the LOCAL fallback, the
        flight record says served=fallback origin=remote, and the
        LOCAL device breaker never advanced (a network trip must not
        bench a healthy local device)."""
        codec = _isa_codec()
        sinfo = _sinfo(codec)
        rng = np.random.default_rng(9)
        buf = rng.integers(0, 256, size=(6 * sinfo.stripe_width,),
                           dtype=np.uint8)

        async def main():
            acc = AccelDaemon("accel.t")
            await acc.start()
            feeder = _Feeder("osd.0", acc.addr)
            from ceph_tpu.osd.ec_failover import EngineSupervisor

            sup = EngineSupervisor(enabled=True, probe_interval=30.0)
            feeder.dispatch._supervisor = sup
            t = asyncio.ensure_future(
                feeder.dispatch.encode(sinfo, codec, buf))
            await asyncio.sleep(0)  # let the batch open
            await acc.stop(crash=True)  # SIGKILL analog: no replies
            out = await t
            _assert_shards_equal(out, ec_util.encode(sinfo, codec, buf))
            totals = feeder.dispatch.dump()["totals"]
            assert totals["failovers"] == 1
            assert totals["replayed_ops"] == 1
            rec = feeder.dispatch.flight.dump()["launches"][-1]
            assert rec["lane"] == "remote"
            assert rec["served"] == "fallback"
            assert rec["origin"] == "remote"
            # the LOCAL breaker never moved
            from ceph_tpu.osd.ec_failover import HEALTHY

            assert sup.state == HEALTHY
            assert sup.totals["fatal_errors"] == 0
            assert feeder.client.unreachable
            assert feeder.client.totals["failures"] >= 1
            await feeder.stop()

        run(main())

    def test_unreachable_accel_replays_and_backs_off(self):
        """No accelerator listening at all: the first batch replays on
        the local fallback, the client backs off, and (prefer mode)
        the NEXT batch takes the local lanes without an RPC attempt."""
        codec = _isa_codec()
        sinfo = _sinfo(codec)
        rng = np.random.default_rng(10)
        buf = rng.integers(0, 256, size=(2 * sinfo.stripe_width,),
                           dtype=np.uint8)

        async def main():
            feeder = _Feeder("osd.0", "127.0.0.1:1", deadline=5.0)
            out = await feeder.dispatch.encode(sinfo, codec, buf)
            _assert_shards_equal(out, ec_util.encode(sinfo, codec, buf))
            assert feeder.client.unreachable
            # prefer mode: next request routes local (no remote batch)
            before = feeder.dispatch.dump()["totals"]["lanes"]["remote"]
            out2 = await feeder.dispatch.encode(sinfo, codec, buf)
            _assert_shards_equal(out2, ec_util.encode(sinfo, codec, buf))
            after = feeder.dispatch.dump()["totals"]["lanes"]["remote"]
            assert after["batches"] == before["batches"]
            await feeder.stop()

        run(main())


class TestCrossClientCoalescing:
    def test_two_feeders_share_a_launch(self):
        """Two OSD clients' concurrent batches coalesce into ONE
        accelerator launch (the shared-occupancy win), and the flight
        record names both clients."""
        codec = _isa_codec()
        sinfo = _sinfo(codec)
        rng = np.random.default_rng(11)
        b1 = rng.integers(0, 256, size=(3 * sinfo.stripe_width,),
                          dtype=np.uint8)
        b2 = rng.integers(0, 256, size=(4 * sinfo.stripe_width,),
                          dtype=np.uint8)

        async def main():
            from ceph_tpu.common import Config

            # a generous window so both clients' RPCs land inside one
            # accelerator batch deterministically
            acc = AccelDaemon("accel.t", config=Config(overrides={
                "osd_ec_dispatch_window": 0.05,
            }))
            await acc.start()
            f1 = _Feeder("osd.1", acc.addr)
            f2 = _Feeder("osd.2", acc.addr)
            o1, o2 = await asyncio.gather(
                f1.dispatch.encode(sinfo, codec, b1),
                f2.dispatch.encode(sinfo, codec, b2),
            )
            _assert_shards_equal(o1, ec_util.encode(sinfo, codec, b1))
            _assert_shards_equal(o2, ec_util.encode(sinfo, codec, b2))
            t = acc.dispatch._totals
            assert t["cross_client_batches"] >= 1
            recs = acc.dispatch.flight.dump()["launches"]
            shared = [r for r in recs
                      if len(r.get("clients") or []) > 1]
            assert shared, recs
            assert set(shared[-1]["clients"]) == {"osd.1", "osd.2"}
            # the service half mirrors the total
            acc._sync_cross_client()
            assert acc.perf.get("accel").get(
                "cross_client_batches") >= 1
            await f1.stop()
            await f2.stop()
            await acc.stop()

        run(main())


async def _mgr_health(client):
    from ceph_tpu.tools.ceph_cli import _mgr_command

    rc, out = await _mgr_command(client, {"prefix": "health"})
    assert rc == 0
    return out


class TestLiveClusterAccel:
    def test_cluster_routes_through_one_accelerator(self):
        """ISSUE 10 acceptance: a MiniCluster with 3 OSDs routed
        through ONE accelerator daemon passes the EC read/write suite
        bit-identically; killing the accelerator mid-write-storm
        yields zero failed client ops (local fallback replay),
        ACCEL_UNREACHABLE raises and clears after a restart, and the
        counters attribute every phase to its lane."""
        from ceph_tpu.rados import MiniCluster

        async def main():
            async with MiniCluster(
                n_osds=3,
                config_overrides={
                    "osd_mgr_report_interval": 0.05,
                    "accel_beacon_interval": 0.05,
                    "osd_ec_accel_retry_interval": 0.1,
                },
            ) as cluster:
                await cluster.start_mgr()
                await cluster.wait_for_active_mgr()
                acc = await cluster.start_accel()
                cluster.route_osds_to_accel(acc.addr, mode="prefer")
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

                def remote_batches():
                    return sum(
                        osd.perf.get("accel").get("remote_batches")
                        for osd in cluster.osds.values()
                    )

                # ---- healthy: writes+reads ride the accelerator ----
                await storm(0)
                assert remote_batches() > 0
                for name, want in model.items():
                    assert await io.read(name) == want, name
                # the accelerator saw multiple client OSDs
                assert len(acc.client_table()) >= 2
                # ---- SIGKILL mid-storm: zero failed client ops -----
                kill = asyncio.ensure_future(
                    cluster.kill_accel("accel.1", crash=True))
                await storm(1)  # NO op may fail
                await kill
                for name, want in model.items():
                    assert await io.read(name) == want, name
                failovers = sum(
                    osd.perf.get("accel").get("remote_failovers")
                    for osd in cluster.osds.values()
                )
                local_served = sum(
                    osd.ec_dispatch._totals["failovers"]
                    + osd.ec_dispatch._totals["lanes"]["device"]["batches"]
                    + osd.ec_dispatch._totals["fallback_direct"]
                    for osd in cluster.osds.values()
                )
                assert local_served > 0
                # ---- ACCEL_UNREACHABLE raises... -------------------
                await storm(2)  # routed locally; marks unreachable
                async with asyncio.timeout(15):
                    while True:
                        st = await _mgr_health(cl)
                        if any(c["code"] == "ACCEL_UNREACHABLE"
                               for c in st["checks"]):
                            break
                        await asyncio.sleep(0.05)
                # ---- ...and clears after a restart ------------------
                acc2 = await cluster.start_accel()
                cluster.route_osds_to_accel(acc2.addr, mode="prefer")
                async with asyncio.timeout(15):
                    while True:
                        await storm(3)
                        st = await _mgr_health(cl)
                        if not any(c["code"] == "ACCEL_UNREACHABLE"
                                   for c in st["checks"]):
                            break
                        await asyncio.sleep(0.1)
                for name, want in model.items():
                    assert await io.read(name) == want, name
                assert failovers >= 0  # counter family exists + sums

        run(main())

    def test_socket_failures_on_accel_links_lose_no_ops(self):
        """ms_inject_socket_failures severing the accelerator's links
        mid-frame: client ops never fail — severed batches replay on
        the local fallback, survivors keep riding the remote lane."""
        from ceph_tpu.rados import MiniCluster

        async def main():
            async with MiniCluster(
                n_osds=3,
                config_overrides={
                    "accel_beacon_interval": 0.05,
                    "osd_ec_accel_retry_interval": 0.05,
                    "osd_ec_accel_deadline": 2.0,
                },
            ) as cluster:
                acc = await cluster.start_accel()
                cluster.route_osds_to_accel(acc.addr, mode="prefer")
                cl = await cluster.client()
                await cl.create_pool("ec", "erasure")
                io = cl.io_ctx("ec")
                model: dict[str, bytes] = {}

                async def put(i, tag):
                    data = bytes([tag, i]) * (300 + 53 * i)
                    await io.write_full(f"s{i}", data)
                    model[f"s{i}"] = data

                await asyncio.gather(*[put(i, 0) for i in range(6)])
                # sever ~1 in 4 socket ops on the ACCELERATOR's
                # messenger (its links carry only accel traffic, so the
                # injection targets exactly the remote lane)
                acc.messenger.inject_socket_failures = 4
                for r in range(1, 4):
                    await asyncio.gather(
                        *[put(i, r) for i in range(6)])
                acc.messenger.inject_socket_failures = 0
                for name, want in model.items():
                    assert await io.read(name) == want, name

        run(main())

    def test_tripped_accelerator_sheds_to_local_lanes(self):
        """ec_inject_engine_failure=1 ON THE ACCELERATOR trips its
        breaker; its beacon says TRIPPED and the OSDs route the next
        batches to their local lanes (routed_away counts, zero failed
        ops).  Lifting the injection re-promotes via the accelerator's
        canary, a healthy beacon arrives, and traffic returns."""
        from ceph_tpu.rados import MiniCluster

        async def main():
            async with MiniCluster(
                n_osds=3,
                config_overrides={
                    "accel_beacon_interval": 0.05,
                    "osd_ec_probe_interval": 0.05,
                },
            ) as cluster:
                from ceph_tpu.common import Config

                acc = await cluster.start_accel(config=Config(overrides={
                    "accel_beacon_interval": 0.05,
                    "osd_ec_probe_interval": 0.05,
                }))
                cluster.route_osds_to_accel(acc.addr, mode="prefer")
                cl = await cluster.client()
                await cl.create_pool("ec", "erasure")
                io = cl.io_ctx("ec")
                model: dict[str, bytes] = {}

                async def storm(tag):
                    async def put(i):
                        data = bytes([tag, i]) * (300 + 31 * i)
                        await io.write_full(f"t{i}", data)
                        model[f"t{i}"] = data
                    await asyncio.gather(*[put(i) for i in range(6)])

                await storm(0)
                # trip the accelerator's own breaker (device faults on
                # ITS device): batches it already took replay on ITS
                # host fallback, so nothing fails...
                acc.config.set("ec_inject_engine_failure", 1)
                await storm(1)
                from ceph_tpu.osd.ec_failover import PROBING, TRIPPED

                async with asyncio.timeout(10):
                    while acc.supervisor.state not in (TRIPPED, PROBING):
                        await storm(2)
                        await asyncio.sleep(0.02)
                # ...and once the TRIPPED beacon lands, OSDs route away
                async with asyncio.timeout(10):
                    while not any(
                        osd.accel_client.remote_state >= 2
                        for osd in cluster.osds.values()
                    ):
                        await asyncio.sleep(0.02)
                routed_before = sum(
                    osd.accel_client.totals["routed_away"]
                    for osd in cluster.osds.values()
                )
                await storm(3)
                routed_after = sum(
                    osd.accel_client.totals["routed_away"]
                    for osd in cluster.osds.values()
                )
                assert routed_after > routed_before
                for name, want in model.items():
                    assert await io.read(name) == want, name
                # lift the fault: canary re-promotes, beacon heals,
                # traffic returns to the remote lane
                acc.config.set("ec_inject_engine_failure", 0)
                from ceph_tpu.osd.ec_failover import HEALTHY

                async with asyncio.timeout(15):
                    while acc.supervisor.state != HEALTHY:
                        await asyncio.sleep(0.02)
                async with asyncio.timeout(10):
                    while any(
                        osd.accel_client.remote_state >= 2
                        for osd in cluster.osds.values()
                    ):
                        await asyncio.sleep(0.02)

                def remote_batches():
                    return sum(
                        osd.perf.get("accel").get("remote_batches")
                        for osd in cluster.osds.values()
                    )

                before = remote_batches()
                await storm(4)
                assert remote_batches() > before
                for name, want in model.items():
                    assert await io.read(name) == want, name

        run(main())
