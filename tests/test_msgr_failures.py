"""Messenger-level fault injection (VERDICT r3 Missing #8 / Next #7).

The reference's thrash matrix leans on ``ms_inject_socket_failures``
(reference:src/common/config_opts.h:209,
reference:qa/suites/rados/thrash-erasure-code/msgr-failures/) — random
mid-message socket drops that every layer must survive via
reconnect + resend.  These tests prove: the injection mechanism
actually severs links mid-frame, the peer never trusts a truncated
frame (crc/length framing), and an EC cluster under continuous socket
loss stays consistent (client op retry + EC sub-op retry + mon
resubscribe).
"""

import asyncio
import random

import pytest

from ceph_tpu.common import Config
from ceph_tpu.msg import AsyncMessenger, Dispatcher, messages
from ceph_tpu.rados import MiniCluster


def run(coro):
    asyncio.run(coro)


class _Sink(Dispatcher):
    def __init__(self):
        self.got = []
        self.resets = 0

    async def ms_dispatch(self, conn, msg):
        self.got.append(msg)

    def ms_handle_reset(self, conn):
        self.resets += 1


class TestInjectionMechanism:
    def test_injection_severs_links_but_never_corrupts(self):
        """With 1-in-8 injection, many sends across reconnects: every
        frame that ARRIVES is intact (crc framing rejects truncation),
        and at least one link was actually severed."""

        async def main():
            sink = _Sink()
            server = AsyncMessenger("srv", sink)
            await server.bind()
            cfg = Config(overrides={"ms_inject_socket_failures": 8})
            cli_sink = _Sink()
            client = AsyncMessenger("cli", cli_sink)
            client.apply_config(cfg)
            assert client.inject_socket_failures == 8
            sent = 0
            for i in range(120):
                try:
                    conn = await client.connect(server.addr, "srv")
                    conn.send(messages.MPing(stamp=float(i)))
                    sent += 1
                except (ConnectionError, OSError):
                    continue  # injected failure mid-handshake: retry
                await asyncio.sleep(0.005)
            await asyncio.sleep(0.2)
            # some frames were lost to injected severs...
            assert len(sink.got) < sent
            assert sink.resets > 0 or cli_sink.resets > 0
            # ...but every delivered frame is whole and well-typed
            for m in sink.got:
                assert isinstance(m, messages.MPing)
                assert isinstance(m.stamp, float)
            await client.shutdown()
            await server.shutdown()

        run(main())

    def test_zero_means_disabled(self):
        m = AsyncMessenger("x", _Sink())
        assert not any(m._inject_failure() for _ in range(10000))


class TestMidVectoredWriteSever:
    """PR-6 frames are SEGMENT LISTS written vectored (writelines); an
    injected sever must be able to land mid-list — whole leading
    segments delivered, the rest never — and the peer must treat the
    half-delivered frame as a reset, never decode it."""

    def _big_op(self, i: int) -> messages.MOSDOp:
        # >1024 bytes of blob across MULTIPLE blobs: the frame takes the
        # vectored segment path (the <=1KiB control fast path joins)
        return messages.MOSDOp(
            tid=i, epoch=1, pool=1, oid=f"obj-{i}",
            ops=[{"op": "writefull", "data": 0}],
            blobs=[bytes([i % 256]) * 3000, bytes([255 - i % 256]) * 2000],
        )

    def test_sever_mid_vectored_write_resets_cleanly(self):
        """Force the injection on exactly one large vectored frame: the
        receiver sees a connection reset and NO message (the length-
        prefixed read never completes, the crc can never pass) — then a
        reconnect + resend delivers the same payload intact."""

        async def main():
            sink = _Sink()
            server = AsyncMessenger("srv", sink)
            await server.bind()
            client = AsyncMessenger("cli", _Sink())
            # deterministic single-shot injection: first vectored write
            # severs, everything after flows
            fired = {"n": 0}

            def inject_once():
                fired["n"] += 1
                return fired["n"] == 1

            client._inject_failure = inject_once
            conn = await client.connect(server.addr, "srv")
            conn.send(self._big_op(1))  # severed mid-segment-list
            await asyncio.sleep(0.3)
            assert sink.got == []  # the half-frame never decoded
            assert sink.resets >= 1  # ...and the peer saw a clean reset
            # client resend path: a fresh connect + send delivers intact
            conn2 = await client.connect(server.addr, "srv")
            assert conn2 is not conn  # the severed conn was dropped
            msg = self._big_op(1)
            conn2.send(msg)
            await asyncio.sleep(0.3)
            assert len(sink.got) == 1
            got = sink.got[0]
            assert isinstance(got, messages.MOSDOp)
            assert got.oid == "obj-1" and got.tid == 1
            assert [bytes(b) for b in got.blobs] == \
                [bytes(b) for b in msg.blobs]
            await client.shutdown()
            await server.shutdown()

        run(main())

    def test_continuous_injection_never_yields_half_frames(self):
        """1-in-4 injection over a stream of multi-blob vectored frames:
        every frame that ARRIVES carries its full blobs byte-exact;
        severed ones vanish entirely (crc/length framing)."""

        async def main():
            sink = _Sink()
            server = AsyncMessenger("srv", sink)
            await server.bind()
            cfg = Config(overrides={"ms_inject_socket_failures": 4})
            client = AsyncMessenger("cli", _Sink())
            client.apply_config(cfg)
            sent = {}
            for i in range(40):
                try:
                    conn = await client.connect(server.addr, "srv")
                    conn.send(self._big_op(i))
                    sent[i] = self._big_op(i)
                except (ConnectionError, OSError):
                    continue  # injected failure mid-handshake
                await asyncio.sleep(0.01)
            await asyncio.sleep(0.3)
            assert sink.resets > 0  # severs really happened
            assert 0 < len(sink.got) < len(sent)  # ...and ate frames
            for got in sink.got:
                want = sent[got.tid]
                assert [bytes(b) for b in got.blobs] == \
                    [bytes(b) for b in want.blobs], got.tid
            await client.shutdown()
            await server.shutdown()

        run(main())


async def _silent_listener():
    """A TCP listener that accepts and never says a word."""
    held = []

    async def hold(reader, writer):
        held.append(writer)
        await asyncio.Event().wait()

    srv = await asyncio.start_server(hold, "127.0.0.1", 0)
    host, port = srv.sockets[0].getsockname()[:2]
    return srv, held, f"{host}:{port}"


class TestHandshakeBounds:
    """A peer that connects and then goes quiet, or a caller that gives
    up on a dial, must never leave a waiter pending for ever."""

    def test_shutdown_closes_a_silent_inbound_handshake(self):
        """An accepted link still in its banner exchange is not yet a
        Connection; shutdown() must close it, or the server's
        wait_closed() waits on its transport for ever."""

        async def main():
            server = AsyncMessenger("srv", _Sink())
            await server.bind()
            host, port = server.addr.rsplit(":", 1)
            reader, writer = await asyncio.open_connection(host, int(port))
            await asyncio.sleep(0.05)  # the acceptor waits on our banner
            await asyncio.wait_for(server.shutdown(), 2.0)
            assert await asyncio.wait_for(reader.read(), 2.0) == b""
            writer.close()

        run(main())

    def test_silent_inbound_peer_is_dropped_after_connect_timeout(self):
        async def main():
            server = AsyncMessenger("srv", _Sink(), connect_timeout=0.3)
            await server.bind()
            host, port = server.addr.rsplit(":", 1)
            reader, writer = await asyncio.open_connection(host, int(port))
            assert await asyncio.wait_for(reader.read(), 2.0) == b""
            writer.close()
            await server.shutdown()

        run(main())

    def test_caller_giving_up_leaves_the_shared_dial_to_the_others(self):
        """Two callers share one dial to a peer that never answers the
        banner.  The first gives up at its own deadline; the second
        must see the dial's own failure, not the first caller's
        cancellation (a CancelledError there ends an EC batch task with
        its waiters unanswered)."""

        async def main():
            srv, held, addr = await _silent_listener()
            client = AsyncMessenger("cli", _Sink(), reconnect_attempts=1,
                                    connect_timeout=0.5)
            impatient = asyncio.ensure_future(
                asyncio.wait_for(client.connect(addr, "srv"), 0.1))
            patient = asyncio.ensure_future(client.connect(addr, "srv"))
            first, second = await asyncio.gather(impatient, patient,
                                                 return_exceptions=True)
            await client.shutdown()
            for w in held:
                w.close()
            srv.close()
            assert isinstance(first, TimeoutError)
            assert isinstance(second, ConnectionError), repr(second)

        run(main())


class TestClosingTransport:
    def test_writes_to_an_aborted_link_leave_its_fd_clean(self):
        """A writer loop can still hand frames to a link whose
        transport has aborted but whose connection_lost has not run.
        Those bytes must be dropped: a writer registered for the dying
        socket outlives its close in the selector, and the next socket
        to reuse the fd number can then never register for reading."""
        import socket

        from ceph_tpu.msg.messenger import _FrameChannel

        async def main():
            loop = asyncio.get_running_loop()
            srv = await asyncio.start_server(
                lambda r, w: None, "127.0.0.1", 0)
            port = srv.sockets[0].getsockname()[1]
            tr, ch = await loop.create_connection(
                _FrameChannel, "127.0.0.1", port)
            fd = tr.get_extra_info("socket").fileno()
            tr.abort()
            ch.writelines([b"x" * 64])
            await asyncio.sleep(0.05)  # the queued close runs
            # a new socket takes the lowest free descriptor: open until
            # one reuses the closed one
            socks = []
            try:
                while not socks or socks[-1].fileno() != fd:
                    assert len(socks) < 256
                    socks.append(socket.socket())
                loop.add_reader(fd, lambda: None)
                loop.remove_reader(fd)
            finally:
                for s in socks:
                    s.close()
                srv.close()

        run(main())


class TestMsgrFailureThrash:
    @pytest.mark.slow
    def test_ec_cluster_consistent_under_socket_loss(self):
        """The msgr-failures thrash variant: an EC pool takes a model
        workload while every OSD's messenger randomly severs sockets
        mid-frame; reconnect/replay plus EC sub-op retry must keep all
        acked writes readable and correct.

        Slow tier (ISSUE 8 CI budget pass): the sustained random-sever
        workload runs ~90s on the 1.5-core CI budget — by far the
        heaviest single test; the single-shot mid-vectored-write sever
        and continuous 1-in-4 frame-sever variants stay in tier-1."""

        async def main():
            rng = random.Random(99)
            async with MiniCluster(
                n_osds=6,
                config_overrides={"ms_inject_socket_failures": 150},
            ) as cluster:
                # daemons really run with injection armed
                assert all(
                    osd.messenger.inject_socket_failures == 150
                    for osd in cluster.osds.values()
                )
                cl = await cluster.client()
                code, status, _ = await cl.command({
                    "prefix": "osd erasure-code-profile set", "name": "rs32",
                    "profile": {"plugin": "jerasure",
                                "technique": "reed_sol_van",
                                "k": "3", "m": "2"},
                })
                assert code == 0, status
                await cl.create_pool(
                    "ec", "erasure", erasure_code_profile="rs32", pg_num=16
                )
                io = cl.io_ctx("ec")
                model: dict[str, bytes] = {}
                for round_no in range(4):
                    for i in range(8):
                        name = f"obj-{rng.randrange(16)}"
                        data = bytes([round_no + 1, i]) * rng.randrange(
                            500, 9000
                        )
                        await io.write_full(name, data)
                        model[name] = data
                    # interleave reads mid-thrash: they must see the model
                    probe = rng.choice(sorted(model))
                    assert await io.read(probe) == model[probe], probe
                await asyncio.sleep(0.3)
                for name, data in model.items():
                    got = await io.read(name)
                    assert got == data, f"{name}: lost under socket thrash"

        run(main())

    def test_replicated_omap_consistent_under_socket_loss(self):
        """Same variant over the replicated + omap path (MOSDRepOp
        fan-out instead of EC sub-ops)."""

        async def main():
            rng = random.Random(7)
            async with MiniCluster(
                n_osds=4,
                config_overrides={"ms_inject_socket_failures": 120},
            ) as cluster:
                cl = await cluster.client()
                await cl.create_pool("rep", "replicated", size=3)
                io = cl.io_ctx("rep")
                model: dict[str, dict[str, bytes]] = {}
                for i in range(24):
                    name = f"o{rng.randrange(8)}"
                    kv = {f"k{j}": bytes([i, j]) * 50 for j in range(3)}
                    await io.write_full(name, bytes([i]) * 256)
                    await io.omap_set(name, kv)
                    model[name] = kv
                for name, kv in model.items():
                    got = await io.omap_get(name)
                    assert got == kv, name

        run(main())
