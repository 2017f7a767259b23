"""End-to-end mini-RADOS tests: the test-erasure-code.sh analog.

Mirrors the reference single-host integration suite
(reference:src/test/erasure-code/test-erasure-code.sh: boot mon + OSDs,
create EC pools with various profiles, rados put/get, kill a shard,
reads must reconstruct), on the in-process MiniCluster.
"""

import asyncio
import json

import pytest

import numpy as np
from ceph_tpu.osd.ec_util import StripeHashes
from ceph_tpu.rados import MiniCluster, RadosError
from ceph_tpu.store import CollectionId, ObjectId
from ceph_tpu.utils import native


def run(coro):
    asyncio.run(coro)


PAYLOAD = bytes(range(256)) * 64  # 16 KiB, non-trivial content


# -- replicated pools --------------------------------------------------------


def test_replicated_put_get_stat_delete():
    async def main():
        async with MiniCluster(n_osds=3) as cluster:
            cl = await cluster.client()
            await cl.create_pool("rbd", "replicated", size=3)
            io = cl.io_ctx("rbd")
            await io.write_full("obj1", PAYLOAD)
            assert await io.read("obj1") == PAYLOAD
            assert await io.stat("obj1") == len(PAYLOAD)
            # partial read
            assert await io.read("obj1", offset=256, length=16) == PAYLOAD[256:272]
            # overwrite part
            await io.write("obj1", b"XYZ", offset=0)
            assert (await io.read("obj1"))[:4] == b"XYZ" + PAYLOAD[3:4]
            await io.remove("obj1")
            with pytest.raises(RadosError):
                await io.read("obj1")

    run(main())


def test_replicated_data_on_all_replicas():
    async def main():
        async with MiniCluster(n_osds=3) as cluster:
            cl = await cluster.client()
            await cl.create_pool("rep", "replicated", size=3)
            io = cl.io_ctx("rep")
            await io.write_full("o", b"payload")
            pool = cl.osdmap.lookup_pool("rep")
            pg, acting, primary = cl.osdmap.object_to_acting("o", pool.id)
            cid = CollectionId(str(pg))
            for osd in acting:
                st = cluster.stores[osd]
                assert st.read(cid, ObjectId("o")) == b"payload"

    run(main())


# -- EC pools ---------------------------------------------------------------


def test_ec_put_get_roundtrip_default_profile():
    async def main():
        async with MiniCluster(n_osds=4) as cluster:
            cl = await cluster.client()
            await cl.create_pool("ecpool", "erasure")  # k=2 m=1 default
            io = cl.io_ctx("ecpool")
            await io.write_full("obj", PAYLOAD)
            assert await io.read("obj") == PAYLOAD
            assert await io.stat("obj") == len(PAYLOAD)
            # object sizes not stripe-aligned round-trip exactly
            odd = PAYLOAD[:5000]
            await io.write_full("odd", odd)
            assert await io.read("odd") == odd
            # tiny object
            await io.write_full("tiny", b"x")
            assert await io.read("tiny") == b"x"

    run(main())


def test_ec_chunks_land_on_positional_shards():
    """Shard i of the acting set stores chunk i with a valid crc table."""

    async def main():
        async with MiniCluster(n_osds=4) as cluster:
            cl = await cluster.client()
            await cl.create_pool("ecpool", "erasure")
            io = cl.io_ctx("ecpool")
            await io.write_full("obj", PAYLOAD)
            pool = cl.osdmap.lookup_pool("ecpool")
            pg, acting, primary = cl.osdmap.object_to_acting("obj", pool.id)
            assert len(acting) == 3  # k+m
            seen_sizes = set()
            for shard, osd in enumerate(acting):
                store = cluster.stores[osd]
                cid = CollectionId(f"{pg}s{shard}")
                soid = ObjectId("obj", shard)
                chunk = store.read(cid, soid)
                seen_sizes.add(len(chunk))
                hashes = StripeHashes.from_dict(
                    json.loads(store.getattr(cid, soid, StripeHashes.XATTR_KEY))
                )
                assert hashes.verify(
                    shard, 0, np.frombuffer(chunk, dtype=np.uint8)
                )
                # pg log entry rode in the same transaction
                omap = store.omap_get(cid, ObjectId("_pgmeta_", shard))
                entries = [
                    json.loads(v) for k, v in omap.items() if "." in k
                ]
                assert len(entries) == 1
                (entry,) = entries
                assert entry["oid"] == "obj" and entry["op"] == "modify"
            assert len(seen_sizes) == 1  # equal chunk sizes

    run(main())


def test_ec_degraded_read_after_shard_kill():
    """Kill a non-primary shard OSD: reads must reconstruct."""

    async def main():
        async with MiniCluster(n_osds=4) as cluster:
            cl = await cluster.client()
            await cl.create_pool("ecpool", "erasure")
            io = cl.io_ctx("ecpool")
            await io.write_full("obj", PAYLOAD)

            pool = cl.osdmap.lookup_pool("ecpool")
            pg, acting, primary = cl.osdmap.object_to_acting("obj", pool.id)
            victim = next(o for o in acting if o != primary)
            await cluster.kill_osd(victim)
            await cluster.wait_for_osd_down(victim)
            assert await io.read("obj") == PAYLOAD  # reconstructed

    run(main())


def test_ec_primary_failover():
    """Kill the primary: client re-targets and the read reconstructs."""

    async def main():
        async with MiniCluster(n_osds=4) as cluster:
            cl = await cluster.client()
            await cl.create_pool("ecpool", "erasure")
            io = cl.io_ctx("ecpool")
            await io.write_full("obj", PAYLOAD)

            pool = cl.osdmap.lookup_pool("ecpool")
            pg, _acting, primary = cl.osdmap.object_to_acting("obj", pool.id)
            await cluster.kill_osd(primary)
            await cluster.wait_for_osd_down(primary)
            assert await io.read("obj") == PAYLOAD
            # and writes still land (k=2 m=1: min_size=2, 2 shards left)
            await io.write_full("obj2", PAYLOAD[:1000])
            assert await io.read("obj2") == PAYLOAD[:1000]

    run(main())


def test_ec_k4m2_two_failures():
    async def main():
        async with MiniCluster(n_osds=8) as cluster:
            cl = await cluster.client()
            code, status, _ = await cl.command({
                "prefix": "osd erasure-code-profile set", "name": "rs42",
                "profile": {"plugin": "jerasure", "technique": "reed_sol_van",
                            "k": "4", "m": "2"},
            })
            assert code == 0, status
            await cl.create_pool("ec42", "erasure", erasure_code_profile="rs42")
            io = cl.io_ctx("ec42")
            big = bytes(range(256)) * 1024  # 256 KiB
            await io.write_full("big", big)

            pool = cl.osdmap.lookup_pool("ec42")
            pg, acting, primary = cl.osdmap.object_to_acting("big", pool.id)
            victims = [o for o in acting if o != primary][:2]
            for v in victims:
                await cluster.kill_osd(v)
                await cluster.wait_for_osd_down(v)
            assert await io.read("big") == big  # 2-erasure reconstruct

    run(main())


def test_ec_write_full_hashes_a_shard_per_native_call():
    """A 4 MiB write_full on k=8 m=3 with 4 KiB chunks spans 128 stripes:
    the primary's commit hashes each shard's 128 chunks in one native
    crc call, and the stored table matches a per-chunk crc32c."""

    async def main():
        async with MiniCluster(n_osds=16) as cluster:
            cl = await cluster.client()
            code, status, _ = await cl.command({
                "prefix": "osd erasure-code-profile set", "name": "rs83",
                "profile": {"plugin": "isa", "technique": "reed_sol_van",
                            "k": "8", "m": "3",
                            "crush-failure-domain": "osd"},
            })
            assert code == 0, status
            await cl.create_pool("ec83", "erasure",
                                 erasure_code_profile="rs83", pg_num=4)
            io = cl.io_ctx("ec83")
            blob = np.random.default_rng(11).integers(
                0, 256, 4 << 20, dtype=np.uint8).tobytes()
            pool = cl.osdmap.lookup_pool("ec83")
            pg, acting, primary = cl.osdmap.object_to_acting("big", pool.id)
            pec = cluster.osds[primary].perf.get("ec")
            before = pec.get("hinfo_chunks"), pec.get("hinfo_calls")
            await io.write_full("big", blob)
            chunks = pec.get("hinfo_chunks") - before[0]
            calls = pec.get("hinfo_calls") - before[1]
            assert (chunks, calls) == (11 * 128, 11)
            assert pec.get("hinfo_time")[1] >= 1
            assert await io.read("big") == blob
            store = cluster.stores[acting[0]]
            cid, soid = CollectionId(f"{pg}s0"), ObjectId("big", 0)
            shard = store.read(cid, soid)
            table = json.loads(store.getattr(cid, soid, StripeHashes.XATTR_KEY))
            assert table["crcs"][0] == [
                native.crc32c(0xFFFFFFFF, shard[o:o + 4096])
                for o in range(0, len(shard), 4096)
            ]

    run(main())


def test_ec_write_refused_below_min_size():
    async def main():
        async with MiniCluster(n_osds=3) as cluster:
            cl = await cluster.client(op_timeout=2.0, max_retries=2)
            await cl.create_pool("ecpool", "erasure")  # k=2 m=1, min_size=2
            io = cl.io_ctx("ecpool")
            await io.write_full("obj", b"data")
            pool = cl.osdmap.lookup_pool("ecpool")
            pg, acting, primary = cl.osdmap.object_to_acting("obj", pool.id)
            # kill both non-primary shards -> only 1 left < min_size=2
            for o in acting:
                if o != primary:
                    await cluster.kill_osd(o)
                    await cluster.wait_for_osd_down(o)
            with pytest.raises(RadosError):
                await io.write_full("obj2", b"nope")

    run(main())


def test_ec_object_not_found_and_delete_all_shards():
    async def main():
        async with MiniCluster(n_osds=4) as cluster:
            cl = await cluster.client()
            await cl.create_pool("ecpool", "erasure")
            io = cl.io_ctx("ecpool")
            with pytest.raises(RadosError) as ei:
                await io.read("ghost")
            assert ei.value.code == -2  # ENOENT
            await io.write_full("obj", PAYLOAD)
            await io.remove("obj")
            with pytest.raises(RadosError):
                await io.read("obj")
            # shards really gone from every store
            pool = cl.osdmap.lookup_pool("ecpool")
            pg, acting, primary = cl.osdmap.object_to_acting("obj", pool.id)
            for shard, osd in enumerate(acting):
                assert not cluster.stores[osd].exists(
                    CollectionId(f"{pg}s{shard}"), ObjectId("obj", shard)
                )

    run(main())


def test_ec_corrupt_chunk_detected_and_reconstructed():
    """Flip bits in one stored chunk: crc check must reject it and the
    read must reconstruct from the other shards (deep-scrub semantics,
    reference:src/osd/ECBackend.cc:994-1008)."""

    async def main():
        async with MiniCluster(n_osds=4) as cluster:
            cl = await cluster.client()
            await cl.create_pool("ecpool", "erasure")
            io = cl.io_ctx("ecpool")
            await io.write_full("obj", PAYLOAD)
            pool = cl.osdmap.lookup_pool("ecpool")
            pg, acting, primary = cl.osdmap.object_to_acting("obj", pool.id)
            # corrupt shard 0's chunk in place (bypassing the OSD)
            store = cluster.stores[acting[0]]
            cid = CollectionId(f"{pg}s0")
            soid = ObjectId("obj", 0)
            from ceph_tpu.store import Transaction
            store.apply(Transaction().write(cid, soid, 0, b"\xff" * 64))
            assert await io.read("obj") == PAYLOAD

    run(main())


def test_ec_corrupt_remote_chunk_detected():
    """Corrupt a chunk on a NON-primary OSD: the crc must be verified on
    the remote read-reply path too (not only the primary-local fast path)."""

    async def main():
        async with MiniCluster(n_osds=4) as cluster:
            cl = await cluster.client()
            await cl.create_pool("ecpool", "erasure")
            io = cl.io_ctx("ecpool")
            await io.write_full("obj", PAYLOAD)
            pool = cl.osdmap.lookup_pool("ecpool")
            pg, acting, primary = cl.osdmap.object_to_acting("obj", pool.id)
            from ceph_tpu.store import Transaction
            for shard, osd in enumerate(acting):
                if osd != primary:  # corrupt every REMOTE shard one at a time
                    cluster.stores[osd].apply(
                        Transaction().write(
                            CollectionId(f"{pg}s{shard}"),
                            ObjectId("obj", shard), 0, b"\xff" * 64,
                        )
                    )
                    break
            assert await io.read("obj") == PAYLOAD

    run(main())


def test_ec_stale_shard_rejected_after_degraded_overwrite():
    """write v1 -> kill shard osd -> overwrite v2 (degraded) -> restart the
    osd: reads must not mix the stale v1 chunk into the v2 decode."""

    async def main():
        async with MiniCluster(n_osds=4) as cluster:
            cl = await cluster.client()
            await cl.create_pool("ecpool", "erasure")
            io = cl.io_ctx("ecpool")
            v1 = bytes([1]) * 8192
            v2 = bytes([2]) * 8192
            await io.write_full("obj", v1)
            pool = cl.osdmap.lookup_pool("ecpool")
            pg, acting, primary = cl.osdmap.object_to_acting("obj", pool.id)
            victim = next(o for o in acting if o != primary)
            await cluster.kill_osd(victim)
            await cluster.wait_for_osd_down(victim)
            await io.write_full("obj", v2)  # degraded: victim missed this
            await cluster.restart_osd(victim)
            await cluster.wait_for_osd_up(victim)
            got = await io.read("obj")
            assert got == v2, "stale chunk leaked into decode"
            assert await io.stat("obj") == len(v2)

    run(main())


def test_ec_delete_propagates_shard_failure():
    """A shard whose delete transaction fails must fail the client op."""

    async def main():
        async with MiniCluster(n_osds=4) as cluster:
            cl = await cluster.client(op_timeout=3.0, max_retries=1)
            await cl.create_pool("ecpool", "erasure")
            io = cl.io_ctx("ecpool")
            await io.write_full("obj", PAYLOAD)
            pool = cl.osdmap.lookup_pool("ecpool")
            pg, acting, primary = cl.osdmap.object_to_acting("obj", pool.id)
            victim_osd = next(o for o in acting if o != primary)
            store = cluster.stores[victim_osd]
            orig_apply = store.apply

            def broken_apply(txn):
                raise OSError("injected store failure")

            store.apply = broken_apply
            try:
                with pytest.raises(RadosError):
                    await io.remove("obj")
            finally:
                store.apply = orig_apply

    run(main())


def test_many_objects_spread_over_pgs():
    async def main():
        async with MiniCluster(n_osds=4) as cluster:
            cl = await cluster.client()
            await cl.create_pool("ecpool", "erasure", pg_num=16)
            io = cl.io_ctx("ecpool")
            objs = {f"obj-{i}": bytes([i % 256]) * (100 + 37 * i) for i in range(40)}
            await asyncio.gather(
                *(io.write_full(k, v) for k, v in objs.items())
            )
            reads = await asyncio.gather(*(io.read(k) for k in objs))
            assert all(got == objs[k] for k, got in zip(objs, reads))
            pgs = {
                str(cl.osdmap.object_locator_to_pg(k,
                    cl.osdmap.lookup_pool("ecpool").id))
                for k in objs
            }
            assert len(pgs) > 4  # objects actually spread

    run(main())


def test_osd_restart_serves_old_data():
    """Kill + restart an OSD (same store): data written before the kill
    is served after rejoin without any recovery copy."""

    async def main():
        async with MiniCluster(n_osds=4) as cluster:
            cl = await cluster.client()
            await cl.create_pool("ecpool", "erasure")
            io = cl.io_ctx("ecpool")
            await io.write_full("obj", PAYLOAD)
            pool = cl.osdmap.lookup_pool("ecpool")
            pg, acting, primary = cl.osdmap.object_to_acting("obj", pool.id)
            victim = next(o for o in acting if o != primary)
            await cluster.kill_osd(victim)
            await cluster.wait_for_osd_down(victim)
            await cluster.restart_osd(victim)
            await cluster.wait_for_osd_up(victim)
            assert await io.read("obj") == PAYLOAD

    run(main())
