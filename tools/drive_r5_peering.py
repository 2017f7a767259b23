"""End-to-end drive of the round-5 peering + mesh data path via the
public API (no pytest): a torn mid-RMW write rolled back across a
primary flip, and an EC write/degraded-read served through the
device-mesh engine."""

import asyncio
import json
import os

flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax

jax.config.update("jax_platforms", "cpu")

from ceph_tpu.osd.daemon import OI_KEY, CollectionId, ObjectId  # noqa: E402
from ceph_tpu.osd.pg_log import (  # noqa: E402
    Eversion, PGLogEntry, add_log_entry_to_txn, read_log, stash_name,
)
from ceph_tpu.rados import MiniCluster  # noqa: E402
from ceph_tpu.store import Transaction  # noqa: E402

PAYLOAD = bytes(range(256)) * 32


async def drive_peering():
    async with MiniCluster(n_osds=4) as cluster:
        cl = await cluster.client()
        await cl.create_pool("ecpool", "erasure")
        io = cl.io_ctx("ecpool")
        await io.write_full("obj", PAYLOAD)  # acked v1
        pool = cl.osdmap.lookup_pool("ecpool")
        pg, acting, primary = cl.osdmap.object_to_acting("obj", pool.id)
        shard = next(s for s, o in enumerate(acting) if o != primary)
        member = acting[shard]
        st = cluster.stores[member]
        cid = CollectionId(f"{pg}s{shard}")
        entries = [e for e in read_log(st, cid, shard) if e.oid == "obj"]
        prior = max(e.version for e in entries)
        # torn mid-RMW state: one shard applied, commit never acked
        v2 = Eversion(prior.epoch, prior.version + 1)
        soid = ObjectId("obj", shard)
        sname = stash_name("obj", v2)
        chunk_len = len(st.read(cid, soid))
        txn = (
            Transaction()
            .create_collection(cid)
            .try_stash(cid, soid, ObjectId(sname, shard))
            .write(cid, soid, 0, b"\xee" * chunk_len)
            .setattr(cid, soid, OI_KEY, json.dumps(
                {"size": chunk_len * 2, "version": v2.to_list()}
            ).encode())
        )
        add_log_entry_to_txn(
            txn, cid, shard, PGLogEntry("modify", "obj", v2, prior,
                                        stash=sname)
        )
        st.apply(txn)
        await cluster.kill_osd(primary)  # the primary dies; flip
        await cluster.wait_for_osd_down(primary)
        async with asyncio.timeout(20):
            while True:
                es = [e for e in read_log(st, cid, shard) if e.oid == "obj"]
                if es and max(e.version for e in es) == prior:
                    break
                await asyncio.sleep(0.1)
        assert await io.read("obj") == PAYLOAD
        print("peering: OK (torn write rolled back across primary flip)")


async def drive_mesh():
    async with MiniCluster(
        n_osds=4, config_overrides={"osd_ec_mesh": True}
    ) as cluster:
        cl = await cluster.client()
        await cl.create_pool("ecpool", "erasure")
        io = cl.io_ctx("ecpool")
        await io.write_full("obj", PAYLOAD)
        pool = cl.osdmap.lookup_pool("ecpool")
        _pg, acting, primary = cl.osdmap.object_to_acting("obj", pool.id)
        assert cluster.osds[primary].perf.get("ec").get(
            "mesh_encode_calls") > 0
        await cluster.kill_osd(acting[0])
        await cluster.wait_for_osd_down(acting[0])
        assert await io.read("obj") == PAYLOAD
        decs = sum(o.perf.get("ec").get("mesh_decode_calls")
                   for o in cluster.osds.values())
        assert decs > 0
        print(f"mesh: OK (encode+reconstruct through the mesh, "
              f"{decs} collective reconstructs)")


asyncio.run(drive_peering())
asyncio.run(drive_mesh())
print("ALL DRIVES PASSED")
