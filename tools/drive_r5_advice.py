"""End-to-end drive of the round-5 ADVICE fixes via the public API
(mini-cluster harness, no pytest): read-only mirror bootstrap under a
live writer, active-active zone sync first contact, MDS client with
rank 0 vacant."""

import asyncio

import jax

jax.config.update("jax_platforms", "cpu")

from ceph_tpu.rados import MiniCluster  # noqa: E402
from ceph_tpu.rbd import RBD, Image, ImageMirrorer  # noqa: E402
from ceph_tpu.rgw import RGWStore, ZoneSyncer  # noqa: E402
from ceph_tpu.mds import CephFSClient  # noqa: E402

ORDER, OBJ = 14, 1 << 14


async def drive_mirror():
    async with MiniCluster(n_osds=4) as cluster:
        cl = await cluster.client()
        await cl.create_pool("src", "replicated", size=2)
        await cl.create_pool("dst", "replicated", size=2)
        sio, dio = cl.io_ctx("src"), cl.io_ctx("dst")
        await RBD(sio).create("vol", 6 * OBJ, order=ORDER,
                              features=["journaling"])
        img = await Image.open(sio, "vol")          # live writer stays open
        await img.write(0, b"live" * 700)
        m = ImageMirrorer(sio, dio, "vol")
        await m.bootstrap()                          # read-only source open
        await img.write(2 * OBJ, b"tail" * 200)
        await img.close()
        n = await m.sync()
        dst = await Image.open(dio, "vol")
        assert await dst.read(0, 2800) == b"live" * 700
        assert await dst.read(2 * OBJ, 800) == b"tail" * 200
        assert "journaling" in dst.features
        await dst.close()
        print(f"mirror: OK (replayed {n} events, dest journaled)")


async def drive_multisite():
    async with MiniCluster(n_osds=3) as cluster:
        cl = await cluster.client()
        a = await RGWStore.create(cl, zone="a")
        b = await RGWStore.create(cl, zone="b")
        await a.create_user("u"); await a.create_bucket("ba", "u")
        await a.put_object("ba", "ka", b"from-a")
        await b.create_user("u"); await b.create_bucket("bb", "u")
        await b.put_object("bb", "kb", b"from-b")
        await ZoneSyncer(a, b, "zone-a").sync()
        await ZoneSyncer(b, a, "zone-b").sync()
        assert (await b.get_object("bb", "kb"))[0] == b"from-b"
        assert (await a.get_object("ba", "ka"))[0] == b"from-a"
        assert (await b.get_object("ba", "ka"))[0] == b"from-a"
        assert (await a.get_object("bb", "kb"))[0] == b"from-b"
        print("multisite: OK (active-active first contact lost nothing)")


async def drive_mds():
    async with MiniCluster(n_osds=3) as cluster:
        cl = await cluster.client()
        for n in ("mds.a", "mds.b"):
            await cluster.start_mds(n)
        await cluster.wait_for_active_mds()
        code, status, _ = await cl.command({"prefix": "fs set max_mds",
                                            "val": 2})
        assert code == 0, status
        async with asyncio.timeout(10):
            while sum(1 for m in cluster.mdss.values() if m.active) < 2:
                await asyncio.sleep(0.02)
        ranks = {m.rank: m for m in cluster.mdss.values() if m.active}
        fs = await CephFSClient.mount(await cluster.client())
        await fs.mkdir("/sub")
        await fs.export_subtree("/sub", 1)
        await fs.write_file("/sub/f", b"alive")
        victim = ranks[0].name
        await cluster.kill_mds(victim)
        await cl.command({"prefix": "mds fail", "name": victim})
        async with asyncio.timeout(10):
            while True:
                m = cl.osdmap
                tbl = m.mds_rank_table() if m else []
                if len(tbl) > 1 and not tbl[0][1] and tbl[1][1]:
                    break
                await asyncio.sleep(0.05)
        fs2 = await CephFSClient.mount(await cluster.client())
        assert await fs2.read_file("/sub/f") == b"alive"
        print("mds: OK (fresh mount served with rank 0 vacant)")


for coro in (drive_mirror, drive_multisite, drive_mds):
    asyncio.run(coro())
print("ALL DRIVES PASSED")
