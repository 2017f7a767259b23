"""Drive script: recovery admission control end-to-end (round 5).

Boots a MiniCluster, storms recovery into one rejoined OSD across a
replicated pool and an EC pool, checks the reservation bounds held,
bumps osd_max_backfills at runtime mid-storm, and verifies convergence.
Run: JAX_PLATFORMS=cpu PYTHONPATH=. python tools/drive_r5_throttle.py
"""

import asyncio

from ceph_tpu.rados import MiniCluster
from ceph_tpu.store import CollectionId, ObjectId


async def wait_for(pred, timeout=40.0, what=""):
    async with asyncio.timeout(timeout):
        while not pred():
            await asyncio.sleep(0.02)
    print(f"  ok: {what}")


async def main():
    async with MiniCluster(
        n_osds=4,
        config_overrides={"osd_max_backfills": 1,
                          "osd_recovery_max_active": 2},
    ) as cluster:
        cl = await cluster.client()
        await cl.create_pool("rp", "replicated", pg_num=16, size=3)
        await cl.create_pool("ecp", "erasure", pg_num=8)
        iorp = cl.io_ctx("rp")
        ioec = cl.io_ctx("ecp")
        robjs = {f"r-{i}": bytes([i]) * 4096 for i in range(24)}
        eobjs = {f"e-{i}": bytes([i + 1]) * 8192 for i in range(8)}
        for n, p in robjs.items():
            await iorp.write_full(n, p)
        for n, p in eobjs.items():
            await ioec.write_full(n, p)

        victim = 3
        await cluster.kill_osd(victim)
        await cluster.wait_for_osd_down(victim)
        robjs = {n: bytes([(p[0] + 100) % 256]) * 4096
                 for n, p in robjs.items()}
        eobjs = {n: bytes([(p[0] + 50) % 256]) * 8192
                 for n, p in eobjs.items()}
        for n, p in robjs.items():
            await iorp.write_full(n, p)
        for n, p in eobjs.items():
            await ioec.write_full(n, p)

        await cluster.restart_osd(victim)
        await cluster.wait_for_osd_up(victim)
        rp = cl.osdmap.lookup_pool("rp")
        ecp = cl.osdmap.lookup_pool("ecp")
        await wait_for(
            lambda: any(victim in cl.osdmap.object_to_acting(n, rp.id)[1]
                        for n in robjs),
            what="client map shows victim rejoined",
        )

        # live knob: raise the budget mid-storm; queued waiters must be
        # granted immediately (observer -> AsyncReserver.set_max)
        await asyncio.sleep(0.2)
        vic = cluster.osds[victim]
        print(f"  mid-storm: victim remote granted={len(vic.remote_reserver.granted)} "
              f"max_granted={vic.remote_reserver.max_granted}")
        assert vic.remote_reserver.max_granted <= 1, "bound broken pre-bump"
        for osd in cluster.osds.values():
            osd.config.set("osd_max_backfills", 2)
        assert vic.remote_reserver.max_allowed == 2

        def replicated_done():
            checked = 0
            for n, p in robjs.items():
                pg, acting, _ = cl.osdmap.object_to_acting(n, rp.id)
                if victim not in acting:
                    continue
                checked += 1
                try:
                    if bytes(vic.store.read(
                            CollectionId(str(pg)), ObjectId(n))) != p:
                        return False
                except KeyError:
                    return False
            return checked > 0

        def ec_done():
            checked = 0
            for n, p in eobjs.items():
                pg, acting, _ = cl.osdmap.object_to_acting(n, ecp.id)
                if victim not in acting:
                    continue
                s = acting.index(victim)
                checked += 1
                try:
                    vic.store.read(
                        CollectionId(f"{pg}s{s}"), ObjectId(n, s)
                    )
                except KeyError:
                    return False
            return checked > 0

        await wait_for(replicated_done, what="replicated storm drained")
        await wait_for(ec_done, what="EC shards rebuilt on victim")

        waits = sum(o.perf.get("recovery").get("reservation_waits")
                    for o in cluster.osds.values())
        pushes = {i: o.perf.get("recovery").get("pushes")
                  for i, o in cluster.osds.items()}
        print(f"  pushes per osd: {pushes}; reservation waits: {waits}")
        assert sum(pushes.values()) > 0
        for i, osd in cluster.osds.items():
            assert osd.recovery.max_active_pushes <= 2, (i, osd.recovery.max_active_pushes)
            assert osd.local_reserver.max_granted <= 2
            assert osd.remote_reserver.max_granted <= 2
        for n, p in robjs.items():
            assert await iorp.read(n) == p
        for n, p in eobjs.items():
            assert await ioec.read(n) == p
        print("PASS: admission-controlled recovery converged byte-exact")


if __name__ == "__main__":
    asyncio.run(main())
