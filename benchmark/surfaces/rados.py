"""The served surface: ``rados bench write`` against an EC pool of an
in-process MiniCluster (mon, OSDs and client on one event loop, loopback
TCP), through ``IoCtx.write_full``.

A closed loop, as ``rados bench write -t <max_in_flight>`` runs:
``max_in_flight`` writers, each sending a new object as soon as its last
one is acknowledged, until the window closes.  Every seed sends the same
sizes; only the bytes differ.  A write's latency runs from the time it
was sent to its acknowledgement; writes still in flight when the window
closes are waited for and count in the tail, not in the bytes.  Warm-up
compiles every dispatcher bucket the window can use, then sends a few
bursts.

The check reads back a seeded sample of the acknowledged objects through
the client, and compares each of their shards, as stored on the OSD that
the PG's acting set names, with the plain reference encoding.
"""

from __future__ import annotations

import asyncio
import math
import time

import numpy as np

from benchmark.reference import gf256


def _payloads(seed: int, blob_bytes: int, size: int):
    blob = np.random.default_rng(seed).integers(
        0, 256, size=blob_bytes + size, dtype=np.uint8).tobytes()
    span = blob_bytes // 4096

    def payload(i: int) -> bytes:
        off = (i * 7919 % span) * 4096
        return blob[off:off + size]

    return payload


def _counters(cluster, client) -> dict:
    out = {"device_ops": 0, "device_batches": 0, "bytes_send": 0,
           "launch_s": 0.0, "launches": 0}
    daemons = [*cluster.osds.values()]
    for osd in daemons:
        lane = osd.ec_dispatch.dump()["totals"]["lanes"]["device"]
        out["device_ops"] += lane["ops"]
        out["device_batches"] += lane["batches"]
        s, c, _, _ = osd.perf.get("ec").get("encode_time")
        out["launch_s"] += s
        out["launches"] += c
    for m in [*daemons, *cluster.mons.values(), client]:
        out["bytes_send"] += m.messenger.perf.get("bytes_send")
    return out


def _shards(cluster, osdmap, pool, oid: str, n: int) -> list:
    from ceph_tpu.store.objectstore import CollectionId, ObjectId

    pg, acting, _ = osdmap.object_to_acting(oid, pool.id)
    out = []
    for i in range(n):
        try:
            out.append(cluster.stores[acting[i]].read(
                CollectionId(f"{pg}s{i}"), ObjectId(oid, i)))
        except (IndexError, KeyError, OSError):
            out.append(None)
    return out


def _differ(got: bytes, row: np.ndarray) -> int:
    n = min(len(got), row.nbytes)
    return int(np.count_nonzero(np.frombuffer(got[:n], np.uint8) != row[:n]))


def _warm_buckets(cluster, pool, profile, size) -> None:
    """Compile every padded batch the EC dispatchers can launch for
    writes of ``size``: one object's stripes rounded up to a power of two,
    doubling up to the dispatcher's ``max_stripes``."""
    from ceph_tpu.osd import ec_util
    from ceph_tpu.osd.ec_dispatch import bucket_stripes

    from benchmark.surfaces.codec import make_codec

    k = int(profile["k"])
    sinfo = ec_util.StripeInfo(pool.stripe_width, pool.stripe_width // k)
    codec = make_codec(profile)
    most = next(iter(cluster.osds.values())).ec_dispatch.max_stripes
    s = bucket_stripes(size // pool.stripe_width)
    while s <= most:
        ec_util.encode(sinfo, codec, np.zeros(s * pool.stripe_width,
                                              dtype=np.uint8))
        s *= 2


async def _run(ctx):
    from ceph_tpu.rados.client import RadosError
    from ceph_tpu.rados.cluster import MiniCluster

    from benchmark.run import Result

    t, cfg = ctx.traffic, ctx.config
    served = cfg["served"]
    profile = cfg["profile"]
    k, m = int(profile["k"]), int(profile["m"])
    size = t["object_bytes"]
    cluster = MiniCluster(n_osds=served["n_osds"])
    loop = asyncio.get_running_loop()
    ctx.finalizers.append(lambda: loop.run_until_complete(cluster.stop()))
    await cluster.start()
    cl = await cluster.client()
    code, status, _ = await cl.command({
        "prefix": "osd erasure-code-profile set", "name": "bench",
        "profile": {**profile,
                    "crush-failure-domain": served["crush-failure-domain"]},
    })
    if code != 0:
        raise RuntimeError(f"profile set failed: {status}")
    await cl.create_pool("bench", "erasure", erasure_code_profile="bench",
                         pg_num=served["pg_num"])
    io = ctx.under_test("io", cl.io_ctx("bench"))
    cluster = ctx.under_test("cluster", cluster)
    _warm_buckets(cluster, cl.osdmap.lookup_pool("bench"), profile, size)
    payload = _payloads(ctx.seed, t["blob_bytes"], size)
    for r, burst in enumerate(t["warmup_bursts"]):
        await asyncio.gather(*(io.write_full(f"warm_{r}_{j}", payload(j))
                               for j in range(burst)))

    sent_at, acked_at = [], {}

    async def writer() -> None:
        while time.perf_counter() < close:
            i = len(sent_at)
            sent_at.append(time.perf_counter())
            try:
                with ctx.span("client_write"):
                    await io.write_full(f"bench_{i}", payload(i))
            except Exception as e:  # counted as a write that never came
                ctx.note(f"bench_{i} write: {e!r}")
                continue
            acked_at[i] = time.perf_counter()

    before = _counters(cluster, cl)
    with ctx.window() as w:
        close = w.start + ctx.seconds
        tasks = [asyncio.ensure_future(writer())
                 for _ in range(t["max_in_flight"])]
        await asyncio.sleep(max(0.0, close - time.perf_counter()))
    done, pending = await asyncio.wait(tasks, timeout=60.0)
    for task in pending:
        task.cancel()
    for task in done:
        task.result()
    after = _counters(cluster, cl)
    ctx.read_memory_peak()

    sent = range(len(sent_at))
    acked = sorted(acked_at)
    in_window = sum(1 for i in acked if acked_at[i] <= w.end)
    gave_up = time.perf_counter()
    lat_ms = sorted(1e3 * (acked_at.get(i, gave_up) - sent_at[i])
                    for i in sent)

    def pct(q: float) -> float:
        return lat_ms[max(0, math.ceil(q * len(lat_ms)) - 1)]

    ctx.note("latency ms: " + " ".join(f"p{round(q * 100)} {pct(q):.1f}"
                                       for q in (0.5, 0.9, 0.95, 0.99, 1)))
    delta = {key: after[key] - before[key] for key in before}

    pick = np.random.default_rng([ctx.seed, 2])
    sample = sorted(pick.choice(acked, size=min(t["check_objects"],
                                                len(acked)), replace=False))
    pool = cl.osdmap.lookup_pool("bench")
    cs = pool.stripe_width // k
    readback_wrong = shards_wrong = 0
    for i in sample:
        want = payload(int(i))
        try:
            got = await asyncio.wait_for(io.read(f"bench_{i}"), 60.0)
        except (RadosError, TimeoutError) as e:
            got = None
            ctx.note(f"bench_{i} read back: {e!r}")
        readback_wrong += got != want
        d = np.frombuffer(want, dtype=np.uint8).reshape(-1, k, cs)
        ref = gf256.encode(profile, np.ascontiguousarray(
            d.transpose(1, 0, 2).reshape(k, -1)))
        stored = _shards(cluster, cl.osdmap, pool, f"bench_{i}", k + m)
        for s, (row, got) in enumerate(zip(ref, stored)):
            if got is None or got != row.tobytes():
                shards_wrong += 1
                ctx.note(f"bench_{i} shard {s}: " + (
                    "missing" if got is None else
                    f"{len(got)} B stored, {row.nbytes} B due, "
                    f"{_differ(got, row)} bytes differ"))
    never = len(sent) - len(acked)
    return Result(
        e2e={"client_MBps": in_window * size / w.elapsed / 1e6,
             "op_p95_ms": pct(0.95)},
        attempted=len(sent), failed=never,
        checks={"writes_never_acked": (never, 0),
                "objects_read_back_wrong": (int(readback_wrong), 0),
                "shards_wrong": (int(shards_wrong), 0)},
        work={"acked_bytes": len(acked) * size, "acked": len(acked),
              "checked_objects": len(sample)},
        counters=delta,
    )


def run(ctx):
    # not asyncio.run: it would cancel and await every daemon task at the
    # end, the teardown that run.py skips by exiting
    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(_run(ctx))
    finally:
        ctx.finalizers.append(loop.close)
