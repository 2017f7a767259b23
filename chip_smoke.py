"""Bring-up smoke test: the system's main path on one TPU chip.

One process holds the chip for the whole run and drives the normal
entry points, each checked byte for byte against the repo's own host
references:

- device: ``jax.devices()`` must be a TPU (there is no CPU branch);
- codec: the five BASELINE.md configs built through the plugin
  registry (as ``ceph_tpu/tools/ec_benchmark.py`` builds them), a
  64-object batched encode and decodes with one and with m erasures,
  against the host oracle (``ops.gf`` / ``native/ec_cpu.cc``); every
  launch names the engine that served it, and a BLOCK-aligned launch
  served by XLA fails the run;
- served: a MiniCluster(n_osds=11) RS(8,3) pool takes a ``rados
  bench``-sized write (64 x 4 MiB, 16 in flight), reads it back, loses
  an OSD and reads it degraded; the EC dispatchers must have served
  device-lane batches and nothing from the host lanes or failover;
- crush: ``crushtool --test`` over 2^20 inputs on a 1024-OSD map for a
  3-replica and an EC(8+3) rule, on the vectorized backend, matched to
  the scalar mapper on 4096 sampled inputs;
- trace: one short device-trace window, split into fused_op / dma /
  collective time.

``--four-chips`` runs only the mesh EC lane (MeshEcEngine over four
chips) and the one-chip engine it is compared with.

The last line of stdout is ``{"ok": true, "device": {...}}``; any
failure exits non-zero without it.
"""

from __future__ import annotations

import argparse
import asyncio
import dataclasses
import itertools
import json
import sys
import time

import numpy as np

SEED = 21
T0 = time.time()

# BASELINE.md:29-33, in its order.  LRC takes l=3: the reference
# rejects k=8 m=4 l=4 (k must be a multiple of (k+m)/l,
# reference:src/erasure-code/lrc/ErasureCodeLrc.cc:321-331), and l=3 is
# the valid neighbour the repo's corpus profile uses.
CODECS = (
    ("isa RS k=8 m=3", "isa",
     {"technique": "reed_sol_van", "k": "8", "m": "3"}, 1 << 20),
    ("jerasure reed_sol_van k=2 m=1", "jerasure",
     {"technique": "reed_sol_van", "k": "2", "m": "1"}, 4096),
    ("jerasure cauchy_good k=10 m=4", "jerasure",
     {"technique": "cauchy_good", "k": "10", "m": "4", "w": "8",
      "packetsize": "4096"}, 1 << 20),
    ("lrc k=8 m=4 l=3", "lrc", {"k": "8", "m": "4", "l": "3"}, 1 << 20),
    ("shec k=8 m=4 c=3", "shec", {"k": "8", "m": "4", "c": "3"}, 1 << 20),
)
RS83 = {"plugin": "isa", "technique": "reed_sol_van", "k": "8", "m": "3"}


@dataclasses.dataclass(frozen=True)
class Sizes:
    codec_objects: int = 64  # objects per encode launch (bench.py)
    object_scale: int = 1  # divides each codec config's object size
    served_objects: int = 64
    served_object_bytes: int = 4 << 20  # rados bench -b default
    served_in_flight: int = 16  # rados bench -t default
    served_pg_num: int = 32
    crush_osds: int = 1024
    crush_inputs: int = 1 << 20
    crush_samples: int = 4096
    mesh_stripes: int = 64  # x 1 MiB stripes of RS(8,3)
    mesh_chunk: int = 128 << 10


class SmokeFailure(Exception):
    pass


def log(msg: str) -> None:
    print(f"[chip_smoke +{time.time() - T0:7.1f}s] {msg}", flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def require_tpu():
    """The device phase: the first device must be a TPU."""
    import jax

    devs = jax.devices()
    d = devs[0]
    log(f"device: platform={d.platform} kind={d.device_kind!r} "
        f"count={len(devs)}")
    check(d.platform == "tpu", f"no TPU: jax found {d.platform!r}")
    return devs


# -- codec -------------------------------------------------------------------

def _engines(platform: str) -> list[tuple]:
    """Drain the engine log of the launch just run; fail a BLOCK-aligned
    GF launch that XLA served on a TPU."""
    from ceph_tpu.ops import gf_jax, gf_pallas

    got = sorted(gf_jax.ENGINE_LOG.items())
    gf_jax.ENGINE_LOG.clear()
    for (kernel, rows_in, rows_out, lanes), engine in got:
        check(
            platform != "tpu" or lanes % gf_pallas.BLOCK or
            engine == "pallas",
            f"{kernel} [{rows_in}->{rows_out}] x {lanes} lanes is "
            f"BLOCK-aligned but {engine} served it",
        )
    return got


def _fmt_engines(got: list[tuple]) -> str:
    if not got:
        return "compiled earlier"
    return ", ".join(f"{engine}:{kernel}[{rin}->{rout}]x{lanes}"
                     for (kernel, rin, rout, lanes), engine in got)


def _layout(codec, data: np.ndarray, parity: np.ndarray) -> np.ndarray:
    """All n chunk rows in chunk-position order (LRC interleaves its
    local parity between the data chunks, per its chunk mapping)."""
    n = codec.get_chunk_count()
    data_pos = codec.get_chunk_mapping() or list(range(data.shape[0]))
    full = np.empty((n, data.shape[1]), dtype=np.uint8)
    full[data_pos] = data
    full[[i for i in range(n) if i not in data_pos]] = parity
    return full


def _erasure_patterns(codec, m: int, probe: np.ndarray):
    """(label, missing) pairs: one data chunk, then m erasures that are
    data-only, parity-only and mixed — for each the first set in a
    fixed order that the host oracle decodes (SHEC and LRC do not
    decode every m-set)."""
    n, k = codec.get_chunk_count(), codec.get_data_chunk_count()
    data_pos = sorted(codec.get_chunk_mapping() or range(k))
    par_pos = [i for i in range(n) if i not in data_pos]
    cands = {
        "data": itertools.combinations(data_pos, m),
        "parity": itertools.combinations(par_pos, m),
        "mixed": (
            tuple(sorted(d + p))
            for d in itertools.combinations(data_pos, (m + 1) // 2)
            for p in itertools.combinations(par_pos, m // 2)
        ),
    }
    yield "1 erasure", (data_pos[0],)
    for label, combos in cands.items():
        for missing in combos:
            present = [i for i in range(n) if i not in missing]
            try:
                got = codec.decode_chunks_host(
                    present, probe[present], list(missing))
            except (IOError, ValueError):
                continue
            if np.array_equal(got, probe[list(missing)]):
                yield f"{m} erasures {label}", missing
                break
        else:
            raise SmokeFailure(f"no decodable {label} {m}-erasure set")


def phase_codec(sizes: Sizes, platform: str) -> None:
    from ceph_tpu.models import registry
    from ceph_tpu.ops import gf_jax

    rng = np.random.default_rng(SEED)
    gf_jax.ENGINE_LOG.clear()
    for name, plugin, profile, obj_bytes in CODECS:
        codec = registry.instance().factory(plugin, dict(profile))
        k = codec.get_data_chunk_count()
        chunk = codec.get_chunk_size(obj_bytes // sizes.object_scale)
        data = rng.integers(0, 256, size=(k, sizes.codec_objects * chunk),
                            dtype=np.uint8)
        t = time.perf_counter()
        parity = codec.encode_chunks(data)
        dt = time.perf_counter() - t
        engines = _engines(platform)
        check(np.array_equal(parity, codec.encode_chunks_host(data)),
              f"{name}: encode differs from the host oracle")
        log(f"codec {name}: encode {sizes.codec_objects} x "
            f"{obj_bytes // sizes.object_scale} B ({data.nbytes} B) "
            f"byte-exact in {dt:.3f}s [{_fmt_engines(engines)}]")
        full = _layout(codec, data, parity)
        n = full.shape[0]
        probe = _layout(codec, data[:, :chunk], parity[:, :chunk])
        for label, missing in _erasure_patterns(
                codec, int(profile["m"]), probe):
            present = [i for i in range(n) if i not in missing]
            t = time.perf_counter()
            got = codec.decode_chunks(present, full[present], list(missing))
            dt = time.perf_counter() - t
            engines = _engines(platform)
            check(np.array_equal(got, full[list(missing)]),
                  f"{name}: decode of {missing} lost bytes")
            check(np.array_equal(got, codec.decode_chunks_host(
                present, full[present], list(missing))),
                f"{name}: decode of {missing} differs from the host oracle")
            log(f"codec {name}: decode {label} {list(missing)} byte-exact "
                f"in {dt:.3f}s [{_fmt_engines(engines)}]")


# -- served path ---------------------------------------------------------------

_MUST_BE_ZERO = ("native_direct", "fallback_direct", "failovers",
                 "replayed_ops")


async def _served(sizes: Sizes, platform: str) -> dict:
    from ceph_tpu.rados.cluster import MiniCluster

    rng = np.random.default_rng(SEED + 1)
    blob = rng.integers(0, 256, size=sizes.served_objects
                        * sizes.served_object_bytes, dtype=np.uint8)
    objs = {
        f"benchmark_data_{i}": blob[i * sizes.served_object_bytes:
                                    (i + 1) * sizes.served_object_bytes]
        .tobytes()
        for i in range(sizes.served_objects)
    }
    sem = asyncio.Semaphore(sizes.served_in_flight)

    async def each(fn):
        async def one(oid):
            async with sem:
                return await fn(oid)
        return await asyncio.gather(*(one(oid) for oid in objs))

    async with MiniCluster(n_osds=11) as cluster:
        cl = await cluster.client()
        code, status, _ = await cl.command({
            "prefix": "osd erasure-code-profile set", "name": "rs83",
            "profile": {**RS83, "crush-failure-domain": "osd"},
        })
        check(code == 0, f"profile set failed: {status}")
        await cl.create_pool("smoke", "erasure",
                             erasure_code_profile="rs83",
                             pg_num=sizes.served_pg_num)
        io = cl.io_ctx("smoke")
        total = sizes.served_objects * sizes.served_object_bytes
        t = time.perf_counter()
        await each(lambda oid: io.write_full(oid, objs[oid]))
        log(f"served: wrote {sizes.served_objects} x "
            f"{sizes.served_object_bytes} B ({total} B), "
            f"{sizes.served_in_flight} in flight, in "
            f"{time.perf_counter() - t:.3f}s")
        for phase in ("read", "degraded read"):
            if phase == "degraded read":
                victim = cluster.osds[0]
                await cluster.kill_osd(0)
                await cluster.wait_for_osd_down(0)
                log("served: killed osd.0")
            t = time.perf_counter()
            got = await each(io.read)
            check(all(g == objs[oid] for g, oid in zip(got, objs)),
                  f"served: {phase} returned different bytes")
            log(f"served: {phase} of every object byte-exact in "
                f"{time.perf_counter() - t:.3f}s")
        totals: dict = {key: 0 for key in _MUST_BE_ZERO}
        totals["device_batches"] = totals["device_ops"] = 0
        for osd in [*cluster.osds.values(), victim]:
            t = osd.ec_dispatch.dump()["totals"]
            for key in _MUST_BE_ZERO:
                totals[key] += t[key]
            totals["device_batches"] += t["lanes"]["device"]["batches"]
            totals["device_ops"] += t["lanes"]["device"]["ops"]
        return totals


def phase_served(sizes: Sizes, platform: str) -> None:
    from ceph_tpu.ops import gf_jax

    gf_jax.ENGINE_LOG.clear()
    totals = asyncio.run(_served(sizes, platform))
    log(f"served: ec_dispatch totals over all OSDs {json.dumps(totals)}")
    log(f"served: engines [{_fmt_engines(_engines(platform))}]")
    check(totals["device_batches"] > 0,
          "served: no batch ran on the device lane")
    for key in _MUST_BE_ZERO:
        check(totals[key] == 0, f"served: {key} = {totals[key]}, must be 0")


# -- CRUSH bulk placement ---------------------------------------------------

def phase_crush(sizes: Sizes) -> None:
    from ceph_tpu.crush import mapper, mapper_jax
    from ceph_tpu.crush.map import CRUSH_ITEM_NONE
    from ceph_tpu.crush.tester import CrushTester
    from ceph_tpu.osd.churn import synthetic_map

    osdmap = synthetic_map(sizes.crush_osds, 16, replicated=(3, 256),
                           ec=(RS83, 256))
    cmap = osdmap.crush
    weight = cmap.get_weights()
    rng = np.random.default_rng(SEED + 2)
    for pool in sorted(osdmap.pools.values(), key=lambda p: p.id):
        rule = cmap.find_rule(pool.crush_ruleset, pool.type, pool.size)
        tester = CrushTester(cmap)
        tester.min_x, tester.max_x = 0, sizes.crush_inputs - 1
        rep = tester.test_rule(rule, pool.size)
        log(f"crush {pool.name} (rule {rule}, size {pool.size}): "
            f"{rep.num_inputs} inputs on the {rep.backend} backend in "
            f"{rep.elapsed_seconds:.3f}s, {rep.bad_mappings} bad mappings, "
            f"{sum(rep.device_counts.values())} placements")
        check(rep.backend == "vectorized",
              f"crush {pool.name}: served by the {rep.backend} mapper")
        # a sample of the inputs mapped on the vectorized mapper and
        # checked against the scalar one
        xs = np.sort(rng.choice(sizes.crush_inputs, sizes.crush_samples,
                                replace=False)).astype(np.uint32)
        vec = mapper_jax.vec_do_rule(cmap, rule, xs, pool.size)
        ws = mapper.Workspace(cmap)
        for x, row in zip(xs, vec):
            want = mapper.crush_do_rule(cmap, rule, int(x), pool.size,
                                        weight=weight, workspace=ws)
            want = want + [CRUSH_ITEM_NONE] * (pool.size - len(want))
            check(list(row) == want,
                  f"crush {pool.name}: x={x} vectorized {list(row)} "
                  f"!= scalar {want}")
        log(f"crush {pool.name}: {len(xs)} sampled inputs match the "
            f"scalar mapper")


# -- device trace -----------------------------------------------------------

def phase_trace(sizes: Sizes) -> None:
    from ceph_tpu.models import registry
    from ceph_tpu.ops.device_trace import tracer

    codec = registry.instance().factory("isa", dict(RS83))
    k = codec.get_data_chunk_count()
    chunk = codec.get_chunk_size((1 << 20) // sizes.object_scale)
    data = np.random.default_rng(SEED + 3).integers(
        0, 256, size=(k, sizes.codec_objects * chunk), dtype=np.uint8)
    codec.encode_chunks(data)  # compiled outside the window
    svc = tracer()
    st = svc.start(duration=60.0, label="chip_smoke", max_duration=60.0)
    check(bool(st.get("success")), f"trace: window did not open: {st}")
    for _ in range(3):
        codec.encode_chunks(data)
    bd = svc.stop()
    check(bd.get("op_events", 0) > 0,
          f"trace: no device op in the window: {bd}")
    log(f"trace: {bd['op_events']} op events, device seconds "
        f"{bd['device_seconds']} split {json.dumps(bd['buckets'])}")
    for op in bd.get("top_ops", [])[:8]:
        log(f"trace: top op {op['name']!r} [{op['bucket']}] "
            f"x{op['count']} {op['seconds']}s")


# -- four chips: the mesh EC lane -------------------------------------------

def phase_mesh(sizes: Sizes, devices) -> None:
    from ceph_tpu.models import registry
    from ceph_tpu.osd import ec_util
    from ceph_tpu.parallel.engine import MeshEcEngine

    check(len(devices) >= 4, f"mesh: {len(devices)} devices, need 4")
    codec = registry.instance().factory("isa", dict(RS83))
    C = sizes.mesh_chunk
    sinfo = ec_util.StripeInfo(8 * C, C)
    buf = np.random.default_rng(SEED + 4).integers(
        0, 256, size=sizes.mesh_stripes * sinfo.stripe_width,
        dtype=np.uint8)
    eng = MeshEcEngine(devices=devices[:4])
    t = time.perf_counter()
    mesh_shards = eng.encode_batch(sinfo, codec, buf)
    log(f"mesh: encode {buf.nbytes} B on a {eng.mesh_key(8)} (pg, shard) "
        f"mesh in {time.perf_counter() - t:.3f}s")
    one_shards = ec_util.encode(sinfo, codec, buf)
    for i in range(11):
        check(np.array_equal(mesh_shards[i], one_shards[i]),
              f"mesh: shard {i} differs from the one-chip engine")
    survivors = {i: v for i, v in mesh_shards.items() if i != 0}
    t = time.perf_counter()
    mesh_bytes = eng.decode_concat(sinfo, codec, survivors)
    log(f"mesh: decode_concat with shard 0 lost in "
        f"{time.perf_counter() - t:.3f}s")
    one_bytes = ec_util.decode_concat(sinfo, codec, survivors)
    check(mesh_bytes == one_bytes == buf.tobytes(),
          "mesh: decode_concat differs from the one-chip engine")
    log("mesh: encode and decode_concat bytes equal the one-chip engine's")
    # the programs themselves, run once more: their outputs must span
    # the four devices, not sit on one
    d3 = buf.reshape(sizes.mesh_stripes, 8, C)
    surv = np.stack([survivors[r] for r in sorted(survivors)[:8]])
    for key, step in list(eng._programs.items()):
        out = step(d3 if key[0] == "enc" else surv)
        n = len(out.sharding.device_set)
        log(f"mesh: {key[0]} program output spans {n} devices")
        check(n == 4, f"mesh: {key[0]} output spans {n} devices, not 4")


# -- driver -----------------------------------------------------------------

def run(sizes: Sizes, four_chips: bool = False,
        device_check=require_tpu) -> dict:
    """Every phase in order; returns the last line's device record.
    Tests hand in a ``device_check`` that accepts the CPU."""
    from ceph_tpu.utils.arch import configure_compile_cache

    log(f"compile cache: {configure_compile_cache()}")
    devices = device_check()
    platform = devices[0].platform
    if four_chips:
        phase_mesh(sizes, devices)
        count = 4
    else:
        for name, fn in (
            ("codec", lambda: phase_codec(sizes, platform)),
            ("served", lambda: phase_served(sizes, platform)),
            ("crush", lambda: phase_crush(sizes)),
            ("trace", lambda: phase_trace(sizes)),
        ):
            t = time.perf_counter()
            fn()
            log(f"phase {name}: ok in {time.perf_counter() - t:.1f}s")
        count = len(devices)
    return {"platform": platform, "kind": devices[0].device_kind,
            "count": count}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the mesh EC lane over four chips")
    args = ap.parse_args(argv)
    try:
        device = run(Sizes(), four_chips=args.four_chips)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
