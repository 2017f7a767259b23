"""The plug-in surface: ``encode_chunks`` through the erasure-code
registry, as ``ceph_erasure_code_benchmark --batch`` drives it.

One caller in a closed loop encodes ``objects_per_call`` objects of
``object_bytes`` per call, cycling over ``distinct_batches`` batches
made from the seed.  Data starts and ends in host memory.  ``kept`` of
the window's outputs, drawn from the seed, and the last one are kept
and compared byte for byte with the plain reference.
"""

from __future__ import annotations

import numpy as np

from benchmark import peaks
from benchmark.reference import gf256


def make_codec(profile: dict):
    from ceph_tpu.models import registry

    rest = {k: v for k, v in profile.items() if k != "plugin"}
    return registry.instance().factory(profile["plugin"], rest)


def run(ctx):
    from benchmark.run import Result

    t = ctx.traffic
    profile = ctx.config["profile"]
    codec = ctx.under_test("codec", make_codec(profile))
    k, n = codec.get_data_chunk_count(), codec.get_chunk_count()
    objects, obj_bytes = t["objects_per_call"], t["object_bytes"]
    chunk = codec.get_chunk_size(obj_bytes)
    rng = np.random.default_rng(ctx.seed)
    batches = [rng.integers(0, 256, size=(k, objects * chunk),
                            dtype=np.uint8)
               for _ in range(t["distinct_batches"])]
    for b in batches:
        codec.encode_chunks(b)
    # a seeded reservoir: every call of the window is equally likely to
    # be kept for the check, and the last one always is
    pick = np.random.default_rng([ctx.seed, 1])
    kept, calls, last = {}, 0, None
    with ctx.window() as w:
        while not w.done():
            with ctx.span("codec_call"):
                out = codec.encode_chunks(batches[calls % len(batches)])
            if len(kept) < t["kept"]:
                kept[calls] = out
            elif pick.random() < t["kept"] / (calls + 1):
                del kept[sorted(kept)[pick.integers(t["kept"])]]
                kept[calls] = out
            last = (calls, out)
            calls += 1
    kept[last[0]] = last[1]
    ctx.read_memory_peak()
    del codec, out, last
    data_bytes = calls * objects * chunk * k
    pos = gf256.data_positions(profile)
    coded = [i for i in range(n) if i not in pos]
    refs, wrong, checked = {}, 0, 0
    for idx, got in sorted(kept.items()):
        b = idx % len(batches)
        if b not in refs:
            refs[b] = gf256.encode(profile, batches[b])[coded]
        want = refs[b]
        if got.shape != want.shape:
            wrong += want.size
        else:
            wrong += int(np.count_nonzero(got != want))
        checked += 1
    return Result(
        e2e={"codec_GBps": data_bytes / w.elapsed / 1e9},
        attempted=calls, failed=0,
        checks={"parity_bytes_wrong": (wrong, 0)},
        work={"calls": calls, "checked_calls": checked,
              "data_bytes": data_bytes,
              "hbm_min_bytes": peaks.codec_min_bytes(data_bytes, k, n)},
    )
