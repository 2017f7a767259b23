"""Record a profiler trace of a few RS(8,3) encodes on the chip and dump
its raw structure: every plane, its lines and the first events of each.

This is how the trace reduction (``benchmark/trace.py``) was fitted to
a real TPU trace, and how its CPU fixture was recorded:

    chiprun -- python3 benchmark/record_trace.py --out chiprun_out/trace

writes ``<out>/fixture.xplane.pb`` (two small encodes) and
``<out>/cell.xplane.pb`` (three encodes at the cell's size), and prints
the dump of both.
"""

from __future__ import annotations

import argparse
import glob
import os
import shutil
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402


def dump(path: str, events_per_line: int = 12) -> None:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    print(f"== {path} ({os.path.getsize(path)} B)")
    for plane in pd.planes:
        lines = list(plane.lines)
        print(f"plane {plane.name!r}: {len(lines)} lines, "
              f"stats {dict(plane.stats) if plane.stats else {}}")
        for line in lines:
            evs = list(line.events)
            busy = sum(e.duration_ns for e in evs)
            print(f"  line {line.name!r}: {len(evs)} events, "
                  f"sum {busy / 1e6:.3f} ms")
            for e in evs[:events_per_line]:
                stats = dict(e.stats) if e.stats else {}
                short = {k: (v if len(str(v)) < 60 else str(v)[:60])
                         for k, v in list(stats.items())[:6]}
                print(f"    {e.name[:70]!r} start {e.start_ns:.0f} "
                      f"dur {e.duration_ns / 1e3:.3f} us {short}")


def record(out: str, name: str, objects: int, calls: int) -> str:
    import jax

    from ceph_tpu.models import registry

    codec = registry.instance().factory(
        "isa", {"technique": "reed_sol_van", "k": "8", "m": "3"})
    data = np.random.default_rng(7).integers(
        0, 256, size=(8, objects * (128 << 10)), dtype=np.uint8)
    codec.encode_chunks(data)  # compile outside the trace
    tmp = tempfile.mkdtemp(prefix="trace")
    with jax.profiler.trace(tmp):
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(calls):
                with jax.profiler.TraceAnnotation("bench.codec_call"):
                    codec.encode_chunks(data)
    [pb] = glob.glob(f"{tmp}/plugins/profile/*/*.xplane.pb")
    dst = os.path.join(out, f"{name}.xplane.pb")
    shutil.copy(pb, dst)
    shutil.rmtree(tmp)
    return dst


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    import jax

    from ceph_tpu.utils.arch import configure_compile_cache

    configure_compile_cache()
    d = jax.devices()[0]
    print(f"device {d.platform} {d.device_kind!r} x{len(jax.devices())}")
    if d.platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 1
    os.makedirs(args.out, exist_ok=True)
    for name, objects, calls in (("fixture", 2, 2), ("cell", 64, 3)):
        dump(record(args.out, name, objects, calls))
    return 0


if __name__ == "__main__":
    sys.exit(main())
