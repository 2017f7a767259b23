"""Controls and planted faults: the timed path replaced under a run, to
show that the correctness check fails when the answers are wrong.

A control is what a later PR could be tempted to ship:

- ``codec``: the plain reference with one data chunk left out of the
  parity, which breaks "any 3 of 11 chunks may be lost";
- ``rados``: the stores drop the data of every coded shard's write and
  the writes are acknowledged all the same, which breaks "acknowledged
  after all 11 sub-writes are applied";
- ``crush``: the program's device rule program without the host's exact
  re-run of the lanes it flags, i.e. straw2 draws in float32.

Faults are the contract's list as far as a one-chip cell can have them:
a step that leaves its state unchanged, half of the batch left out, an
answer altered where it is produced.  The tests under
``tests/benchmark`` run each at a small size on the CPU.

    python3 benchmark/control.py --workload rs83.encode --seeds 1,2,3 \
        --seconds 3 [--sound]

runs the cell in one process once per seed (with ``--sound``, the
program as it is), and prints each run's checks.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402


class _Codec:
    """A codec whose ``encode_chunks`` is replaced; the rest delegates."""

    def __init__(self, codec, encode):
        self._codec, self.encode_chunks = codec, encode

    def __getattr__(self, name):
        return getattr(self._codec, name)


def _codec_control(config):
    from benchmark.reference import gf256

    profile = config["profile"]
    pos = gf256.data_positions(profile)

    def encode(data):
        short = data.copy()
        short[-1] = 0
        full = gf256.encode(profile, short)
        return full[[i for i in range(full.shape[0]) if i not in pos]]

    return encode


def _codec_fault(kind):
    def wrap(codec):
        def encode(data):
            out = np.array(codec.encode_chunks(data))
            if kind == "unchanged":
                return np.zeros_like(out)
            if kind == "half":
                out[:, out.shape[1] // 2:] = 0
            else:
                out[0, 0] ^= 1
            return out

        return _Codec(codec, encode)

    return wrap


def _drop_parity_writes(stores, k, alter=False):
    """Make every store drop (or with ``alter`` flip a byte of) the data
    of each write to a coded shard (index k and up) of a window object:
    the write is still acknowledged, reads of the data shards still
    succeed, and the object can no longer lose 3 chunks."""
    for store in stores:
        orig = store.apply

        def apply(txn, orig=orig):
            ops = []
            for op in txn.ops:
                if (op[0] == "write" and op[2].name.startswith("bench_")
                        and op[2].shard >= k):
                    if not alter:
                        continue
                    data = bytearray(op[4])
                    data[0] ^= 1
                    op = (*op[:4], bytes(data))
                ops.append(op)
            txn.ops = ops
            return orig(txn)

        store.apply = apply


class _Io:
    def __init__(self, io, skip):
        self._io, self._skip, self._n = io, skip, 0

    def __getattr__(self, name):
        return getattr(self._io, name)

    async def write_full(self, oid, data):
        self._n += 1
        if oid.startswith("bench_") and self._skip(self._n):
            await asyncio.sleep(0)  # answered at once, as a lost write
            return None
        return await self._io.write_full(oid, data)


def _crush_control(cmap_rule_width):
    from ceph_tpu.crush import mapper_jax_hier

    cmap, ruleno, width = cmap_rule_width

    def do_rule(xs):
        out, _ = mapper_jax_hier._hier_engine(cmap, ruleno, xs, width, None)
        return np.array(out)

    return do_rule


def under_test(surface: str, config: dict, what: str):
    """The ``under_test`` seam for one cell's surface: ``what`` is
    ``control``, or a fault ``unchanged``, ``half`` or ``altered``."""

    def seam(kind, obj):
        if surface == "codec" and kind == "codec":
            if what == "control":
                return _Codec(obj, _codec_control(config))
            return _codec_fault(what)(obj)
        if surface == "rados" and kind == "io":
            if what == "unchanged":
                return _Io(obj, lambda n: True)
            if what == "half":
                return _Io(obj, lambda n: n % 2 == 0)
        if surface == "rados" and kind == "cluster":
            if what in ("control", "altered"):
                _drop_parity_writes(obj.stores, int(config["profile"]["k"]),
                                    alter=what == "altered")
        if surface == "crush" and kind == "crush":
            if what == "control":
                return _crush_control(obj.program)
            state = {}

            def do_rule(xs):
                out = np.array(obj(xs))
                if what == "unchanged":
                    out = state.setdefault("first", out)
                elif what == "half":
                    out[len(out) // 2:] = 0
                else:
                    out[:, -1] += 1
                return out

            return do_rule
        return obj

    return seam


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--sound", action="store_true",
                    help="run the program as it is, not the control")
    args = ap.parse_args()
    from benchmark import run

    _, cell, config, traffic = run.load_cell(run.ROOT, args.workload)
    for seed in args.seeds.split(","):
        seam = None if args.sound else under_test(traffic["surface"], config,
                                                  "control")
        line = run.execute(run.parse_args([
            "--workload", args.workload, "--seed", seed,
            "--seconds", str(args.seconds)]), under_test=seam,
            teardown_after=True)
        print(json.dumps({"seed": int(seed), "sound": args.sound,
                          "correct": line["correct"],
                          "attempted": line["attempted"],
                          "checks": line["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    rc = main()
    sys.stdout.flush()
    os._exit(rc)
