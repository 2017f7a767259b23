"""Inside-the-kernel device tracing (ISSUE 9 / ROADMAP 5a): trace-event
classification pinned by a checked-in fixture, interval attribution,
the one-window-at-a-time trace service round-tripping on the cpu
backend (dispatcher batch and mesh-reconstruct windows, ICI-collective
bucket distinct from rebuild compute), the device-launch flight
recorder (ring semantics, dispatcher wiring, SLOW_OPS dump
enrichment), and the live-cluster surfaces: `kernel trace
start/stop/status/dump` + `dump_launch_history` over admin sockets,
with a trace window open across the PR-7 fault matrix adding zero
failed client ops."""

import asyncio
import gzip
import json
import os
import pathlib
import time

import numpy as np
import pytest

from ceph_tpu.common.op_tracker import OpTracker
from ceph_tpu.common.tracing import current_trace
from ceph_tpu.models.matrix_codec import MatrixErasureCode
from ceph_tpu.ops import matrices as mx
from ceph_tpu.ops.device_trace import (
    BUCKETS,
    DeviceTracer,
    FlightRecorder,
    classify_trace_event,
    parse_trace_dir,
    summarize_events,
    tracer,
)
from ceph_tpu.ops.profiler import profiler
from ceph_tpu.osd import ec_util
from ceph_tpu.osd.ec_dispatch import ECDispatcher

GOLDEN = pathlib.Path(__file__).parent / "golden" / "device_trace_events.json"


def run(coro):
    return asyncio.run(coro)


def _codec(k: int = 2, m: int = 1) -> MatrixErasureCode:
    return MatrixErasureCode(k, m, 8, mx.isa_rs_vandermonde(k, m))


def _sinfo(k: int = 2, cs: int = 512) -> ec_util.StripeInfo:
    return ec_util.StripeInfo(stripe_width=cs * k, chunk_size=cs)


# -- classification -----------------------------------------------------------


class TestClassify:
    def test_hlo_op_families(self):
        hlo = {"hlo_module": "jit_step", "hlo_op": "x"}
        assert classify_trace_event("fusion.3", hlo) == "fused_op"
        assert classify_trace_event("dot.1", hlo) == "fused_op"
        # hyphenated collectives only: reduce-window is plain compute
        assert classify_trace_event("reduce-window", hlo) == "fused_op"
        assert classify_trace_event("reduce.8", hlo) == "fused_op"
        assert classify_trace_event("all-gather.1", hlo) == "collective"
        assert classify_trace_event("all-reduce-start", hlo) == "collective"
        assert classify_trace_event("reduce-scatter.2", hlo) == "collective"
        assert classify_trace_event("collective-permute.1", hlo) \
            == "collective"
        # HLO send/recv are cross-chip transfers
        assert classify_trace_event("send.1", hlo) == "collective"
        assert classify_trace_event("copy.2", hlo) == "dma"
        assert classify_trace_event("copy-start.1", hlo) == "dma"
        assert classify_trace_event("infeed.1", hlo) == "dma"

    def test_runtime_and_python_noise_ignored(self):
        """Runtime scaffolding WRAPS the op events counted above —
        classifying it would double-count every launch."""
        assert classify_trace_event("TfrtCpuExecutable::Execute") is None
        assert classify_trace_event("ThunkExecutor::Execute "
                                    "(wait for completion)") is None
        assert classify_trace_event("$profiler.py:91 start_trace") is None
        assert classify_trace_event("PjitFunction(<lambda>)") is None
        # a host event merely CONTAINING "send" is not a collective
        assert classify_trace_event("MessageSendLoop") is None

    def test_dma_thread_rows(self):
        """TPU traces put DMA engines on their own rows without
        per-event hlo args — the thread name classifies them."""
        assert classify_trace_event("0xaf 128KiB", None,
                                    "DMA transfers") == "dma"
        assert classify_trace_event("anything", None, "Infeed") == "dma"

    def test_tpu_op_rows(self):
        """A TPU device's "XLA Ops" row is one event per HLO op, with or
        without hlo args; the "XLA Modules" spans that wrap them are not
        counted again."""
        assert classify_trace_event("fusion.7", None, "XLA Ops") \
            == "fused_op"
        assert classify_trace_event("all-gather.2", {}, "XLA Ops") \
            == "collective"
        assert classify_trace_event("copy.1", None, "XLA Ops") == "dma"
        assert classify_trace_event("jit_fn(123)", None,
                                    "XLA Modules") is None


class TestFixture:
    """The checked-in trace-event capture pins bucket classification —
    a jax upgrade that changes event shapes fails HERE, not silently
    in production dumps."""

    def _layout(self, tmp_path, gz: bool):
        run_dir = tmp_path / "plugins" / "profile" / "2026_08_04"
        run_dir.mkdir(parents=True)
        raw = GOLDEN.read_bytes()
        if gz:
            (run_dir / "host.trace.json.gz").write_bytes(
                gzip.compress(raw)
            )
        else:
            (run_dir / "host.trace.json").write_bytes(raw)
        return tmp_path

    @pytest.mark.parametrize("gz", [True, False])
    def test_parse_and_buckets(self, tmp_path, gz):
        events, threads = parse_trace_dir(str(self._layout(tmp_path, gz)))
        assert threads[(1, 11)] == "DMA transfers"
        s = summarize_events(events, threads)
        assert s["op_events"] == 6
        # microsecond durations from the fixture, exactly
        assert s["buckets"] == {"fused_op": 0.00084, "dma": 0.00035,
                                "collective": 0.0007}
        assert s["device_seconds"] == pytest.approx(0.00189)
        names = {o["name"] for o in s["top_ops"]}
        assert "TfrtCpuExecutable::Execute" not in names
        assert "all-gather.1" in names

    def test_parse_empty_dir_raises(self, tmp_path):
        with pytest.raises(FileNotFoundError):
            parse_trace_dir(str(tmp_path))

    def test_attribution_by_interval_overlap(self, tmp_path):
        """Events land in the engine whose launch interval contains
        them; events >2 ms from every interval stay unattributed."""
        events, threads = parse_trace_dir(
            str(self._layout(tmp_path, gz=True))
        )
        # anchor_offset=0: event ts (us) maps to ts/1e6 on the pc
        # timeline.  One interval covers the jit_step/compute cluster
        # (1.0-1.9 ms), one the all-gather (1.9-2.8 ms); the DMA-row
        # infeed at 1.2 ms falls inside the first.
        s = summarize_events(
            events, threads,
            intervals=[
                (0.0009, 0.0019, "gf_encode", "k-enc"),
                (0.0019, 0.0028, "mesh_reconstruct", "k-rec"),
            ],
            anchor_offset=0.0,
        )
        assert s["engines"]["mesh_reconstruct"]["collective"] \
            == pytest.approx(0.0007)
        ge = s["engines"]["gf_encode"]
        assert ge["fused_op"] == pytest.approx(0.00084)
        assert ge["dma"] == pytest.approx(0.00025)  # the infeed row
        assert sum(s["unattributed"].values()) < 2e-4
        # far-away intervals leave everything unattributed
        far = summarize_events(
            events, threads,
            intervals=[(1.0, 1.1, "gf_encode", "k")],
            anchor_offset=0.0,
        )
        assert far["engines"] == {}
        assert far["unattributed"]["collective"] == pytest.approx(0.0007)


# -- the window service -------------------------------------------------------


class TestWindowService:
    def test_unavailable_paths_are_structured(self, tmp_path):
        svc = DeviceTracer()
        assert "unavailable" in svc.dump()  # nothing captured yet
        stopped = svc.stop()
        assert "unavailable" in stopped
        # the structured flag bench keys its expiry-race fallback on
        assert stopped["no_window"] is True
        st = svc.status()
        assert st["active"] is False and st["windows"] == 0

    def test_one_window_at_a_time_and_expiry(self):
        svc = DeviceTracer()
        st = svc.start(duration=0.2, label="w1")
        assert st.get("success"), st
        second = svc.start(duration=1.0)
        assert second.get("busy") and "already open" in second["error"]
        # an expired window auto-closes on the next service call: the
        # start -> launch -> dump round trip needs no explicit stop
        time.sleep(0.25)
        d = svc.dump()
        assert "unavailable" not in d or "still open" not in str(d)
        assert svc.status()["active"] is False

    def test_dispatcher_batch_window_round_trip(self):
        """The acceptance path: start -> one dispatcher EC batch ->
        stop -> dump returns a non-empty per-engine breakdown carrying
        all three buckets, merged into the KernelProfiler entries."""
        profiler().reset()
        sinfo, codec = _sinfo(), _codec()
        rng = np.random.default_rng(3)
        bufs = [
            rng.integers(0, 256, size=(s * sinfo.stripe_width,),
                         dtype=np.uint8)
            for s in (2, 3, 3)
        ]
        svc = tracer()
        st = svc.start(duration=30.0, label="disp")
        assert st.get("success"), st

        async def main():
            disp = ECDispatcher(window=0.002, max_stripes=1 << 20)
            outs = await asyncio.gather(
                *[disp.encode(sinfo, codec, b) for b in bufs]
            )
            await disp.stop()
            return outs

        try:
            outs = run(main())
        finally:
            bd = svc.stop()
        assert len(outs) == 3
        assert "unavailable" not in bd, bd
        assert set(bd["buckets"]) == set(BUCKETS)
        assert bd["buckets"]["fused_op"] > 0
        assert bd["engines"], bd  # attributed to the codec engines
        # ...and folded into the kernel profiler under the same names
        kp = profiler().dump()["engines"]
        traced = [e for e in kp.values() if "device_trace" in e]
        assert traced, kp.keys()
        d = svc.dump()
        assert d["buckets"] == bd["buckets"]
        assert svc.status()["windows"] >= 1

    def test_mesh_reconstruct_window_splits_ici(self):
        """A mesh reconstruct window attributes nonzero time to the
        ICI-collective bucket DISTINCTLY from the rebuild compute —
        the "gather-bound or rebuild-bound?" answer, measured."""
        from ceph_tpu.parallel.engine import MeshEcEngine

        sinfo, codec = _sinfo(), _codec()
        eng = MeshEcEngine()
        rng = np.random.default_rng(4)
        buf = rng.integers(0, 256, size=(16 * sinfo.stripe_width,),
                           dtype=np.uint8)
        full = eng.encode(sinfo, codec, buf)
        surv = {s: np.asarray(v) for s, v in full.items() if s != 0}
        eng.decode_concat(sinfo, codec, surv)  # warm the program
        svc = tracer()
        st = svc.start(duration=30.0, label="mesh")
        assert st.get("success"), st
        try:
            for _ in range(3):
                eng.decode_concat(sinfo, codec, surv)
        finally:
            bd = svc.stop()
        assert "unavailable" not in bd, bd
        rec = bd["engines"].get("mesh_reconstruct")
        assert rec, bd["engines"].keys()
        assert rec["collective"] > 0
        assert rec["fused_op"] > 0
        assert rec["collective"] != rec["fused_op"]


# -- the flight recorder ------------------------------------------------------


class TestFlightRecorder:
    def test_ring_bound_and_lookup(self):
        fr = FlightRecorder(capacity=3)
        for i in range(5):
            t = fr.begin(lane="device", kind="enc", klass="client",
                         ops=1, traces=[f"c:t{i}"])
            fr.end(t, device_wall_s=0.001 * i, served="device")
        d = fr.dump()
        assert d["capacity"] == 3 and len(d["launches"]) == 3
        assert d["launches"][-1]["device_wall_s"] == pytest.approx(0.004)
        assert fr.lookup("c:t0") is None  # aged out of the ring
        hit = fr.lookup("c:t4")
        assert hit["lane"] == "device" and hit["klass"] == "client"
        assert fr.lookup(None) is None
        # internal trace sets never leak into dumps
        assert all(not k.startswith("_") for rec in d["launches"]
                   for k in rec)

    def test_in_flight_launches_are_visible(self):
        """A wedged launch must be findable BEFORE it completes — the
        slow ops it carries are in flight too."""
        fr = FlightRecorder()
        t = fr.begin(lane="mesh", kind="dec", klass="client",
                     ops=2, traces=["c:t9"])
        hit = fr.lookup("c:t9")
        assert hit["in_flight"] is True and hit["age_s"] >= 0
        assert fr.dump()["in_flight"][0]["lane"] == "mesh"
        fr.end(t, device_wall_s=0.5, served="fallback",
               error="EngineFault('x')")
        hit = fr.lookup("c:t9")
        assert "in_flight" not in hit
        assert hit["served"] == "fallback" and "EngineFault" in hit["error"]

    def test_dispatcher_records_launches(self):
        """Batched launches land in the ring with lane / QoS class /
        queue-wait vs device wall / the slowest member's trace id."""
        sinfo, codec = _sinfo(), _codec()
        rng = np.random.default_rng(5)
        bufs = [
            rng.integers(0, 256, size=(2 * sinfo.stripe_width,),
                         dtype=np.uint8)
            for _ in range(3)
        ]

        async def main():
            disp = ECDispatcher(window=0.002, max_stripes=1 << 20)

            async def one(i, b):
                tok = current_trace.set(f"client.0:t{i}")
                try:
                    return await disp.encode(sinfo, codec, b)
                finally:
                    current_trace.reset(tok)

            await asyncio.gather(*[one(i, b) for i, b in enumerate(bufs)])
            d = disp.flight.dump()
            hit = disp.flight.lookup("client.0:t1")
            await disp.stop()
            return d, hit

        d, hit = run(main())
        assert d["launches"], d
        rec = d["launches"][-1]
        assert rec["lane"] == "device" and rec["klass"] == "client"
        assert rec["kind"] == "enc" and rec["ops"] == 3
        assert rec["queue_wait_s"] >= 0
        assert rec["device_wall_s"] > 0
        assert rec["served"] == "device"
        assert rec["slowest_trace"].startswith("client.0:t")
        assert rec["stripe_width"] == sinfo.stripe_width
        assert hit is not None and hit["seq"] == rec["seq"]

    def test_fallback_direct_lane_records_too(self):
        """While the breaker is TRIPPED the host fallback serves each
        op on its own — a slow op carried by such a per-op call must
        still name its launch."""
        from ceph_tpu.osd.ec_failover import TRIPPED, EngineSupervisor

        sinfo, codec = _sinfo(2, 512), _codec()
        buf = np.arange(2 * sinfo.stripe_width, dtype=np.uint32).astype(
            np.uint8
        )

        async def main():
            sup = EngineSupervisor(probe_interval=30.0)  # no re-promote
            disp = ECDispatcher(window=0.0, supervisor=sup)
            disp.inject_engine_failure = 1
            for _ in range(2):  # two fatals: SUSPECT then TRIPPED
                await disp.encode(sinfo, codec, buf)
            assert sup.state == TRIPPED
            tok = current_trace.set("client.0:t77")
            try:
                await disp.encode(sinfo, codec, buf)
            finally:
                current_trace.reset(tok)
            hit = disp.flight.lookup("client.0:t77")
            await disp.stop()
            return hit

        hit = run(main())
        assert hit is not None
        assert hit["lane"] == "fallback_direct"
        assert hit["served"] == "fallback_direct"
        assert hit["ops"] == 1 and hit["device_wall_s"] > 0

    def test_op_tracker_dump_names_the_launch(self):
        """SLOW_OPS consultation: an op dump carries the launch that
        carried the op (in-flight and historic)."""
        fr = FlightRecorder()
        t = fr.begin(lane="device", kind="enc", klass="client", ops=1,
                     queue_wait_s=0.01, traces=["client.0:t5"])
        fr.end(t, device_wall_s=2.5, served="device")
        tracker = OpTracker()
        tracker.launch_lookup = fr.lookup
        op = tracker.create(trace="client.0:t5", tid=5)
        d = tracker.dump_ops_in_flight()
        assert d["ops"][0]["launch"]["lane"] == "device"
        assert d["ops"][0]["launch"]["device_wall_s"] == 2.5
        tracker.finish(op)
        hist = tracker.dump_historic_ops()
        assert hist["ops"][0]["launch"]["klass"] == "client"
        # ops without a matching launch dump cleanly
        other = tracker.create(trace="client.0:t6", tid=6)
        d = tracker.dump_ops_in_flight()
        assert all("launch" not in o or o["trace"] != "client.0:t6"
                   for o in d["ops"])
        tracker.finish(other, completed=False)


# -- live cluster surfaces ----------------------------------------------------


class TestLiveCluster:
    def test_kernel_trace_and_launch_history_admin(self, tmp_path):
        """The operator surface end to end on a live MiniCluster:
        `kernel trace start` -> EC writes -> `kernel trace dump`
        returns the per-engine breakdown over every daemon's socket;
        `dump_launch_history` names the launch (lane, batch key, QoS
        class) that carried an injected slow op; an open window across
        the PR-7 fault matrix adds zero failed client ops."""
        from ceph_tpu.common.admin_socket import admin_command
        from ceph_tpu.rados import MiniCluster

        asok = str(tmp_path / "{name}.asok")

        async def main():
            async with MiniCluster(
                n_osds=3,
                config_overrides={
                    "admin_socket": asok,
                    "osd_mgr_report_interval": 0.05,
                },
            ) as cluster:
                cl = await cluster.client()
                await cl.create_pool("ec", "erasure")  # k2m1
                io = cl.io_ctx("ec")
                sock0 = str(tmp_path / "osd.0.asok")

                # ---- window guard over the admin socket -------------
                st = await admin_command(sock0, "kernel trace start",
                                         duration=30.0, label="t1")
                assert st.get("success"), st
                busy = await admin_command(
                    str(tmp_path / "osd.1.asok"), "kernel trace start",
                )
                assert busy.get("busy"), busy  # process-wide guard

                # ---- slow-op injection inside the window ------------
                for osd in cluster.osds.values():
                    osd.config.set("ec_inject_launch_hang", 0.2)
                model: dict[str, bytes] = {}

                async def put(i):
                    data = bytes([i]) * (1024 + 37 * i)
                    await io.write_full(f"o{i}", data)
                    model[f"o{i}"] = data

                await asyncio.gather(*[put(i) for i in range(4)])
                for osd in cluster.osds.values():
                    osd.config.set("ec_inject_launch_hang", 0.0)

                # ---- fault matrix with the window still open --------
                for osd in cluster.osds.values():
                    osd.config.set("ec_inject_engine_failure", 1)
                await asyncio.gather(*[put(i) for i in range(4, 8)])
                for osd in cluster.osds.values():
                    osd.config.set("ec_inject_engine_failure", 0)
                # zero failed client ops; replayed bytes identical
                for name, want in model.items():
                    assert await io.read(name) == want, name

                # ---- the breakdown round-trips ----------------------
                stopped = await admin_command(sock0, "kernel trace stop")
                # capture racing an engine trip may degrade — but only
                # to a STRUCTURED unavailable, never an op error
                assert ("buckets" in stopped
                        or "unavailable" in stopped), stopped
                if "buckets" in stopped:
                    assert stopped["buckets"]["fused_op"] > 0
                    assert stopped["engines"], stopped
                status = await admin_command(
                    str(tmp_path / "osd.2.asok"), "kernel trace status",
                )
                assert status["active"] is False
                assert status["windows"] + status["failed_windows"] >= 1
                dumped = await admin_command(sock0, "kernel trace dump")
                assert ("buckets" in dumped
                        or "unavailable" in dumped), dumped

                # ---- dump_launch_history names the slow op ----------
                histories = {}
                for n in range(3):
                    h = await admin_command(
                        str(tmp_path / f"osd.{n}.asok"),
                        "dump_launch_history",
                    )
                    histories[n] = h
                launches = [
                    rec for h in histories.values()
                    for rec in h["launches"]
                ]
                assert launches, histories
                slow = [r for r in launches
                        if (r.get("device_wall_s") or 0) > 0.15]
                assert slow, [r.get("device_wall_s") for r in launches]
                rec = slow[0]
                assert rec["lane"] in ("device", "mesh")
                assert rec["klass"] == "client"
                assert rec["kind"] in ("enc", "dec")
                assert rec["stripe_width"] > 0
                assert rec["slowest_trace"], rec
                # ...and the op side points back at the launch: some
                # OSD's historic dump carries the launch record
                found_link = False
                for n in range(3):
                    ops = (await admin_command(
                        str(tmp_path / f"osd.{n}.asok"),
                        "dump_historic_ops",
                    ))["ops"]
                    if any("launch" in o for o in ops):
                        found_link = True
                assert found_link, "no op dump carried its launch"

                # counters flowed to the ec family off the report tick
                await asyncio.sleep(0.15)
                traced = 0.0
                for osd in cluster.osds.values():
                    perf = osd.perf.dump()["ec"]
                    traced += perf["device_time_fused_op"]
                    assert "device_occupancy" in perf
                if "buckets" in stopped:
                    assert traced > 0

        run(main())
