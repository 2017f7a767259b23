"""Tail-sampled tracing (ISSUE 18): the always-trace/decide-late keep
policy, the mgr's kept-trace store, SLO exemplar linkage, and the CI
gates that bound the new surface.

Covers the acceptance criteria end to end: TraceStore ring/retrieval
units, the hop-manifest drift lint, the bench_regress overhead gate,
a live MiniCluster where injected-slow ops are kept with complete
attributed waterfalls while fast ops drop at the baseline rate, a
real-multiprocess ProcCluster keep (cross-process spans with honest
uncertainty), and the fault-matrix case: an accelerator SIGKILL whose
fallback replay condemns the op's trace with zero failed client ops.
"""

import asyncio
import importlib.util
import json
import pathlib
import time

from ceph_tpu.common.tracing import op_waterfall
from ceph_tpu.mgr.trace_store import TraceStore
from ceph_tpu.rados import MiniCluster
from ceph_tpu.tools.ceph_cli import _mgr_command

# the canonical top-level hop chain a small replicated write crosses
PATH_CHAIN = ("client_serialize", "wire", "dispatch", "qos_wait",
              "execute", "reply_wire", "reply_dispatch")


def run(coro):
    asyncio.run(coro)


def _load_tool(name):
    path = (pathlib.Path(__file__).resolve().parent.parent
            / "tools" / f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"_{name}_tt", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


async def _mgr(client, **cmd):
    rc, out = await _mgr_command(client, cmd)
    assert rc == 0, cmd
    return out


async def _write(cl, pool, oid, payload=b"\xa5" * 2048):
    reply = await cl.operate(
        pool, oid, [{"op": "writefull", "data": 0}], [payload]
    )
    assert reply.result == 0, (oid, reply.result)
    return reply


_FAST = {
    "osd_mgr_report_interval": 0.2,
    "mgr_tsdb_step": 0.2,
    "osd_client_ledger_window": 120.0,
}


# ---------------------------------------------------------------------------
# TraceStore units
# ---------------------------------------------------------------------------

def _wf(trace, wall=0.01, reason="slow", client=10, pool=1,
        hop="execute", dur=None):
    """One shipped-waterfall record, in the shape the OSD assembles
    (common/tracing.op_waterfall keys + the keep metadata)."""
    return {
        "trace": trace, "client": client, "pool": pool,
        "klass": "client", "reason": reason, "wall_s": wall,
        "path_sum_s": wall, "span_s": wall, "max_uncertainty_s": 0.0,
        "dominant_hop": hop,
        "hops": [{"hop": hop, "entity": "osd.0", "start_s": 0.0,
                  "dur_s": dur if dur is not None else wall}],
    }


class TestTraceStore:
    def test_ring_evicts_oldest_and_counts(self):
        ts = TraceStore(capacity=3)
        for i in range(5):
            ts.ingest(_wf(f"t{i}"))
        assert ts.stats() == {"size": 3, "capacity": 3,
                              "ingested": 5, "evictions": 2}
        assert ts.get("t0") is None and ts.get("t1") is None
        assert ts.get("t4")["trace"] == "t4"

    def test_reingest_replaces_and_refreshes_recency(self):
        """The same op kept by two reporting OSDs (or a resent report)
        must not double count or age out early."""
        ts = TraceStore(capacity=2)
        ts.ingest(_wf("a", wall=0.01))
        ts.ingest(_wf("b"))
        ts.ingest(_wf("a", wall=0.02))  # replace in place, refresh
        assert ts.stats()["size"] == 2
        assert ts.stats()["evictions"] == 0
        assert ts.get("a")["wall_s"] == 0.02
        ts.ingest(_wf("c"))  # b is now the oldest, not a
        assert ts.get("b") is None and ts.get("a") is not None

    def test_ls_filters_newest_first(self):
        ts = TraceStore()
        ts.ingest(_wf("t1", client=1, pool=1, hop="execute"))
        ts.ingest(_wf("t2", client=2, pool=1, hop="wire"))
        ts.ingest(_wf("t3", client=1, pool=2, hop="execute"))
        assert [r["trace"] for r in ts.ls()] == ["t3", "t2", "t1"]
        assert [r["trace"] for r in ts.ls(client=1)] == ["t3", "t1"]
        assert [r["trace"] for r in ts.ls(pool=1)] == ["t2", "t1"]
        assert [r["trace"] for r in ts.ls(hop="wire")] == ["t2"]
        assert [r["trace"] for r in ts.ls(limit=1)] == ["t3"]

    def test_top_is_slowest_first(self):
        ts = TraceStore()
        for trace, wall in (("a", 0.01), ("b", 0.5), ("c", 0.1)):
            ts.ingest(_wf(trace, wall=wall))
        assert [r["trace"] for r in ts.top(2)] == ["b", "c"]

    def test_summary_reasons_and_dominant_hops(self):
        ts = TraceStore()
        ts.ingest(_wf("a", wall=0.2, reason="slow", hop="execute"))
        ts.ingest(_wf("b", wall=0.3, reason="slow", hop="execute"))
        ts.ingest(_wf("c", wall=0.1, reason="baseline", hop="wire"))
        s = ts.summary()
        assert s["traces"] == 3
        assert s["reasons"] == {"slow": 2, "baseline": 1}
        assert s["dominant_hops"][0]["hop"] == "execute"
        assert s["dominant_hops"][0]["count"] == 2
        assert s["dominant_hops"][0]["wall_max_s"] == 0.3

    def test_exemplars_prefer_anomalies_over_baseline(self):
        """A slow baseline sample must not displace anomaly keeps —
        SLO_BURN should cite the op that burned the budget."""
        ts = TraceStore()
        ts.ingest(_wf("base", wall=1.0, reason="baseline"))
        ts.ingest(_wf("slow", wall=0.1, reason="slow"))
        ts.ingest(_wf("err", wall=0.05, reason="error"))
        assert ts.exemplars(3) == ["slow", "err", "base"]
        assert ts.exemplars(1) == ["slow"]

    def test_exemplar_for_matches_bucket_bounds(self):
        ts = TraceStore()
        ts.ingest(_wf("t1", hop="execute", dur=0.003))
        assert ts.exemplar_for("execute", 0.002, 0.004) == ("t1", 0.003)
        assert ts.exemplar_for("execute", 0.004, 0.008) is None
        assert ts.exemplar_for("wire", 0.0, 1.0) is None


class TestPrometheusExemplars:
    def test_bucket_lines_carry_trace_exemplars(self):
        """stack.lat_* bucket series gain OpenMetrics exemplar
        annotations keyed by trace id when the mgr's store holds a
        kept trace whose span lands in that bucket."""
        from ceph_tpu.common import stack_ledger
        from tests.test_prometheus import _FakeMgr, _metrics

        stack_ledger.feed_hop("execute", 0.003)
        mgr = _FakeMgr(osd_stats={
            0: {"perf": {"stack": stack_ledger.stack_perf().dump()}},
        })
        mgr.trace_store = TraceStore()
        mgr.trace_store.ingest(_wf("wf-ex-1", hop="execute", dur=0.003))
        lines = _metrics(mgr).splitlines()
        annotated = [
            ln for ln in lines
            if ln.startswith("ceph_stack_lat_execute_bucket")
            and '# {trace_id="wf-ex-1"}' in ln
        ]
        assert annotated, "no exemplar-annotated execute bucket"
        # the annotation rides AFTER the sample value, OpenMetrics-style
        assert annotated[0].split(" # ")[0].split()[-1].replace(
            ".", "").isdigit()
        # non-stack families stay annotation-free
        assert not any(
            "trace_id=" in ln for ln in lines
            if not ln.startswith("ceph_stack_lat_")
        )


# ---------------------------------------------------------------------------
# CI gates: hop-manifest drift + bench overhead
# ---------------------------------------------------------------------------

class TestHopManifestLint:
    def _pkg(self, tmp_path, hops, body):
        (tmp_path / "common").mkdir()
        (tmp_path / "common" / "hop_manifest.json").write_text(
            json.dumps({"hops": hops})
        )
        (tmp_path / "mod.py").write_text(body)

    def test_unlisted_hop_fails(self, tmp_path):
        cc = _load_tool("check_counters")
        self._pkg(
            tmp_path, ["execute"],
            'record_span("execute", 0.0, 1.0)\n'
            'feed_hop("mystery", 0.001)\n'
        )
        problems = cc.check(tmp_path)
        assert len(problems) == 1, problems
        assert "mystery" in problems[0] and "manifest" in problems[0]

    def test_orphan_manifest_hop_fails(self, tmp_path):
        cc = _load_tool("check_counters")
        self._pkg(tmp_path, ["execute", "ghost"],
                  'feed_hop("execute", 0.001)\n')
        problems = cc.check(tmp_path)
        assert len(problems) == 1, problems
        assert "ghost" in problems[0]

    def test_stack_hops_tuple_is_a_site(self, tmp_path):
        cc = _load_tool("check_counters")
        self._pkg(tmp_path, ["execute", "wire"],
                  'STACK_HOPS = ("execute", "wire")\n')
        assert cc.check(tmp_path) == []

    def test_no_manifest_no_lint(self, tmp_path):
        """Fixture trees without a committed manifest have nothing to
        validate — the hop check stays off."""
        cc = _load_tool("check_counters")
        (tmp_path / "mod.py").write_text(
            'record_span("anything_goes", 0.0, 1.0)\n'
        )
        assert cc.check(tmp_path) == []

    def test_repo_manifest_is_drift_free(self):
        cc = _load_tool("check_counters")
        pkg = pathlib.Path(__file__).resolve().parent.parent / "ceph_tpu"
        assert (pkg / "common" / "hop_manifest.json").exists()
        assert cc.check(pkg) == []


def _write_trace_round(tmp_path, n, phase, value, share=None):
    line = {"metric": "m", "value": value, "unit": "GB/s",
            "phase": phase}
    if share is not None:
        line["smallops"] = {"trace_overhead_share": share}
    (tmp_path / f"BENCH_r{n:02d}.json").write_text(
        json.dumps({"n": n, "rc": 0, "parsed": line})
    )


class TestBenchRegressTraceOverheadGate:
    def test_overhead_growth_is_the_regression(self, tmp_path):
        """smallops.trace_overhead_share is lower-is-better: the keep
        policy getting expensive fails the gate even when headline
        GB/s barely moves.  (0.02+0.1)/(0.5+0.1) = 0.2 < 0.8."""
        br = _load_tool("bench_regress")
        _write_trace_round(tmp_path, 1, "tpu", 660.0, share=0.02)
        _write_trace_round(tmp_path, 2, "tpu", 658.0, share=0.5)
        rep = br.compare(br.load_rounds(str(tmp_path)),
                         metric="smallops.trace_overhead_share")
        assert rep["comparable"] and rep["lower_is_better"]
        assert rep["regression"] is True
        for metric in ("smallops.trace_overhead_share",
                       "smallops_trace_overhead_share"):
            assert br.main(
                ["--dir", str(tmp_path), "--metric", metric]
            ) == 1, metric

    def test_overhead_wobble_and_shrink_pass(self, tmp_path):
        br = _load_tool("bench_regress")
        _write_trace_round(tmp_path, 1, "tpu", 660.0, share=0.03)
        # (0.03+0.1)/(0.06+0.1) = 0.81 >= 0.8: noise, not a regression
        _write_trace_round(tmp_path, 2, "tpu", 659.0, share=0.06)
        assert br.main(
            ["--dir", str(tmp_path),
             "--metric", "smallops.trace_overhead_share"]
        ) == 0
        _write_trace_round(tmp_path, 3, "tpu", 661.0, share=0.01)
        rep = br.compare(br.load_rounds(str(tmp_path)),
                         metric="smallops.trace_overhead_share")
        assert rep["ratio"] > 1 and not rep["regression"]

    def test_overhead_skips_until_two_rounds_carry_it(self, tmp_path):
        br = _load_tool("bench_regress")
        _write_trace_round(tmp_path, 1, "tpu", 660.0)  # pre-capture
        _write_trace_round(tmp_path, 2, "tpu", 650.0, share=0.04)
        rep = br.compare(br.load_rounds(str(tmp_path)),
                         metric="smallops.trace_overhead_share")
        assert rep["comparable"] is False
        assert br.main(
            ["--dir", str(tmp_path),
             "--metric", "smallops.trace_overhead_share"]
        ) == 0


# ---------------------------------------------------------------------------
# Live clusters
# ---------------------------------------------------------------------------

class TestTailSamplingLive:
    def test_injected_slow_ops_kept_fast_ops_baseline(self):
        """The acceptance run: ~1-in-25 ops eat an injected 80ms delay
        inside the measured window; >=95% of them land in the mgr
        store as reason=slow with the complete canonical hop chain,
        client and pool attributed, while fast ops keep only at the
        1-in-N baseline rate — and the trace surfaces (trace top/
        summary/show, ceph_top's pane) all serve them."""
        overrides = dict(_FAST)
        overrides.update({
            "osd_op_trace_sample_every": 16,
            "osd_trace_keep_slow_threshold": 0.03,
            "osd_inject_op_delay": 0.08,
            "osd_inject_op_delay_every": 25,
        })

        async def main():
            async with MiniCluster(
                n_osds=1, config_overrides=overrides,
            ) as c:
                await c.start_mgr()
                await c.wait_for_active_mgr()
                cl = await c.client(name="tenant.traced")
                await cl.create_pool("data", "replicated", size=1)
                n_ops = 200
                walls = []  # (trace, wall_s) per op
                for i in range(n_ops):
                    t0 = time.perf_counter()
                    reply = await _write(cl, "data", f"o{i % 16}")
                    walls.append(
                        (reply.trace, time.perf_counter() - t0)
                    )
                slow_ids = [t for t, w in walls if w >= 0.06]
                assert len(slow_ids) >= 4, "injection did not fire"

                osd = next(iter(c.osds.values()))
                ptr = osd.perf.get("trace")
                assert ptr.get("kept_slow") >= len(slow_ids)
                # fast-op keep rate ~ the 1-in-16 baseline draw
                assert 2 <= ptr.get("kept_baseline") <= 3 * n_ops // 16
                assert ptr.get("dropped") >= n_ops * 0.7

                # every kept trace ships to the mgr at report cadence
                found: dict[str, dict] = {}
                async with asyncio.timeout(20):
                    while len(found) < len(slow_ids):
                        for tid in slow_ids:
                            if tid in found:
                                continue
                            rc, rec = await _mgr_command(
                                cl, {"prefix": "trace show",
                                     "trace": tid})
                            if rc == 0:
                                found[tid] = rec
                        if len(found) < len(slow_ids):
                            await asyncio.sleep(0.2)
                kept = len(found)
                assert kept >= max(1, int(0.95 * len(slow_ids)))
                for rec in found.values():
                    assert rec["reason"] == "slow"
                    assert rec["client"] == cl.client_id
                    assert rec["pool"] is not None
                    names = [h["hop"] for h in rec["hops"]
                             if "parent" not in h]
                    assert set(names) >= set(PATH_CHAIN), names
                    starts = [h["start_s"] for h in rec["hops"]]
                    assert starts == sorted(starts)
                    assert rec["wall_s"] >= 0.03

                # trace top names the slowest keeps; summary tallies
                top = await _mgr(cl, prefix="trace top", n=5)
                assert top["traces"]
                assert top["traces"][0]["wall_s"] >= 0.06
                assert top["traces"][0]["reason"] == "slow"
                summ = await _mgr(cl, prefix="trace summary")
                assert summ["reasons"].get("slow", 0) >= kept
                assert summ["dominant_hops"]

                # the CLI hands filters over as STRINGS — trace ls
                # must still match the store's int client/pool ids
                rc, ls = await _mgr_command(
                    cl, {"prefix": "trace ls",
                         "client": str(cl.client_id)})
                assert rc == 0, ls
                assert ls["traces"], "string client filter matched nothing"
                assert all(r["client"] == cl.client_id
                           for r in ls["traces"])

                # ceph_top's pane rides the same command (and the
                # frame is what --once --json prints: stays JSON-able)
                ceph_top = _load_tool("ceph_top")
                frame = await ceph_top.collect_frame(cl, 60.0)
                assert frame["traces"], "traces pane empty"
                json.dumps(frame)
                text = ceph_top.render_frame(frame)
                assert str(frame["traces"][0]["trace"]) in text

        run(main())

    def test_slo_burn_cites_exemplar_traces(self):
        """Under a latency storm SLO_BURN's detail names kept trace
        ids, and each cited id resolves through `trace show` to a full
        waterfall — the operator's next command, not a fishing
        expedition."""
        overrides = dict(_FAST)
        overrides.update({
            "mgr_slo_fast_window": 1.0,
            "mgr_slo_slow_window": 2.5,
            "mgr_slo_op_p99_target": 0.05,
            "mgr_slo_slow_frac_budget": 0.05,
            "mgr_slo_burn_threshold": 2.0,
            "osd_trace_keep_slow_threshold": 0.03,
        })

        async def main():
            async with MiniCluster(
                n_osds=1, config_overrides=overrides,
            ) as c:
                await c.start_mgr()
                await c.wait_for_active_mgr()
                cl = await c.client(name="tenant.burned")
                await cl.create_pool("data", "replicated", size=1)
                io = cl.io_ctx("data")
                payload = b"z" * 1024
                failed: list[str] = []
                stop = False

                async def writer():
                    i = 0
                    while not stop:
                        try:
                            await io.write_full(f"o{i % 8}", payload)
                        except Exception as e:  # must stay empty
                            failed.append(repr(e))
                        i += 1
                        await asyncio.sleep(0.01)

                wtask = asyncio.ensure_future(writer())
                try:
                    # storm: every op eats 120ms inside the window —
                    # every op is a slow keep, the store fills
                    for o in c.osds.values():
                        o.config.set("osd_inject_op_delay", 0.12)
                    async with asyncio.timeout(30):
                        while True:
                            st = await _mgr(cl, prefix="health")
                            burn = [ch for ch in st["checks"]
                                    if ch["code"] == "SLO_BURN"]
                            if burn:
                                break
                            await asyncio.sleep(0.2)
                    summary = burn[0]["summary"]
                    assert "exemplar traces" in summary, summary
                    ids = summary.split("exemplar traces ")[1]
                    cited = [s.strip() for s in ids.split(",")]
                    assert cited
                    rec = await _mgr(cl, prefix="trace show",
                                     trace=cited[0])
                    assert rec["reason"] == "slow"
                    assert rec["hops"]
                finally:
                    stop = True
                    await asyncio.gather(wtask, return_exceptions=True)
                assert failed == []

        run(main())


class TestProcClusterTail:
    def test_cross_process_keep_and_drop(self, tmp_path):
        """Real multiprocess: head sampling fully OFF, an injected
        delay on 1-in-4 ops — delayed ops come back KEPT (reply spans
        present, merged waterfall monotonic, cross-process spans carry
        alignment uncertainty) while fast ops carry no spans at all
        (the drop side of decide-late)."""
        from ceph_tpu.rados.proc_cluster import ProcCluster

        async def main():
            async with ProcCluster(
                str(tmp_path / "c"), n_osds=1,
                osd_config={
                    "osd_op_trace_sample_every": 0,
                    "osd_trace_keep_slow_threshold": 0.04,
                    "osd_inject_op_delay": 0.12,
                    "osd_inject_op_delay_every": 4,
                },
            ) as pc:
                cl = await pc.client()
                await cl.create_pool("wf", "replicated", size=1)
                results = []
                for i in range(12):
                    t0 = time.perf_counter()
                    reply = await _write(cl, "wf", f"o{i}")
                    results.append(
                        (reply, time.perf_counter() - t0)
                    )
                slow = [r for r, w in results if w >= 0.1]
                fast = [r for r, w in results if w < 0.03]
                assert slow, "injection did not fire"
                assert fast, "no fast ops to prove the drop side"
                for reply in slow:
                    assert reply.spans, "slow op dropped its spans"
                    wf = op_waterfall(reply.trace)
                    names = [h["hop"] for h in wf["hops"]
                             if "parent" not in h]
                    assert names == [
                        h for h in PATH_CHAIN if h in names
                    ], names
                    assert set(names) >= {"wire", "dispatch",
                                          "execute", "reply_wire"}
                    remote = [h for h in wf["hops"]
                              if h["entity"] == "osd.0"]
                    assert remote, wf
                    for h in remote:
                        assert h.get("uncertainty_s", 0.0) > 0.0, h
                    starts = [h["start_s"] for h in wf["hops"]]
                    assert starts == sorted(starts)
                for reply in fast:
                    assert not reply.spans
                    assert op_waterfall(reply.trace)["hops"] == []

        run(main())


class TestAccelReplayKept:
    def test_accel_sigkill_replay_is_kept_with_zero_failed_ops(self):
        """Fault-matrix e2e: the only accelerator is wedged mid-batch
        (ec_inject_launch_hang — the make_pjrt_c_api_client stall)
        then SIGKILLed while the OSD's RPC is in flight; the EC
        dispatcher replays on the host fallback (bit-identical, no
        client-visible failure), and the replayed op's trace is KEPT
        with reason=replay and the launch linkage naming the fallback
        — the flight record's verdict riding the keep policy."""

        async def main():
            async with MiniCluster(
                n_osds=3,
                config_overrides={
                    "osd_mgr_report_interval": 0.1,
                    "mgr_tsdb_step": 0.2,
                    "accel_beacon_interval": 0.05,
                },
            ) as c:
                await c.start_mgr()
                await c.wait_for_active_mgr()
                acc = await c.start_accel()
                c.set_accel_mode("prefer")
                async with asyncio.timeout(10):
                    while not all(
                        len(o.accel_client._map_clients) == 1
                        for o in c.osds.values()
                    ):
                        await asyncio.sleep(0.02)
                cl = await c.client(name="tenant.ec")
                await cl.create_pool("ec", "erasure")  # k2m1
                io = cl.io_ctx("ec")
                model: dict[str, bytes] = {}
                failed: list[str] = []

                async def storm(tag: int, n: int = 8):
                    async def put(i):
                        data = bytes([tag, i]) * (400 + 97 * i)
                        try:
                            await io.write_full(f"o{i}", data)
                            model[f"o{i}"] = data
                        except Exception as e:  # must stay empty
                            failed.append(repr(e))
                    await asyncio.gather(*[put(i) for i in range(n)])

                await storm(0)
                assert failed == []
                assert sum(
                    o.perf.get("accel").get("remote_batches")
                    for o in c.osds.values()
                ) > 0

                # wedge the accelerator's serving path (the
                # make_pjrt_c_api_client stall; _launch is the choke
                # point every batch it serves rides), stream a storm
                # INTO the wedge, and SIGKILL once an OSD shows a
                # remote batch in flight — the connection dies under a
                # pending RPC, the canonical mid-batch crash (a kill
                # between batches just reroutes: the router marks the
                # accel unreachable before the next launch ever leaves)
                orig_launch = acc.dispatch._launch

                async def wedged(*a, **kw):
                    await asyncio.sleep(2.0)
                    return await orig_launch(*a, **kw)

                acc.dispatch._launch = wedged
                stask = asyncio.ensure_future(storm(1))

                def remote_pending():
                    return any(
                        rec.get("lane") == "remote"
                        for o in c.osds.values()
                        for rec in o.ec_dispatch.flight.dump()[
                            "in_flight"]
                    )

                async with asyncio.timeout(10):
                    while not remote_pending():
                        await asyncio.sleep(0.02)
                await c.kill_accel(acc.name, crash=True)
                await stask
                assert failed == []
                for name, want in model.items():
                    assert await io.read(name) == want, name
                assert sum(
                    o.perf.get("trace").get("kept_replay")
                    for o in c.osds.values()
                ) >= 1

                # the kept trace reaches the mgr store with the launch
                # verdict attached
                row = None
                async with asyncio.timeout(15):
                    while row is None:
                        ls = await _mgr(cl, prefix="trace ls")
                        for r in ls["traces"]:
                            if r["reason"] == "replay":
                                row = r
                                break
                        if row is None:
                            await asyncio.sleep(0.1)
                rec = await _mgr(cl, prefix="trace show",
                                 trace=row["trace"])
                assert rec["reason"] == "replay"
                launch = rec.get("launch") or {}
                assert (launch.get("served") == "fallback"
                        or launch.get("origin")
                        or launch.get("error")), rec

        run(main())
