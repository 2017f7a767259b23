"""KernelProfiler (ISSUE 3 tentpole): compile-vs-execute split,
jit-cache hit/miss accounting keyed on call signatures, per-engine
batch shapes and latency histograms, and the instrumentation taps in
the matrix codec and the vectorized CRUSH mapper.
"""

import numpy as np
import pytest

from ceph_tpu.ops.profiler import KernelProfiler, profiler


class TestProfilerCore:
    def test_miss_then_hit(self):
        p = KernelProfiler()
        with p.timed("eng", ("m", (2, 8)), nbytes=16, shape=(2, 8)):
            pass
        with p.timed("eng", ("m", (2, 8)), nbytes=16, shape=(2, 8)):
            pass
        with p.timed("eng", ("m", (2, 16)), nbytes=32, shape=(2, 16)):
            pass
        d = p.dump()["engines"]["eng"]
        assert d["calls"] == 3
        # two distinct signatures -> two compiles, one cached repeat
        assert d["jit_cache"] == {"misses": 2, "hits": 1}
        assert d["bytes"] == 64
        assert d["shapes"] == {"(2, 8)": 2, "(2, 16)": 1}
        assert d["compile_time"] >= 0 and d["exec_time"] >= 0

    def test_explicit_compiled_override(self):
        p = KernelProfiler()
        p.record("e", "k1", 0.5, compiled=False)  # steady-state record
        d = p.dump()["engines"]["e"]
        assert d["jit_cache"] == {"misses": 0, "hits": 1}
        assert d["exec_time"] == 0.5

    def test_exec_gbps_excludes_compile_call_bytes(self):
        """A compile call's bytes must not inflate the steady-state
        rate: 1 GB compiled in 10 s + 1 GB cached in 0.1 s is
        10 GB/s, not 20."""
        p = KernelProfiler()
        p.record("e", "k", 10.0, nbytes=10 ** 9)   # miss (compile)
        p.record("e", "k", 0.1, nbytes=10 ** 9)    # hit (exec)
        d = p.dump()["engines"]["e"]
        assert d["bytes"] == 2 * 10 ** 9
        assert d["exec_gbps"] == 10.0

    def test_reset_keeps_compile_signatures(self):
        """A profiler reset (bench phase boundary) clears the stats but
        NOT the seen-signature set: jax's jit cache is still warm, so a
        post-reset call on an old signature must count as a hit."""
        p = KernelProfiler()
        p.record("e", "k", 0.1)
        p.reset()
        assert p.dump()["engines"] == {}
        p.record("e", "k", 0.1)
        assert p.dump()["engines"]["e"]["jit_cache"]["hits"] == 1

    def test_histogram_rides_along(self):
        p = KernelProfiler()
        p.record("e", "k", 0.002, nbytes=1 << 20)
        h = p.dump_histograms()["e"]
        assert h["count"] == 1
        assert [a["name"] for a in h["axes"]] == [
            "request_bytes", "latency"
        ]

    def test_non_aot_first_call_is_first_exec_not_compile(self):
        """ISSUE 9 satellite (ROADMAP 5a caveat): a non-AOT callable's
        first call fuses tracing + compile + the first execution — it
        must land in ``first_exec_s`` with ``aot_split`` false, in
        NEITHER compile_time nor exec_time, so neither stat lies."""
        p = KernelProfiler()
        p.record("e", "k", 3.0, nbytes=10 ** 9)   # first sighting
        p.record("e", "k", 0.1, nbytes=10 ** 9)   # steady state
        d = p.dump()["engines"]["e"]
        assert d["aot_split"] is False
        assert d["first_exec_s"] == 3.0
        assert d["compile_time"] == 0.0
        assert d["exec_time"] == 0.1
        # the fused first call still counts as the jit-cache miss
        assert d["jit_cache"] == {"misses": 1, "hits": 1}
        # ...and never pollutes the steady-state rate
        assert d["exec_gbps"] == 10.0

    def test_dump_top_n_and_device_share(self):
        """ISSUE 9 satellite: ``dump(top=N)`` keeps the N heaviest
        engines (readable on a busy daemon) and every entry carries
        its share of the window's recorded device-seconds."""
        p = KernelProfiler()
        p.record("heavy", "k", 8.0, compiled=False)
        p.record("light", "k", 1.0, compiled=False)
        p.record("mid", "k", 3.0, compiled=False)
        full = p.dump()
        assert full["total_seconds"] == pytest.approx(12.0)
        assert full["engines"]["heavy"]["device_share"] \
            == pytest.approx(8 / 12, abs=1e-3)
        assert "engines_omitted" not in full
        top = p.dump(top=2)
        assert set(top["engines"]) == {"heavy", "mid"}
        assert top["engines_omitted"] == 1
        # shares stay relative to the FULL window, not the page
        assert top["engines"]["mid"]["device_share"] \
            == pytest.approx(3 / 12, abs=1e-3)
        assert p.dump(top=0)["engines"] == {}

    def test_merge_device_time(self):
        """A closed trace window's per-engine buckets fold into the
        matching entries (ops.device_trace merge) and reset clears
        them with everything else."""
        p = KernelProfiler()
        p.record("e", "k", 0.1, compiled=False)
        p.merge_device_time({"e": {"collective": 0.04, "fused_op": 0.01}})
        p.merge_device_time({"e": {"collective": 0.02}})
        d = p.dump()["engines"]["e"]["device_trace"]
        assert d == {"collective": 0.06, "fused_op": 0.01}
        p.reset()
        assert p.dump()["engines"] == {}


class TestInstrumentationTaps:
    def test_matrix_codec_reports(self):
        from ceph_tpu.models import registry

        p = profiler()
        p.reset()
        codec = registry.instance().factory(
            "isa", {"k": "2", "m": "1", "technique": "reed_sol_van"}
        )
        rng = np.random.default_rng(0)
        data = rng.integers(0, 256, size=(2, 512), dtype=np.uint8)
        parity = codec.encode_chunks(data)
        engines = p.dump()["engines"]
        assert "gf_encode" in engines, engines
        assert engines["gf_encode"]["calls"] >= 1
        assert engines["gf_encode"]["bytes"] >= data.size
        # decode reports on its own engine (native or u32 path)
        chunks = np.concatenate([data, parity])
        rebuilt = codec.decode_chunks((1, 2), chunks[1:], (0,))
        np.testing.assert_array_equal(rebuilt[0], data[0])
        engines = p.dump()["engines"]
        assert any(e.startswith("gf_decode") for e in engines), engines

    def test_launches_beside_calls(self):
        """``dump_kernel_profile`` reports ``launches`` beside
        ``calls``: a single-launch engine (the fused stripe encode)
        reads one per call; a recorded multi-launch call adds its
        count."""
        from ceph_tpu.models import registry

        p = profiler()
        p.reset()
        codec = registry.instance().factory(
            "isa", {"k": "2", "m": "1", "technique": "reed_sol_van"}
        )
        d3 = np.zeros((3, 2, 256), dtype=np.uint32)
        codec.encode_shards_u32(d3)
        codec.encode_shards_u32(d3)
        e = p.dump()["engines"]["ec_shards"]
        assert (e["calls"], e["launches"]) == (2, 2)
        p.record("e", "k", 0.1, launches=8)
        p.record("e", "k", 0.1)
        e = p.dump()["engines"]["e"]
        assert (e["calls"], e["launches"]) == (2, 9)

    def test_crush_mapper_reports(self):
        from ceph_tpu.crush import mapper, mapper_jax
        from ceph_tpu.crush.map import CrushMap

        p = profiler()
        p.reset()
        cmap = CrushMap.flat(8)
        rule = cmap.add_simple_rule(
            cmap.root_id(), 0, indep=False, max_size=2
        )
        xs = np.arange(64, dtype=np.uint32)
        rows = mapper_jax.vec_do_rule(cmap, rule, xs, 2)
        assert list(rows[0]) == mapper.crush_do_rule(cmap, rule, 0, 2)
        counts, bad = mapper_jax.vec_rule_stats(cmap, rule, xs, 2)
        assert bad == 0 and sum(counts.values()) == 2 * 64
        engines = p.dump()["engines"]
        assert "crush_vec_rule" in engines, engines
        assert "crush_vec_stats" in engines, engines
        assert engines["crush_vec_rule"]["shapes"] == {"(64,)": 1}
