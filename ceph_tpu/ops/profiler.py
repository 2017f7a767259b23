"""Kernel-boundary profiler for the JAX/Pallas EC kernels and the
vectorized CRUSH mapper.

The hot path the paper cares about — GF(2^8) encode/decode behind
``ErasureCodePluginTPU`` and ``crush.mapper_jax`` — previously had zero
internal visibility: a bench run dying inside backend acquisition left
no phase breakdown at all.  This module is the
process-global timing tap every host-side kernel entry reports into:

- **trace/compile vs execute split**: jitted callables compile once per
  (program, input-shape) signature; the first call on a new signature
  pays tracing + XLA/Mosaic compilation on top of the execution.  The
  profiler keys every call on the caller-supplied signature: first
  sightings count as jit-cache ``misses``, repeats as ``hits``.  Where
  jax allows AOT (``lower().compile()``, via :meth:`KernelProfiler.
  call_jitted`) the compile is timed alone (``compile_time``,
  ``aot_split=true``) and the first execution joins the steady-state
  numbers; otherwise the fused first call is reported as
  ``first_exec_s`` — in NEITHER compile nor exec time, so neither
  stat lies for codecs that cannot AOT.
- **per-engine batch shapes**: which [k, N] / [n_x] shapes actually hit
  each engine, so batching regressions (a shape explosion defeating the
  jit cache) are visible instead of inferred.
- **per-engine latency histograms**: every call lands in a 2D
  (bytes x seconds) log2 PerfHistogram, served via the admin-socket
  ``dump_histograms`` command next to the daemon subsystems and dumped
  by ``dump_kernel_profile``.

Deliberately import-light: no jax import, so the admin socket (and
tools that never touch a device) can serve profiler state without
initializing a backend.
"""

from __future__ import annotations

import contextlib
import threading
import time
from typing import Any, Hashable

from ..common.perf_counters import PerfHistogram, size_latency_axes

# kernel-call latencies start ~1 us (cached host dispatch) — a finer
# floor than the daemon op histograms
_KERNEL_AXES = dict(size_min=4096.0, lat_min=1e-6)


class _EngineStats:
    __slots__ = ("calls", "compile_calls", "cache_hits", "compile_time",
                 "exec_time", "bytes", "exec_bytes", "shapes", "hist",
                 "aot_splits", "first_exec_time", "first_execs",
                 "device")

    def __init__(self):
        self.calls = 0
        self.compile_calls = 0
        self.cache_hits = 0
        self.compile_time = 0.0
        self.exec_time = 0.0
        self.bytes = 0
        self.exec_bytes = 0  # cached-call bytes only, for exec_gbps
        self.shapes: dict[str, int] = {}
        self.hist = PerfHistogram(size_latency_axes(**_KERNEL_AXES))
        self.aot_splits = 0  # compiles timed separately via jax AOT
        # first sightings of a signature on the NON-AOT path: tracing +
        # compile + the first execution fused in one wall time (jax
        # offers no portable split without lower().compile()) — kept
        # out of BOTH compile_time and exec_time so neither stat lies
        self.first_exec_time = 0.0
        self.first_execs = 0
        # per-bucket device-seconds merged from a jax.profiler trace
        # window (ops.device_trace): fused_op / dma / collective
        self.device: dict[str, float] = {}


class KernelProfiler:
    """Process-global per-engine kernel timing (see module docstring).

    An *engine* is a kernel family as the codec layer routes it
    ("gf_encode", "ec_shards", "bitmatrix_decode", "crush_vec", ...);
    a *key* is the jit-cache signature the caller knows (matrix
    signature + batch shape), used to classify compile vs cached calls.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._engines: dict[str, _EngineStats] = {}
        # compile signatures OUTLIVE reset(): jax's jit cache is not
        # cleared by a profiler reset, so a warmed key stays a hit
        self._seen: set[tuple[str, Hashable]] = set()
        # AOT-compiled executables per signature (call_jitted) — same
        # lifetime class as jax's own jit cache, so it survives reset();
        # FIFO-bounded like the codec layer's lru_cache(512) so a
        # signature storm cannot pin compiled programs forever (an
        # evicted signature stays in _seen: its re-compile is jax's
        # problem, not a double-counted miss)
        self._aot: dict[tuple[str, Hashable], Any] = {}
        self._aot_cap = 512
        # serializes AOT compiles: without it, two threads first-seeing
        # the same signature would both pay the compile AND double-count
        # the jit-cache miss (compiles are rare; contention is fine)
        self._compile_lock = threading.Lock()
        self._reset_at = time.time()
        # ops.device_trace window sink: while a trace window is open,
        # every recorded call reports its (engine, key, wall interval)
        # for per-engine attribution of the captured device events.
        # One attribute read when no window exists — zero-cost default.
        self.trace_sink: Any = None

    # -- recording -----------------------------------------------------------
    def record(self, engine: str, key: Hashable, seconds: float,
               nbytes: int = 0, shape: Any = None,
               compiled: bool | None = None) -> None:
        """``compiled`` overrides the first-sighting classification for
        callers that know (bench.py records a chained-scan marginal as
        steady-state even on a shape it never timed standalone;
        ``compiled=True`` marks a pure compile).  An un-overridden
        first sighting lands in the ``first_exec`` bucket: its wall
        time fuses tracing + compile + the first execution, so folding
        it into either compile_time or exec_time would lie (ROADMAP 5a
        caveat — the AOT path in :meth:`call_jitted` is the only place
        a clean compile-only time exists)."""
        t_end = time.perf_counter()
        sig = (engine, key)
        with self._lock:
            st = self._engines.get(engine)
            if st is None:
                st = self._engines[engine] = _EngineStats()
            st.calls += 1
            st.bytes += int(nbytes)
            first = sig not in self._seen
            self._seen.add(sig)
            if compiled is True:
                st.compile_calls += 1
                st.compile_time += seconds
            elif compiled is None and first:
                st.compile_calls += 1  # a jit-cache miss either way
                st.first_execs += 1
                st.first_exec_time += seconds
            else:
                st.cache_hits += 1
                st.exec_time += seconds
                st.exec_bytes += int(nbytes)
            if shape is not None:
                s = str(tuple(shape))
                st.shapes[s] = st.shapes.get(s, 0) + 1
        st.hist.sample(max(float(nbytes), 0.0), seconds)
        sink = self.trace_sink
        if sink is not None and sink.active:
            try:
                sink.note_kernel(engine, key, seconds, nbytes=nbytes,
                                 t_end_pc=t_end)
            except Exception:  # pragma: no cover - observability only
                pass

    @contextlib.contextmanager
    def timed(self, engine: str, key: Hashable, nbytes: int = 0,
              shape: Any = None, compiled: bool | None = None):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.record(engine, key, time.perf_counter() - t0,
                        nbytes=nbytes, shape=shape, compiled=compiled)

    def call_jitted(self, engine: str, key: Hashable, fn, args: tuple,
                    *, nbytes: int = 0, shape: Any = None, wrap=None):
        """Call a (possibly jitted) kernel under the profiler, shrinking
        the "compile includes the first execution" blind spot: on the
        first sighting of a signature, if ``fn`` exposes jax's AOT path
        (``fn.lower(*args).compile()``), the compile is timed as its own
        compile-call (zero bytes) and the first execution then lands in
        the steady-state numbers like any cached call; the engine's
        profile entry is marked ``aot_split``.  Callables without
        ``.lower`` (CEPH_TPU_NO_JIT eager fns, native wrappers) keep the
        current first-call split.  ``wrap`` post-processes the result
        INSIDE the exec timing (e.g. np.asarray, so host
        materialization stays accounted as before)."""
        sig = (engine, key)
        with self._lock:
            exe = self._aot.get(sig)
            fresh = sig not in self._seen
        if exe is None and fresh and hasattr(fn, "lower"):
            with self._compile_lock:
                # re-check under the compile lock: a concurrent caller
                # may have compiled this signature while we waited
                with self._lock:
                    exe = self._aot.get(sig)
                    fresh = sig not in self._seen
                if exe is None and fresh:
                    t0 = time.perf_counter()
                    try:
                        exe = fn.lower(*args).compile()
                    except Exception:
                        # tracing-only callables, older jax: fall back
                        exe = None
                    else:
                        dt = time.perf_counter() - t0
                        with self._lock:
                            # account the compile WITHOUT record(): it
                            # is not a kernel call — calls and the
                            # latency histogram must keep matching
                            # actual invocations (a zero-byte compile
                            # sample would also pollute the size axis)
                            st = self._engines.get(engine)
                            if st is None:
                                st = self._engines[engine] = \
                                    _EngineStats()
                            st.compile_calls += 1
                            st.compile_time += dt
                            st.aot_splits += 1
                            # sig seen -> the exec below is a cache hit
                            self._seen.add(sig)
                            self._aot[sig] = exe
                            while len(self._aot) > self._aot_cap:
                                self._aot.pop(next(iter(self._aot)))
        f = fn if exe is None else exe
        with self.timed(engine, key, nbytes=nbytes, shape=shape):
            out = f(*args)
            return out if wrap is None else wrap(out)

    def merge_device_time(self,
                          per_engine: dict[str, dict[str, float]]) -> None:
        """Fold a closed trace window's per-engine device-event buckets
        (ops.device_trace: fused_op / dma / collective seconds) into
        the matching engine entries, so ``dump_kernel_profile`` answers
        "where did the device time go INSIDE the program?" next to the
        compile/exec stats.  Accumulates across windows; cleared by
        :meth:`reset` like every other per-engine stat."""
        with self._lock:
            for engine, buckets in per_engine.items():
                st = self._engines.get(engine)
                if st is None:
                    st = self._engines[engine] = _EngineStats()
                for bucket, seconds in buckets.items():
                    st.device[bucket] = (
                        st.device.get(bucket, 0.0) + float(seconds)
                    )

    # -- views ---------------------------------------------------------------
    @staticmethod
    def _engine_seconds(st: _EngineStats) -> float:
        return st.compile_time + st.first_exec_time + st.exec_time

    def dump(self, prefix: str | None = None,
             top: int | None = None) -> dict:
        """JSON-able per-engine breakdown (``dump_kernel_profile``).
        ``prefix`` filters to one engine family — bench.py's mesh phase
        embeds ``dump(prefix="mesh")`` so the mesh shard_map programs
        (mesh_encode / mesh_reconstruct / mesh_gather) read distinctly
        from the single-chip kernel entries.  ``top`` keeps only the N
        heaviest engines by recorded seconds (a busy daemon's dump
        stays readable without paging through every signature); each
        entry carries ``device_share`` — its recorded seconds over the
        window total — so the heavy hitters read at a glance."""
        with self._lock:
            picked = [
                (name, st)
                for name, st in sorted(self._engines.items())
                if prefix is None or name.startswith(prefix)
            ]
            total_s = sum(self._engine_seconds(st) for _n, st in picked)
            n_matched = len(picked)
            if top is not None and top >= 0:
                picked = sorted(
                    picked, key=lambda ns: -self._engine_seconds(ns[1])
                )[:top]
                picked.sort(key=lambda ns: ns[0])
            engines = {}
            for name, st in picked:
                engines[name] = {
                    "calls": st.calls,
                    "jit_cache": {
                        "misses": st.compile_calls,
                        "hits": st.cache_hits,
                    },
                    # aot_split=True: compiles were timed separately via
                    # jax AOT (lower().compile()), so compile_time holds
                    # NO execution and first executions land in
                    # exec_time; aot_split=False: compiles could not be
                    # split, so each signature's first call — tracing +
                    # compile + first execution fused — is reported as
                    # first_exec_s, in NEITHER compile_time nor
                    # exec_time (ROADMAP 5a: the old accounting called
                    # it "compile" and lied)
                    "aot_split": st.aot_splits > 0,
                    "compile_time": round(st.compile_time, 6),
                    "first_exec_s": round(st.first_exec_time, 6),
                    "exec_time": round(st.exec_time, 6),
                    # steady-state bytes over steady-state time: mixing
                    # compile-call bytes in would inflate the rate by
                    # (1 + misses/hits)
                    "exec_gbps": round(
                        st.exec_bytes / st.exec_time / 1e9, 3
                    ) if st.exec_time > 0 else None,
                    "bytes": st.bytes,
                    "device_share": round(
                        self._engine_seconds(st) / total_s, 4
                    ) if total_s > 0 else 0.0,
                    "shapes": dict(st.shapes),
                    # per-bucket device-event seconds from the last
                    # trace window(s) (ops.device_trace merge); absent
                    # until a window captured this engine
                    **({"device_trace": {
                        b: round(v, 6)
                        for b, v in sorted(st.device.items())
                    }} if st.device else {}),
                }
            return {
                "since": self._reset_at,
                "total_seconds": round(total_s, 6),
                **({"engines_omitted": n_matched - len(engines)}
                   if len(engines) < n_matched else {}),
                "engines": engines,
            }

    def dump_histograms(self) -> dict:
        with self._lock:
            return {
                name: st.hist.dump()
                for name, st in sorted(self._engines.items())
            }

    def reset(self) -> None:
        """Clear the accumulated stats (bench phase boundaries); the
        compile-signature set survives — see __init__."""
        with self._lock:
            self._engines.clear()
            self._reset_at = time.time()


_profiler: KernelProfiler | None = None
_profiler_lock = threading.Lock()


def profiler() -> KernelProfiler:
    global _profiler
    if _profiler is None:
        with _profiler_lock:
            if _profiler is None:
                _profiler = KernelProfiler()
    return _profiler
