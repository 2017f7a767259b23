"""North-star benchmark: RS(8,3) encode + single-chunk reconstruct GB/s.

The TPU-native equivalent of ``ceph_erasure_code_benchmark`` on the
BASELINE.md config-2 workload (isa-l RS k=8 m=3, 1 MiB stripe; metric
GB/s = data bytes processed / seconds, per
reference:qa/workunits/erasure-code/bench.sh:166).

Prints one JSON line per completed phase (the last line is the final,
best-known result):
  {"metric": ..., "value": N, "unit": "GB/s", "vs_baseline": N, "phase": ...}

``value`` is the combined encode+reconstruct throughput (data bytes /
time for one encode pass plus one reconstruct pass) on the best
accelerator backend that answered within budget.  ``vs_baseline`` is the
ratio vs the same workload on this host's native single-thread C++
engine (native/ec_cpu.cc -O3 -march=native — the reference's
gf-complete/ISA-L engine class), measured in the same run.

Process contract:
- every device phase runs in ONE child process, the only one that holds
  the chip, under a hard deadline; the parent is pinned to the CPU and
  runs only host phases (the native C++ baseline, the QoS harness);
- the native baseline is printed under its own metric name, never the
  device metric;
- a result line is printed as soon as the headline completes, so a
  driver timeout still leaves a parseable line; SIGTERM/SIGALRM print
  the best-so-far line before exiting;
- a child that finds no TPU, dies or measures no headline leaves an
  error line (``"value": null``, ``"phase": "no-device"``) and the run
  exits non-zero: no CPU number is ever reported under the device
  metric.

Usage: python bench.py [--budget S] [--batch N] [--full]
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import time

import numpy as np

K, M, W = 8, 3, 8
OBJECT_SIZE = 1 << 20  # 1 MiB stripe
CHUNK = OBJECT_SIZE // K  # 128 KiB
BATCH_OBJECTS = 64  # fill the chip: 64 MiB data per device call
ERASED = [0]  # single-chunk reconstruct, per BASELINE config 2

T0 = time.time()

# every phase attempt (parent side), shipped in the final JSON line so a
# device child dying in device acquisition still leaves a
# machine-readable per-phase record
_PHASES: list = []


def _phase_note(phase: str, status: str, seconds: float, **extra) -> None:
    _PHASES.append({
        "phase": phase, "status": status,
        "seconds": round(seconds, 2), "t": round(time.time() - T0, 1),
        **extra,
    })


def _kprof():
    """The in-process kernel profiler (ceph_tpu.ops.profiler): phase
    functions reset it on entry and attach its dump to their result, so
    every emitted JSON line carries compile-vs-execute and jit-cache
    evidence for the kernels that phase actually ran."""
    from ceph_tpu.ops.profiler import profiler

    return profiler()


def _device_trace_capture(run_fn, label: str,
                          duration: float = 20.0) -> dict:
    """One bounded jax.profiler trace window around ``run_fn()``
    (ISSUE 9 / ROADMAP 5a): the phase's MEASURED fused-op / DMA /
    ICI-collective device-time split, embedded in the round JSON.
    TRACER failures degrade to ``{"unavailable": reason}`` — a bench
    phase must never die on observability — but a ``run_fn`` failure
    PROPAGATES: the burst is real device work, and an engine dying in
    it must reach the caller's failover accounting, not hide as a
    capture miss."""
    try:
        from ceph_tpu.ops.device_trace import tracer

        svc = tracer()
        st = svc.start(duration=duration, label=label,
                       max_duration=duration)
    except Exception as e:
        return {"unavailable": f"device trace capture failed: {e!r}"}
    if not st.get("success"):
        return {"unavailable": st.get("unavailable")
                or st.get("error") or str(st)}
    try:
        run_fn()
    finally:
        try:
            bd = svc.stop()
            if bd.get("no_window"):
                # the expiry timer closed the window mid-burst (slow
                # host): the capture was still parsed and stored —
                # dump() serves it rather than discarding the evidence
                bd = svc.dump()
            bd.pop("top_ops", None)  # keep the round JSON bounded
        except Exception as e:  # tracer-side close failure only
            bd = {"unavailable": f"device trace capture failed: {e!r}"}
    return bd


def _capture_or_failover(run_fn, label: str) -> tuple[dict, str | None]:
    """Capture wrapper for the phase bursts: tracer failures degrade
    (see above); a FATAL engine error in the burst is reported as
    ``(unavailable, error)`` so the phase can record the failover
    verdict while keeping its already-measured numbers; data/shape
    errors re-raise (a bench bug must surface)."""
    try:
        return _device_trace_capture(run_fn, label), None
    except Exception as e:
        from ceph_tpu.models.matrix_codec import classify_engine_error

        if classify_engine_error(e) != "fatal":
            raise
        log(f"{label}: engine died during trace burst ({e!r:.160})")
        return {
            "unavailable": f"engine died during trace burst: {e!r:.200}"
        }, repr(e)[:200]


def log(msg: str) -> None:
    print(f"[bench +{time.time() - T0:7.1f}s] {msg}", file=sys.stderr, flush=True)


def bench_loop(fn, *args, min_iters=3, min_seconds=0.5, deadline=None):
    """Time fn(*args); returns seconds/iter.  Stops at deadline regardless."""
    fn(*args)  # warmup / compile
    fn(*args)
    t0 = time.perf_counter()
    iters = 0
    while True:
        fn(*args)
        iters += 1
        dt = time.perf_counter() - t0
        if iters >= min_iters and dt >= min_seconds:
            return dt / iters
        if deadline is not None and time.time() > deadline:
            return dt / max(iters, 1)


def _matrices():
    from ceph_tpu.ops import matrices as mx
    from ceph_tpu.parallel.distributed import _recovery_rows

    P = mx.isa_rs_vandermonde(K, M)
    present = [r for r in range(K + M) if r not in ERASED]
    RM = _recovery_rows(P, K, W, present, list(ERASED))
    return P, RM, present


def bench_native(quick: bool = True) -> dict:
    """Single-thread C++ engine on one 1 MiB object (the CPU reference class)."""
    from ceph_tpu.utils import native

    P, RM, present = _matrices()
    rng = np.random.default_rng(0)
    data = rng.integers(0, 256, size=(K, CHUNK), dtype=np.uint8)
    data_bytes = data.size
    ms = 0.3 if quick else 1.0

    t_encode = bench_loop(lambda: native.encode(P, data), min_seconds=ms)
    parity = native.encode(P, data)
    surv = np.concatenate([data, parity])[present[:K]]
    t_decode = bench_loop(lambda: native.encode(RM, surv), min_seconds=ms)

    return {
        "batch_bytes": data_bytes,
        "encode_gbps": data_bytes / t_encode / 1e9,
        "reconstruct_gbps": data_bytes / t_decode / 1e9,
        "combined_gbps": 2 * data_bytes / (t_encode + t_decode) / 1e9,
    }


def _mc_worker(barrier, run_seconds, out_q):
    """One multicore-baseline worker: encode+reconstruct loop on its own
    buffers for ~run_seconds after the barrier; reports bytes and span."""
    from ceph_tpu.utils import native

    P, RM, present = _matrices()
    rng = np.random.default_rng(os.getpid())
    data = rng.integers(0, 256, size=(K, CHUNK), dtype=np.uint8)
    parity = native.encode(P, data)
    surv = np.concatenate([data, parity])[present[:K]]
    barrier.wait()
    t0 = time.perf_counter()
    done = 0
    while True:
        native.encode(P, data)
        native.encode(RM, surv)
        done += 2 * data.size
        dt = time.perf_counter() - t0
        if dt >= run_seconds:
            break
    out_q.put((done, dt))


def bench_native_multicore(quick: bool = True) -> dict:
    """ALL-CORES C++ baseline (VERDICT r2 Weak #2: the BASELINE.md north
    star is ISA-L on a 64-core HOST, not one thread): N processes run the
    same encode+reconstruct loop concurrently; aggregate GB/s = total
    bytes / slowest worker span."""
    import multiprocessing as mp

    n = os.cpu_count() or 1
    run_seconds = 0.6 if quick else 1.5
    ctx = mp.get_context("fork")  # parent holds no jax/device state
    barrier = ctx.Barrier(n + 1)
    q = ctx.Queue()
    procs = [
        ctx.Process(target=_mc_worker, args=(barrier, run_seconds, q))
        for _ in range(n)
    ]
    for p in procs:
        p.start()
    try:
        # a worker dying pre-barrier (OOM, import failure) must fail the
        # phase, not hang the whole benchmark (review r3 finding)
        barrier.wait(timeout=30)
        results = [q.get(timeout=60) for _ in procs]
    except Exception:
        for p in procs:
            p.kill()
        raise
    for p in procs:
        p.join(timeout=10)
    total = sum(b for b, _t in results)
    span = max(t for _b, t in results)
    return {
        "workers": n,
        "combined_gbps": total / span / 1e9,
    }


def _make_chained(fn):
    """Dependency-chained lax.scan wrapper (see bench_device docstring
    for the methodology): each iteration XOR-folds EVERY output row back
    into the input so nothing is skipped, overlapped, or DCE'd."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    def make(T):
        @jax.jit
        def run(v):
            def body(c, _):
                out = fn(c)
                folded = out[0]
                for i in range(1, out.shape[0]):
                    folded = folded ^ out[i]
                return c ^ jnp.broadcast_to(folded, c.shape), ()
            c, _ = lax.scan(body, v, None, length=T)
            return c
        return run

    return make


def _measure_rate(name, fn, data, data_bytes, quick, deadline) -> float:
    """Marginal seconds-per-iteration of ``fn`` on ``data`` via the
    short-vs-long chained-scan spread; conservative whole-call fallback
    when the spread drowns in timer noise."""
    make = _make_chained(fn)
    t_lo_T, t_hi_T = (2, 130) if quick else (4, 260)
    # best of 5 reps per chain length: fewer left the short-chain time
    # with enough jitter to swing the marginal 2x
    reps = 5
    # the marginal is only meaningful when the chain spread clears the
    # host's timing jitter by a wide margin: a 2.5 ms spread once
    # reported a 796 GB/s "reconstruct" on a ~30 GB/s workload.  For
    # fast kernels on small data, ESCALATE the long chain until the
    # spread is unambiguous instead of guessing from noise.
    MIN_SPREAD = 12e-3

    lo = make(t_lo_T)
    r = lo(data); _ = np.asarray(r.ravel()[:1])   # compile
    best_lo = float("inf")
    for _ in range(reps):
        t = time.time(); r = lo(data); _ = np.asarray(r.ravel()[:1])
        best_lo = min(best_lo, time.time() - t)

    best_hi = float("inf")
    meas_T = t_hi_T  # the chain length best_hi was actually measured at
    for _esc in range(3):
        meas_T = t_hi_T
        hi = make(t_hi_T)
        r = hi(data); _ = np.asarray(r.ravel()[:1])   # compile
        best_hi = float("inf")
        for _ in range(reps):
            t = time.time(); r = hi(data); _ = np.asarray(r.ravel()[:1])
            best_hi = min(best_hi, time.time() - t)
            if deadline is not None and time.time() > deadline:
                break
        if best_hi - best_lo > MIN_SPREAD:
            break
        if deadline is not None and time.time() > deadline - 5:
            break
        if best_hi > 1.0:  # never escalate an already-long chain
            break
        t_hi_T *= 8
    delta = (best_hi - best_lo) / (meas_T - t_lo_T)
    per = (
        delta if best_hi - best_lo > MIN_SPREAD
        else best_hi / meas_T  # conservative floor incl. dispatch
    )
    log(f"child: {name}: T{t_lo_T}={best_lo*1e3:.1f}ms T{meas_T}="
        f"{best_hi*1e3:.1f}ms -> {data_bytes / per / 1e9:.1f} GB/s")
    return per


def bench_device(batch: int, quick: bool, deadline: float | None) -> dict:
    """The headline, in the device child.

    Timing methodology: each measurement runs a *dependency-chained*
    ``lax.scan`` of T iterations inside ONE jitted call (each
    iteration's input depends on the previous output, so nothing can be
    skipped or overlapped), syncs with a 4-byte fetch, and takes the
    marginal rate between a short and a long chain: (t_long - t_short)
    / (T_long - T_short), so the fixed dispatch+fetch cost of a call
    drops out.  Whether this marginal is what the chip does is an open
    question (ROADMAP A.2): it has never been checked against the
    device trace.
    """
    import jax

    dev = jax.devices()[0]
    log(f"child: device ready: {dev}")

    from ceph_tpu.ops.gf_jax import bytes_to_u32, make_gf_matmul_u32
    from ceph_tpu.utils import native

    P, RM, present = _matrices()
    # candidate engines, raced per direction (VERDICT r4 #7: the pallas
    # vs xla comparison must be measured on device, not asserted from
    # the code comment).  XLA is always available; pallas joins when the
    # platform + lane count allow it.
    # (name, enc, dec, probe_n4): probe_n4 is a lane count the engine's
    # block constraint accepts, used for the small dec parity probe
    cands: list[tuple[str, object, object, int]] = [
        ("xla", make_gf_matmul_u32(P, W), make_gf_matmul_u32(RM, W), 4096)
    ]
    from ceph_tpu.ops.gf_pallas import BLOCK, make_gf_matmul_pallas, on_tpu

    n4 = (batch * CHUNK) // 4
    # prefer the larger block at bench shapes (~4% on a v5e)
    blk = next((b for b in (8192, BLOCK) if n4 % b == 0), None)
    if on_tpu() and blk:
        cands.insert(
            0,
            ("pallas", make_gf_matmul_pallas(P, W, block=blk),
             make_gf_matmul_pallas(RM, W, block=blk), blk),
        )
    log(f"child: GF engine candidates: {[c[0] for c in cands]}")

    n = batch * CHUNK
    rng = np.random.default_rng(0)
    data_u8 = rng.integers(0, 256, size=(K, n), dtype=np.uint8)
    data = jax.device_put(bytes_to_u32(data_u8), dev)  # [K, n//4] u32
    data_bytes = K * n
    log(f"child: {data_bytes >> 20} MiB uploaded")

    # correctness pin: TPU parity == native C++ engine parity (first 4 KiB).
    # This is also each engine's first real Mosaic/XLA compile — a
    # pallas lowering failure here must DROP that candidate, not kill
    # the phase (the import-time try above can't see compile errors)
    head_ref = native.encode(P, data_u8[:, :4096])
    prof = _kprof()
    prof.reset()  # per-phase window (the bench analog of `perf reset`)
    live: list[tuple[str, object, object]] = []
    for name, enc32, dec32, probe_n4 in cands:
        try:
            # first call on each engine = trace + XLA/Mosaic compile:
            # timed into the profiler so the phase line splits compile
            # from the steady-state rates recorded after the race
            with prof.timed(f"gf_encode[{name}]",
                            ("headline-enc", name, data.shape),
                            nbytes=data_bytes, shape=data.shape):
                parity_dev = jax.jit(enc32)(data)
            # the recovery matrix lowers a DIFFERENT unroll — probe it
            # too, or a dec-only Mosaic failure still kills the phase
            with prof.timed(f"gf_decode[{name}]",
                            ("headline-dec", name, probe_n4),
                            nbytes=K * probe_n4 * 4):
                jax.block_until_ready(jax.jit(dec32)(data[:, :probe_n4]))
            head = np.asarray(parity_dev[:, :1024]).view(np.uint8)
            if not np.array_equal(head, head_ref):
                # wrong bytes is the exact failure class this probe
                # exists to catch — drop the candidate, keep the phase
                log(f"child: {name} parity bytes != native engine; "
                    "dropping")
                continue
        except Exception as e:
            log(f"child: {name} compile failed ({e!r}); dropping")
            continue
        live.append((name, enc32, dec32))
    if not live:
        raise RuntimeError("no GF engine produced verified parity")
    log(f"child: parity bytes match native engine "
        f"({'/'.join(n for n, _, _ in live)})")

    # the fixed dispatch+fetch overhead is ~65 ms; the spread between the
    # short and long chain must put the marginal well above timer jitter
    # (~1 ms), so the long chain does >=128 extra iterations (~0.15 ms
    # each).  _measure_rate's XOR-fold feedback makes every output row a
    # real dependency (code-review r2 finding: out[0]-only feedback
    # measured ~1/m of the encode work).  Every live engine is raced in
    # both directions; the headline takes the per-direction winner.
    engines: dict[str, dict] = {}
    t_by_dir: dict[str, dict[str, float]] = {"enc": {}, "dec": {}}
    failovers: list[dict] = []
    for name, enc32, dec32 in live:
        if engines and deadline is not None and deadline - time.time() < 30:
            log(f"child: skipping {name} race (deadline close)")
            break
        try:
            # a device dying MID-PHASE drops this engine with a
            # recorded engine_failover verdict; the race continues on
            # the next engine, and the verdict rides the result line
            _maybe_inject_device_death(name)
            t_e = _measure_rate(
                f"encode[{name}]", enc32, data, data_bytes, quick,
                deadline,
            )
            t_d = _measure_rate(
                f"reconstruct[{name}]", dec32, data, data_bytes, quick,
                deadline,
            )
        except Exception as e:
            from ceph_tpu.models.matrix_codec import classify_engine_error

            if classify_engine_error(e) != "fatal":
                raise  # a data/shape bug is a bench bug: surface it
            failovers.append({
                "engine": name, "error": repr(e)[:200],
                "t": round(time.time() - T0, 1),
            })
            log(f"child: engine {name} DIED mid-phase ({e!r:.160}); "
                "failing over to the next engine")
            continue
        t_by_dir["enc"][name] = t_e
        t_by_dir["dec"][name] = t_d
        # steady-state per-iteration rate -> jit-cache-hit records (the
        # compile record above already claimed the miss for this key)
        prof.record(f"gf_encode[{name}]",
                    ("headline-enc", name, data.shape), t_e,
                    nbytes=data_bytes, shape=data.shape, compiled=False)
        prof.record(f"gf_decode[{name}]",
                    ("headline-dec-full", name, data.shape), t_d,
                    nbytes=data_bytes, shape=data.shape, compiled=False)
        engines[name] = {
            "encode_gbps": round(data_bytes / t_e / 1e9, 3),
            "reconstruct_gbps": round(data_bytes / t_d / 1e9, 3),
        }
    if not t_by_dir["enc"]:
        # every device engine died mid-phase: the parent prints the
        # error line carrying the verdicts, with no value
        err = RuntimeError(
            f"all device engines lost mid-phase "
            f"({[f['engine'] for f in failovers]})"
        )
        err.engine_failovers = failovers
        raise err
    enc_win = min(t_by_dir["enc"], key=t_by_dir["enc"].get)
    dec_win = min(t_by_dir["dec"], key=t_by_dir["dec"].get)
    t_encode = t_by_dir["enc"][enc_win]
    t_decode = t_by_dir["dec"][dec_win]
    engine = enc_win if enc_win == dec_win else f"{enc_win}/{dec_win}"

    # ISSUE 9: one measured trace window over the winning engines —
    # the phase's fused-op/DMA/collective device-time split, captured
    # rather than inferred (the profiler tap attributes the events to
    # the gf_encode/gf_decode engine families).  The guard is generous:
    # the FIRST start_trace in a process pays ~15-20s of profiler init
    # on this container class, so tight-budget children must skip
    # capture entirely rather than burn their measurement budget on it
    device_trace = {"unavailable": "skipped (deadline close)"}
    if deadline is None or deadline - time.time() > 60:
        fns = {nm: (e32, d32) for nm, e32, d32 in live}
        import jax as _jax

        enc_fn = _jax.jit(fns[enc_win][0])
        dec_fn = _jax.jit(fns[dec_win][1])
        # warm OUTSIDE the window: these are fresh jit wrappers (empty
        # trace cache), and a compile inside the burst would both
        # pollute the capture and book compile seconds as steady-state
        # exec via compiled=False
        _jax.block_until_ready(enc_fn(data))
        _jax.block_until_ready(dec_fn(data))

        def _burst():
            with prof.timed(f"gf_encode[{enc_win}]",
                            ("headline-enc", enc_win, data.shape),
                            nbytes=data_bytes, compiled=False):
                _jax.block_until_ready(enc_fn(data))
            with prof.timed(f"gf_decode[{dec_win}]",
                            ("headline-dec-full", dec_win, data.shape),
                            nbytes=data_bytes, compiled=False):
                _jax.block_until_ready(dec_fn(data))

        device_trace, burst_err = _capture_or_failover(_burst,
                                                       "headline")
        if burst_err:
            failovers.append({
                "engine": engine, "error": burst_err,
                "t": round(time.time() - T0, 1),
            })

    out = {
        "platform": str(dev),
        "engine": engine,
        "engines": engines,
        **({"engine_failover": failovers} if failovers else {}),
        # the measured batch, recorded so the regression gate never
        # compares a --batch 8 round against a 64 MiB one
        "batch_bytes": data_bytes,
        "encode_gbps": data_bytes / t_encode / 1e9,
        "reconstruct_gbps": data_bytes / t_decode / 1e9,
        "combined_gbps": 2 * data_bytes / (t_encode + t_decode) / 1e9,
    }
    # the phase's kernel evidence rides its own JSON line
    out["device_trace"] = device_trace
    out["kernel_profile"] = prof.dump()
    return out


def bench_grid(quick: bool, deadline: float | None) -> dict:
    """The rest of the BASELINE.md grid on the device (VERDICT r2 Weak
    #1: the perf contract was 1/5 measured).  One child, one device
    acquisition, one config at a time:

    1. jerasure reed_sol_van k=2 m=1, 4 KiB stripes — the small-stripe
       case (SURVEY hard part #1): ≥64 stripes batched per device call
       (here 16384 stripes = 32 MiB/chunk-row).
    3. jerasure cauchy_good k=10 m=4 w=8 packetsize=4096 — the
       BITMATRIX kernel family (whole-packet XOR schedule).
    4. LRC k=8 m=4 l=4 — the layered code collapsed to its generator
       matrix (linear codes compose; parity bytes verified against the
       codec) + local-group XOR repair.
    5. SHEC k=8 m=4 c=3 — shingled matrix, MULTI-failure (3-erasure)
       decode.

    Every kernel's parity bytes are verified against the repo codec
    (which test_isa_oracle pins to the vendored reference) before it is
    timed.  Per-config vs_native is this host's single-thread C++ engine
    on the same matrix shapes.
    """
    import jax

    dev = jax.devices()[0]
    log(f"grid child: device ready: {dev}")

    from ceph_tpu.models import registry
    from ceph_tpu.ops import matrices as mx
    from ceph_tpu.ops.gf import gf
    from ceph_tpu.ops.gf_jax import (
        bytes_to_u32,
        make_bitmatrix_matmul_u32,
        make_gf_matmul_u32,
        u32_to_bytes,
    )
    from ceph_tpu.utils import native

    G8 = gf(8)
    rng = np.random.default_rng(7)
    out: dict[str, dict] = {}
    _kprof().reset()  # grid gets its own kernel-profile window

    def left() -> float:
        return float("inf") if deadline is None else deadline - time.time()

    def _np_oracle(matrix, inp_u8, bitmatrix):
        """Host-side expected output prefix for kernel verification."""
        cols = 256
        if bitmatrix:
            bm = np.asarray(matrix) != 0
            out = np.zeros((bm.shape[0], cols), dtype=np.uint8)
            for i in range(bm.shape[0]):
                acc = np.zeros(cols, dtype=np.uint8)
                for j in range(bm.shape[1]):
                    if bm[i, j]:
                        acc ^= inp_u8[j, :cols]
                out[i] = acc
            return out
        return G8.matmul_region(
            np.asarray(matrix, dtype=np.int64), inp_u8[:, :cols]
        )

    def _engine(matrix, n4, *, bitmatrix):
        """All live engines for this matrix shape, pallas first: the
        fused Pallas kernel when the TPU + lane count allow it, plus the
        XLA kernel — both u32-native.  run_cfg races them on device
        (VERDICT r4 #7: per-config engine evidence, not code-comment
        folklore)."""
        from ceph_tpu.ops import gf_pallas

        cands: list[tuple[object, str]] = []
        blk = next(
            (b for b in (8192, gf_pallas.BLOCK) if n4 % b == 0), None
        )
        if gf_pallas.on_tpu() and blk:
            if bitmatrix:
                cand = gf_pallas.make_bitmatrix_matmul_pallas(
                    matrix, block=blk
                )
            else:
                cand = gf_pallas.make_gf_matmul_pallas(
                    matrix, W, block=blk
                )
            cands.append((cand, "pallas"))
        if bitmatrix:
            cands.append((make_bitmatrix_matmul_u32(matrix), "xla"))
        else:
            cands.append((make_gf_matmul_u32(matrix, W), "xla"))
        return cands

    def run_cfg(name, enc_matrix, data_u8, dec_matrix, dec_input_u8,
                *, bitmatrix=False):
        """Measure encode + reconstruct for one config.  BOTH kernels'
        outputs are verified against the numpy GF oracle on their own
        inputs before they are timed; throughput is normalized by each
        direction's OWN input size (the decode input can be smaller,
        e.g. an LRC local group — review r3 finding)."""
        enc_bytes = data_u8.size
        dec_bytes = dec_input_u8.size
        enc_cands = _engine(
            enc_matrix, data_u8.shape[1] // 4, bitmatrix=bitmatrix
        )
        dec_cands = _engine(
            dec_matrix, dec_input_u8.shape[1] // 4, bitmatrix=bitmatrix
        )
        dev_in = jax.device_put(bytes_to_u32(data_u8), dev)
        dec_in = jax.device_put(bytes_to_u32(dec_input_u8), dev)

        def verified(cand_list, dev_arr, host_arr, matrix):
            """Candidates whose bytes match the numpy oracle on their
            own input.  A miscompiling candidate is DROPPED, not fatal —
            configs must never be lost while a verified engine is live;
            only zero verified engines aborts the config."""
            keep = []
            for fn, eng in cand_list:
                try:
                    out_dev = np.asarray(jax.jit(fn)(dev_arr))
                    head = u32_to_bytes(out_dev[:, :64])  # 64 u32=256 B
                    np.testing.assert_array_equal(
                        head, _np_oracle(matrix, host_arr, bitmatrix)
                    )
                except Exception as e:
                    log(f"grid child: {name}: dropping {eng} "
                        f"({type(e).__name__})")
                    continue
                keep.append((fn, eng))
            if not keep:
                raise RuntimeError(f"{name}: no verified engine")
            return keep

        enc_cands = verified(enc_cands, dev_in, data_u8, enc_matrix)
        dec_cands = verified(dec_cands, dec_in, dec_input_u8, dec_matrix)

        def race(cand_list, dev_arr, nbytes, tag):
            """Time each engine, return (winner_t, winner_name, rates).
            The second engine is skipped when the grid deadline is close
            — configs must never be lost to the race."""
            rates: dict[str, float] = {}
            best_t, best_n = None, None
            for i, (fn, eng) in enumerate(cand_list):
                if i > 0 and left() < 25:
                    log(f"grid child: {name} {tag}: skipping {eng} race "
                        f"(deadline close)")
                    break
                t = _measure_rate(
                    f"{name} {tag}[{eng}]", fn, dev_arr, nbytes, quick,
                    deadline,
                )
                rates[eng] = round(nbytes / t / 1e9, 3)
                if best_t is None or t < best_t:
                    best_t, best_n = t, eng
            return best_t, best_n, rates

        t_enc, eng_e, enc_rates = race(enc_cands, dev_in, enc_bytes,
                                       "encode")
        t_dec, eng_d, dec_rates = race(dec_cands, dec_in, dec_bytes,
                                       "reconstruct")
        cfg = {
            "encode_gbps": round(enc_bytes / t_enc / 1e9, 3),
            "reconstruct_gbps": round(dec_bytes / t_dec / 1e9, 3),
            "combined_gbps": round(
                (enc_bytes + dec_bytes) / (t_enc + t_dec) / 1e9, 3
            ),
            "engine": eng_e if eng_e == eng_d else f"{eng_e}/{eng_d}",
        }
        if len(enc_rates) > 1 or len(dec_rates) > 1:
            cfg["engine_race"] = {
                "encode": enc_rates, "reconstruct": dec_rates
            }
        return cfg

    def native_ratio(cfg, matrix, k):
        n = 1 << 20
        d = rng.integers(0, 256, size=(k, n // k), dtype=np.uint8)
        d = d[:, : (d.shape[1] // 8) * 8]
        t = bench_loop(
            lambda: native.encode(np.asarray(matrix, dtype=np.int64), d),
            min_seconds=0.2, deadline=deadline,
        )
        nat = d.size / t / 1e9
        cfg["native_1t_encode_gbps"] = round(nat, 3)
        cfg["vs_native_1t"] = round(cfg["encode_gbps"] / nat, 3)

    # -- config 1: k2m1 @ 4 KiB stripes --------------------------------------
    if left() > 30:
        try:
            P = mx.rs_vandermonde(2, 1, 8)  # [[1, 1]] — the XOR parity
            stripes = 16384
            n = stripes * 2048  # 4 KiB stripe -> 2 KiB chunks
            data = rng.integers(0, 256, size=(2, n), dtype=np.uint8)
            cfg = run_cfg("k2m1-4KiB", P, data, P, data)
            cfg["stripes_per_call"] = stripes
            native_ratio(cfg, P, 2)
            out["jerasure_k2m1_4KiB"] = cfg
        except Exception as e:
            log(f"grid child: k2m1 failed: {e!r}")

    # -- config 3: cauchy_good k10m4 w8 ps4096 (bitmatrix) -------------------
    if left() > 30:
        try:
            from ceph_tpu.models.matrix_codec import BitmatrixErasureCode

            k, m, w, ps = 10, 4, 8, 4096
            M = mx.cauchy_good(k, m, w)
            codec = BitmatrixErasureCode(k, m, w, M, ps)
            # blocks -> 15 MiB data: small payloads put the chained-scan
            # marginal at noise level (one run reported a 268 GB/s
            # reconstruct outlier vs ~9 GB/s real)
            B = 48
            packets = rng.integers(
                0, 256, size=(k * w, B * ps), dtype=np.uint8
            )
            present = tuple(range(1, k + 1))
            RM, _rm_key = codec._recovery_bitmatrix(present, (0,))
            surv = rng.integers(
                0, 256, size=(k * w, B * ps), dtype=np.uint8
            )
            bm = G8.matrix_to_bitmatrix(M)
            cfg = run_cfg(
                "cauchy-k10m4", bm, packets, RM, surv, bitmatrix=True
            )
            cfg["packetsize"] = ps
            native_ratio(cfg, M, k)
            out["jerasure_cauchy_good_k10m4_ps4096"] = cfg
        except Exception as e:
            log(f"grid child: cauchy failed: {e!r}")

    # -- config 4: LRC 8-4-l (generator-matrix collapse) ---------------------
    # BASELINE.md says l=4, but the REFERENCE itself rejects that combo:
    # parse_kml demands k and m be multiples of (k+m)/l
    # (reference:src/erasure-code/lrc/ErasureCodeLrc.cc:321-331), and
    # 8 % ((8+4)/4)=3 != 0.  l=3 is the valid neighbor (4 local groups),
    # matching the repo corpus profile lrc-4096-k=8-l=3-m=4.
    if left() > 30:
        try:
            codec = registry.instance().factory(
                "lrc", {"k": "8", "m": "4", "l": "3"}
            )
            kd = codec.get_data_chunk_count()
            ntot = codec.get_chunk_count()
            # extract the parity generator by probing (linear code)
            Gp = np.zeros((ntot - kd, kd), dtype=np.int64)
            for j in range(kd):
                probe = np.zeros((kd, 8), dtype=np.uint8)
                probe[j, :] = 1
                Gp[:, j] = codec.encode_chunks(probe)[:, 0]
            # verify the collapse against the layered codec
            sample = rng.integers(0, 256, size=(kd, 64), dtype=np.uint8)
            np.testing.assert_array_equal(
                G8.matmul_region(Gp, sample), codec.encode_chunks(sample)
            )
            n = 1 << 21
            data = rng.integers(0, 256, size=(kd, n), dtype=np.uint8)
            # local repair: one data chunk from its local group = a pure
            # XOR row over the group (the LRC selling point)
            ones = np.ones((1, 3), dtype=np.int64)
            grp = rng.integers(0, 256, size=(3, n), dtype=np.uint8)
            cfg = run_cfg("lrc-8-4-3", Gp, data, ones, grp)
            cfg["note"] = (
                "l=3: the reference rejects l=4 with k=8 m=4 "
                "(k,m must be multiples of (k+m)/l)"
            )
            native_ratio(cfg, Gp, kd)
            out["lrc_k8m4l3"] = cfg
        except Exception as e:
            log(f"grid child: lrc failed: {e!r}")

    # -- config 5: SHEC 8-4-3 multi-failure ----------------------------------
    if left() > 30:
        try:
            codec = registry.instance().factory(
                "shec", {"k": "8", "m": "4", "c": "3"}
            )
            Ms = np.asarray(codec.matrix, dtype=np.int64)  # [4, 8]
            k = 8
            # 3-erasure (multi-failure) recovery via the codec's own
            # minimal-set solver (shingled codes need the RIGHT survivor
            # subset, not just any k)
            erased = (0, 1, 2)
            present = tuple(r for r in range(k + 4) if r not in erased)
            ordered, X = codec._solve(present, erased)
            if X is None:
                raise RuntimeError("shec cannot decode the chosen erasures")
            RMs = np.asarray(X, dtype=np.int64)
            n = 1 << 21
            data = rng.integers(0, 256, size=(k, n), dtype=np.uint8)
            surv = rng.integers(
                0, 256, size=(len(ordered), n), dtype=np.uint8
            )
            cfg = run_cfg("shec-8-4-3", Ms, data, RMs, surv)
            cfg["erasures"] = len(erased)
            native_ratio(cfg, Ms, k)
            out["shec_k8m4c3"] = cfg
        except Exception as e:
            log(f"grid child: shec failed: {e!r}")

    return {"platform": str(dev), "configs": out,
            "kernel_profile": _kprof().dump()}


def bench_crush(deadline: float | None) -> dict:
    """crushtool --test 1M-object placement sim (BASELINE config 5's
    second half) ON THE DEVICE (VERDICT r3 Weak #3: the cpu pin meant the
    SURVEY §3.5 north star was never measured where it counts).

    Two map shapes: the flat 64-device straw2 rule (the
    ``crushtool --test`` default shape, reference:src/crush/
    CrushTester.cc:648) and a racks->hosts->devices chooseleaf rule (the
    production shape, hier engine).  Placement statistics are bincounted
    on device (mapper_jax.vec_rule_stats) so only counts come back to
    the host; a sampled lane subset is fetched and checked bit-exact
    against the scalar oracle.  Baselines measured in the same run: the
    python scalar mapper and the native C straw2 engine
    (native/crush_cpu.cc, the reference's single-thread mapper.c class).
    """
    import jax

    dev = jax.devices()[0]
    from ceph_tpu.crush import mapper, mapper_jax
    from ceph_tpu.crush.map import CrushMap

    def left() -> float:
        return float("inf") if deadline is None else deadline - time.time()

    out: dict = {"platform": str(dev)}
    _kprof().reset()  # crush phase window (vec_rule_stats reports in)
    shapes: dict[str, tuple] = {}
    n_dev, nrep = 64, 3
    cmap = CrushMap.flat(n_dev)
    rule = cmap.add_simple_rule(cmap.root_id(), 0, indep=False, max_size=nrep)
    shapes["flat_64"] = (cmap, rule, nrep, 1_000_000)
    # 16 hosts x 4 devices, chooseleaf firstn over hosts — the hier engine
    hmap = CrushMap.hierarchical(
        [[h * 4 + d for d in range(4)] for h in range(16)]
    )
    hrule = hmap.add_simple_rule(hmap.root_id(), 1, indep=False, max_size=nrep)
    shapes["chooseleaf_16x4"] = (hmap, hrule, nrep, 1_000_000)

    for name, (m, rn, nr, n_x) in shapes.items():
        if left() < 20:
            break
        try:
            xs = np.arange(n_x, dtype=np.uint32)
            # warm at full shape (one compile), then time the second call
            mapper_jax.vec_rule_stats(m, rn, xs, nr)
            t0 = time.perf_counter()
            counts, bad = mapper_jax.vec_rule_stats(m, rn, xs, nr)
            t_vec = time.perf_counter() - t0
            # bit-exact spot check: 128 sampled lanes vs the scalar oracle
            sample_xs = np.linspace(0, n_x - 1, 128, dtype=np.uint32)
            vec_rows = mapper_jax.vec_do_rule(m, rn, sample_xs, nr)
            for i, x in enumerate(sample_xs):
                ref = mapper.crush_do_rule(m, rn, int(x), nr)
                assert list(vec_rows[i]) == ref, (int(x), list(vec_rows[i]), ref)
            # python scalar baseline on a sample
            s = 1000
            t0 = time.perf_counter()
            for x in range(s):
                mapper.crush_do_rule(m, rn, x, nr)
            t_scalar_per = (time.perf_counter() - t0) / s
            cfg = {
                "mappings": n_x,
                "vec_seconds": round(t_vec, 3),
                "mappings_per_sec": round(n_x / t_vec, 0),
                "placed": int(sum(counts.values())),
                "bad_mappings": int(bad),
                "scalar_per_mapping_us": round(t_scalar_per * 1e6, 2),
                "vs_scalar": round(t_scalar_per * n_x / t_vec, 1),
            }
            try:  # native C straw2 single-thread cost (honest C baseline)
                from ceph_tpu.utils import native_crush

                t_c = native_crush.bench_flat(m, rn, nr, min(200_000, n_x))
                cfg["native_c_per_mapping_us"] = round(t_c * 1e6, 3)
                cfg["vs_native_c"] = round(t_c * n_x / t_vec, 2)
            except Exception as e:
                log(f"crush: native C baseline unavailable: {e!r}")
            out[name] = cfg
            log(f"crush {name}: {cfg['mappings_per_sec']:.0f} mappings/s "
                f"(vs_scalar {cfg['vs_scalar']}x)")
        except Exception as e:
            log(f"crush {name} failed: {e!r}")
    out["kernel_profile"] = _kprof().dump()
    return out


def _bench_codec_stack(deadline: float | None) -> float:
    """GB/s of the OSD data path's batched encode: ec_util.encode over
    the registry-built RS(8,3) codec, whole-buffer in, shards out."""
    from ceph_tpu.models import registry
    from ceph_tpu.osd import ec_util

    codec = registry.instance().factory(
        "isa", {"plugin": "isa", "technique": "reed_sol_van",
                "k": str(K), "m": str(M)},
    )
    chunk = codec.get_chunk_size(4096 * K)
    sinfo = ec_util.StripeInfo(
        stripe_width=chunk * K, chunk_size=chunk
    )
    rng = np.random.default_rng(1)
    buf = rng.integers(
        0, 256, size=(sinfo.stripe_width * 512,), dtype=np.uint8
    )  # 512 stripes per call
    ec_util.encode(sinfo, codec, buf)  # warm/compile
    t = bench_loop(
        lambda: ec_util.encode(sinfo, codec, buf),
        min_iters=3, min_seconds=0.5, deadline=deadline,
    )
    return buf.size / t / 1e9


def _bench_stack_e2e(deadline: float | None) -> dict:
    """The WHOLE-stack round trip the zero-copy PR targets, measured
    end to end off the wire format: client write frame encode (segment
    list, no join) -> frame decode (views) -> striper extent table
    (vectorized) -> EC encode (one gather + device/native call) ->
    shard reply frames.  GB/s over the client payload, plus the
    ``data_path`` copy audit for ONE pass — the copies-per-payload
    ratio is the PR's whole point, so the round JSON carries it."""
    from ceph_tpu.models import registry
    from ceph_tpu.msg import message as msgmod
    from ceph_tpu.msg import messages as msgs
    from ceph_tpu.osd import ec_util
    from ceph_tpu.rados.striper import StripedLayout
    from ceph_tpu.utils import buffers as _bufs

    codec = registry.instance().factory(
        "isa", {"plugin": "isa", "technique": "reed_sol_van",
                "k": str(K), "m": str(M)},
    )
    chunk = codec.get_chunk_size(4096 * K)
    sinfo = ec_util.StripeInfo(stripe_width=chunk * K, chunk_size=chunk)
    layout = StripedLayout(stripe_unit=sinfo.stripe_width,
                           stripe_count=1, object_size=1 << 26)
    rng = np.random.default_rng(11)
    payload = rng.integers(
        0, 256, size=(sinfo.stripe_width * 512,), dtype=np.uint8
    ).tobytes()

    def one_pass() -> int:
        # client: MOSDOp write frame as a segment list (vectored send)
        op = msgs.MOSDOp(
            tid=1, epoch=1, pool="bench", oid="obj",
            ops=[{"op": "write", "data": 0}], blobs=[payload],
        )
        segs, total, _rel = msgmod.encode_frame_segments(op, 1)
        # wire: the transport would scatter/gather these; the receiver
        # sees one contiguous receive buffer — model that cost honestly
        # with a single join standing in for the kernel's copy
        frame = b"".join(segs)
        _rel()  # scratch recycled the moment the "socket" has it
        # osd: decode hands out VIEWS of the receive buffer
        decoded, _seq = msgmod.decode_frame(frame)
        data = decoded.blobs[0]
        # striper: vectorized extent table, view slices
        obj, ooff, run, boff = layout.extent_table(0, len(data))
        view = memoryview(data)
        shard_msgs = []
        for i in range(obj.size):
            chunk_view = view[int(boff[i]): int(boff[i]) + int(run[i])]
            # EC: one gather-into-layout + the device/native call
            shards = ec_util.encode(sinfo, codec, chunk_view)
            # fan-out: shard rows ride sub-write frames as views
            for s in (0, K):  # one data + one parity shard is enough
                sub = msgs.MOSDECSubOpWrite(
                    pgid="1.0", tid=1, from_osd=0, shard=s, epoch=1,
                    at_version=[1, 1], trim_to=[0, 0], log=[], txn=[],
                    blobs=[shards[s]],
                )
                _ssegs, _stotal, _srel = msgmod.encode_frame_segments(
                    sub, 2)
                _srel()
                shard_msgs.append(_stotal)
        return total + sum(shard_msgs)

    one_pass()  # warm/compile
    _bufs.reset_copies()
    one_pass()
    copied = _bufs.copied_bytes()
    per_hop = {
        h: _bufs.copied_bytes(h)
        for h in ("msgr_encode", "msgr_decode", "striper", "ec_gather",
                  "client_read", "flatten")
        if _bufs.copied_bytes(h)
    }
    t = bench_loop(one_pass, min_iters=3, min_seconds=0.5,
                   deadline=deadline)
    return {
        "stack_e2e_gbps": round(len(payload) / t / 1e9, 3),
        "payload_bytes": len(payload),
        "copied_bytes_per_pass": copied,
        "copied_ratio": round(copied / len(payload), 3),
        "copied_by_hop": per_hop,
    }


def _smallops_waterfall(deadline: float | None, n_ops: int = 96) -> dict:
    """Small-op hop waterfall + header cost ledger (ISSUE 12): a real
    1-OSD loopback MiniCluster serves ``n_ops`` 4 KiB writes with
    ``osd_op_trace_sample_every=1``, and every op's cross-hop spans
    are read back from the client-side waterfall
    (common/tracing.op_waterfall — the same merge `dump_op_waterfall`
    serves).  Reports per-hop p50/p99 and ``header_share``: the
    measured frame-header encode+decode seconds
    (stack.header_encode_s/header_decode_s, timed at the messenger
    boundary — struct pack/unpack + field-tail codec since the binary
    wire protocol landed; json.dumps/loads before it) over total op
    wall time.  At 4 KiB the payload-proportional work is negligible,
    so this approximates the non-payload share directly — the ~6.6%
    JSON-era baseline the binary header is gated against via
    ``bench_regress --metric smallops.header_share`` (lower is
    better).  op_p99_ms comes from the serial walls (one op in
    flight — honest per-op latency); the promoted ops_per_sec comes
    from a depth-32 pipelined window on the same cluster (ISSUE 19:
    the op aggregator + wire-level batch frames only exist at depth),
    with the serial rate kept alongside as ops_per_sec_serial."""
    import asyncio

    from ceph_tpu.common import stack_ledger
    from ceph_tpu.common.tracing import op_waterfall
    from ceph_tpu.rados.cluster import MiniCluster

    payload = np.random.default_rng(11).integers(
        0, 256, size=4096, dtype=np.uint8
    ).tobytes()

    async def drive() -> dict:
        async with MiniCluster(
            n_osds=1,
            config_overrides={"osd_op_trace_sample_every": 1},
        ) as c:
            cl = await c.client()
            await cl.create_pool("wf", "replicated", size=1)
            # warm-up: first op pays connect + clock-probe seeding;
            # its hops would misreport the steady state
            for i in range(4):
                await cl.operate(
                    "wf", f"warm{i}",
                    [{"op": "writefull", "data": 0}], [payload],
                )
            stack_ledger.reset_stack()
            traces = []
            walls = []
            t_all0 = time.perf_counter()
            for i in range(n_ops):
                if deadline is not None and deadline - time.time() < 10:
                    # a slow/contended host must not blow the bench's
                    # budget here: keep the partial capture (the
                    # percentiles just get fewer samples)
                    log(f"smallops: waterfall stopping at {i} ops "
                        "(deadline close)")
                    break
                t0 = time.perf_counter()
                reply = await cl.operate(
                    "wf", f"o{i}",
                    [{"op": "writefull", "data": 0}], [payload],
                )
                walls.append(time.perf_counter() - t0)
                traces.append(reply.trace)
            wall_s = time.perf_counter() - t_all0
            # NB: assigning to n_ops here would shadow the enclosing
            # parameter and make the range(n_ops) loop above raise
            # UnboundLocalError — the silent-capture bug that kept
            # header_share out of every pre-binary-header round
            n_done = len(traces)
            if not traces:
                return {"unavailable": "deadline before any sampled op"}
            enc_s, dec_s = stack_ledger.header_seconds()
            per_hop: dict[str, list] = {}
            covered = 0
            for tr in traces:
                wf = op_waterfall(tr)
                if wf["hops"]:
                    covered += 1
                for h in wf["hops"]:
                    per_hop.setdefault(h["hop"], []).append(h["dur_s"])
            hops = {
                hop: {
                    "p50_ms": round(float(np.percentile(v, 50)) * 1e3, 4),
                    "p99_ms": round(float(np.percentile(v, 99)) * 1e3, 4),
                    "n": len(v),
                }
                for hop, v in sorted(per_hop.items())
            }
            # tail-sampling overhead (ISSUE 18): ops/sec with the keep
            # policy ARMED at production settings (provisional spans on
            # every op, 1-in-N baseline keeps) vs tracing OFF entirely
            # (keep policy disarmed AND head sampling zeroed), on the
            # SAME cluster via live config flips — the share gates the
            # always-on decide-late tracing against the PR-13 IOPS win
            async def _rate_arm(keep: bool, every: int, tag: str
                                ) -> float | None:
                for osd in c.osds.values():
                    osd.config.set("osd_trace_keep", keep)
                    osd.config.set("osd_op_trace_sample_every", every)
                if deadline is not None and deadline - time.time() < 8:
                    return None
                n = 0
                t0 = time.perf_counter()
                for i in range(n_ops):
                    if deadline is not None \
                            and deadline - time.time() < 5:
                        break
                    await cl.operate(
                        "wf", f"{tag}{i}",
                        [{"op": "writefull", "data": 0}], [payload],
                    )
                    n += 1
                dt = time.perf_counter() - t0
                return n / dt if n and dt > 0 else None

            armed_rate = await _rate_arm(True, 64, "arm")
            off_rate = await _rate_arm(False, 0, "off")
            overhead = None
            if armed_rate and off_rate:
                overhead = round(max(0.0, 1.0 - armed_rate / off_rate), 4)

            # ISSUE 19: the pipelined window — serial walls above keep
            # the hop percentiles and op_p99 honest (one op in flight,
            # nothing to batch), but the aggregator + wire-level op
            # batching only show at depth.  Bounded concurrency, keep
            # policy armed at production settings, and the client/
            # messenger batching counters read back so the promoted
            # rate says HOW it was reached (ops actually packed per
            # frame), not just that it was.
            async def _pipelined_rate(n: int, width: int
                                      ) -> dict | None:
                for osd in c.osds.values():
                    osd.config.set("osd_trace_keep", True)
                    osd.config.set("osd_op_trace_sample_every", 64)
                if deadline is not None and deadline - time.time() < 8:
                    return None
                base_ops = cl.messenger.perf.get("batched_ops")
                base_frames = cl.messenger.perf.get("batch_frames")
                sem = asyncio.Semaphore(width)
                done = 0

                async def one(i: int) -> None:
                    nonlocal done
                    async with sem:
                        if deadline is not None \
                                and deadline - time.time() < 5:
                            return
                        await cl.operate(
                            "wf", f"p{i}",
                            [{"op": "writefull", "data": 0}], [payload],
                        )
                        done += 1

                t0 = time.perf_counter()
                await asyncio.gather(*[one(i) for i in range(n)])
                dt = time.perf_counter() - t0
                if not done or dt <= 0:
                    return None
                opf = cl.perf.get("ops_per_frame")  # [sum, n, min, max]
                return {
                    "ops": done,
                    "depth": width,
                    "ops_per_sec": round(done / dt, 1),
                    "batched_ops": cl.messenger.perf.get("batched_ops")
                    - base_ops,
                    "batch_frames": cl.messenger.perf.get("batch_frames")
                    - base_frames,
                    "ops_per_flush_avg": round(opf[0] / opf[1], 2)
                    if opf[1] else None,
                }

            pipelined = await _pipelined_rate(512, 32)

            total_op_s = float(sum(walls))
            return {
                **({"trace_overhead_share": overhead,
                    "ops_per_sec_keep_armed": round(armed_rate, 1),
                    "ops_per_sec_tracing_off": round(off_rate, 1)}
                   if overhead is not None else {}),
                "ops": n_done,
                "payload_bytes": len(payload),
                # the promoted rate is the PIPELINED one (depth 32) —
                # that is the client's real concurrency shape and the
                # only regime where op batching exists to regress; the
                # serial rate stays alongside so the two never blur
                "ops_per_sec": (pipelined["ops_per_sec"] if pipelined
                                else round(n_done / wall_s, 1)),
                "ops_per_sec_serial": round(n_done / wall_s, 1),
                **({"pipelined": pipelined} if pipelined else {}),
                "op_p50_ms": round(
                    float(np.percentile(walls, 50)) * 1e3, 4),
                "op_p99_ms": round(
                    float(np.percentile(walls, 99)) * 1e3, 4),
                "hops": hops,
                "sampled_ops_with_spans": covered,
                "header_encode_s": round(enc_s, 6),
                "header_decode_s": round(dec_s, 6),
                "frame_allocs": int(
                    stack_ledger.stack_perf().get("frame_allocs")),
                # the ledger counts EVERY frame in the window (map
                # subs and mon chatter included) — honest: those
                # headers are part of what the stack pays per op
                "header_share": round(
                    (enc_s + dec_s) / total_op_s, 4
                ) if total_op_s > 0 else 0.0,
            }

    return asyncio.run(drive())


def _smallops_proc(deadline: float | None, n_ops: int = 384) -> dict:
    """Multi-host truth pass (ISSUE 19 / ROADMAP 1c): the same
    pipelined smallops round against a real-multiprocess ProcCluster
    (2 OSD processes + 1 mon process, TCP between them), with the hop
    re-rank read off the mgr's kept-trace store via ``trace top`` /
    ``trace summary`` — NOT off loopback client-side merges.  The mgr
    runs in THIS process (exactly how an operator box would host it:
    it beacons to the mon, the map names it, OSD processes discover it
    from the map push and report kept waterfalls over MPGStats).
    Per-hop p99s come from the kept traces' spans; every cross-process
    span (wire, client_serialize — the ones whose endpoints live on
    two clocks) must carry clock-alignment uncertainty or the ranking
    is fiction, and the record pins how many did."""
    import asyncio
    import tempfile

    from ceph_tpu.common import Config
    from ceph_tpu.mgr import MgrDaemon
    from ceph_tpu.rados.proc_cluster import ProcCluster
    from ceph_tpu.tools.ceph_cli import _mgr_command

    payload = np.random.default_rng(13).integers(
        0, 256, size=4096, dtype=np.uint8
    ).tobytes()

    async def drive(store_dir: str) -> dict:
        async with ProcCluster(
            store_dir, n_osds=2,
            osd_config={
                # baseline keeps 1-in-16 so the trace store fills from
                # a healthy run (the keep policy's slow/error/replay
                # lanes stay armed on top), reports flushed fast enough
                # that the ranking reads THIS round, not the last one
                "osd_op_trace_sample_every": 16,
                "osd_mgr_report_interval": 0.25,
            },
        ) as pc:
            mgr = MgrDaemon("mgr.bench", pc.monmap, config=Config())
            try:
                await mgr.start()
                cl = await pc.client()
                await cl.create_pool("wf", "replicated", size=2)
                # the map must name the mgr before OSD processes can
                # report to it (map push: mon -> osd, mon -> client)
                async with asyncio.timeout(15):
                    while not (cl.osdmap and cl.osdmap.mgr_addr
                               and mgr.active):
                        await asyncio.sleep(0.05)
                for i in range(4):
                    await cl.operate(
                        "wf", f"warm{i}",
                        [{"op": "writefull", "data": 0}], [payload],
                    )

                sem = asyncio.Semaphore(32)
                done = 0

                async def one(i: int) -> None:
                    nonlocal done
                    async with sem:
                        if deadline is not None \
                                and deadline - time.time() < 20:
                            return
                        await cl.operate(
                            "wf", f"o{i}",
                            [{"op": "writefull", "data": 0}], [payload],
                        )
                        done += 1

                t0 = time.perf_counter()
                await asyncio.gather(*[one(i) for i in range(n_ops)])
                wall_s = time.perf_counter() - t0
                if not done:
                    return {"unavailable": "deadline before any op"}

                # keeps ride the NEXT MPGStats report; wait until the
                # store has a usable population (deadline-bounded)
                rows = []
                async with asyncio.timeout(10):
                    while len(rows) < 4:
                        rc, out = await _mgr_command(
                            cl, {"prefix": "trace ls", "limit": 256})
                        rows = out["traces"] if rc == 0 else []
                        if len(rows) < 4:
                            await asyncio.sleep(0.25)

                rc, top = await _mgr_command(
                    cl, {"prefix": "trace top", "n": 8})
                rc2, summ = await _mgr_command(
                    cl, {"prefix": "trace summary"})
                if rc != 0 or rc2 != 0:
                    return {"unavailable": "mgr trace query failed"}

                # per-hop p99 across the kept set: pull each kept
                # trace's full waterfall (trace show) — spans carry
                # entity + uncertainty, which the summary rows do not.
                # Cross-process = the span's endpoints live on two
                # clocks: the wire hop (client send stamp aligned into
                # the assembling OSD's time) and any span whose entity
                # is not the assembling OSD (client_serialize).  The
                # OSD-local hops (dispatch/qos_wait/execute) honestly
                # carry none — both stamps are one clock.
                per_hop: dict[str, list] = {}
                cross_spans = 0
                cross_with_unc = 0
                for row in rows[:128]:
                    rc3, rec = await _mgr_command(
                        cl, {"prefix": "trace show",
                             "trace": row["trace"]})
                    if rc3 != 0:
                        continue  # evicted between ls and show
                    osd_ent = f"osd.{rec.get('osd')}"
                    for h in rec.get("hops") or []:
                        per_hop.setdefault(h["hop"], []).append(
                            h.get("dur_s") or 0.0)
                        if (h["hop"] == "wire"
                                or str(h.get("entity")) != osd_ent):
                            cross_spans += 1
                            if (h.get("uncertainty_s") or 0.0) > 0.0:
                                cross_with_unc += 1
                hops = {
                    hop: {
                        "p50_ms": round(
                            float(np.percentile(v, 50)) * 1e3, 4),
                        "p99_ms": round(
                            float(np.percentile(v, 99)) * 1e3, 4),
                        "n": len(v),
                    }
                    for hop, v in sorted(per_hop.items())
                }
                return {
                    "n_osds": 2,
                    "ops": done,
                    "depth": 32,
                    "ops_per_sec": round(done / wall_s, 1),
                    "kept_traces": len(rows),
                    "hops": hops,
                    "hop_rank": [h["hop"]
                                 for h in summ["dominant_hops"]],
                    "summary": summ,
                    "top_wall_ms": [
                        round((r.get("wall_s") or 0.0) * 1e3, 3)
                        for r in top["traces"]],
                    "cross_process_spans": cross_spans,
                    "cross_process_spans_with_uncertainty":
                        cross_with_unc,
                }
            finally:
                await mgr.stop()

    with tempfile.TemporaryDirectory(prefix="bench_proc_") as d:
        return asyncio.run(drive(d))


def bench_smallops(deadline: float | None) -> dict:
    """Many-small-ops EC throughput: coalesced microbatch dispatch vs
    per-op dispatch over a mixed size distribution — the OSD's real
    concurrency shape (N in-flight writes of assorted sizes), not one
    giant buffer.

    512 ops of 1..16 stripes each (16 KiB..256 KiB at k=8 with 2 KiB
    chunks; ~64 MiB total).  The per-op side issues one device launch
    per op, exactly the pre-dispatcher data path; the coalesced side
    runs the same ops concurrently through
    ``ceph_tpu.osd.ec_dispatch.ECDispatcher`` (cross-op stacking +
    power-of-two shape buckets + worker-thread launches).  GB/s is
    logical bytes / wall time with the same numerator on both sides;
    both sides race with warm jit caches — the compile-storm pathology
    is gated separately (tests/test_ec_dispatch.py), this phase measures
    launch amortization.
    """
    import asyncio

    import jax

    dev = jax.devices()[0]
    from ceph_tpu.models import registry
    from ceph_tpu.osd import ec_util
    from ceph_tpu.osd.ec_dispatch import ECDispatcher
    from ceph_tpu.utils import native as _native

    prof = _kprof()
    prof.reset()
    codec = registry.instance().factory(
        "isa", {"plugin": "isa", "technique": "reed_sol_van",
                "k": str(K), "m": str(M)},
    )
    chunk = codec.get_chunk_size(2048 * K)
    sinfo = ec_util.StripeInfo(stripe_width=chunk * K, chunk_size=chunk)
    rng = np.random.default_rng(7)
    n_ops = 512
    if deadline is not None and deadline - time.time() < 45:
        n_ops = 128  # a tight budget still lands a comparable ratio
        log(f"smallops: shrinking to {n_ops} ops (deadline close)")
    sizes = [int(s) for s in rng.integers(1, 17, size=n_ops)]
    bufs = [
        rng.integers(0, 256, size=(s * sinfo.stripe_width,), dtype=np.uint8)
        for s in sizes
    ]
    total_bytes = int(sum(b.size for b in bufs))
    log(f"smallops: {n_ops} ops, {total_bytes >> 20} MiB total, "
        f"stripe {sinfo.stripe_width}")

    def per_op_pass() -> float:
        t0 = time.perf_counter()
        for b in bufs:
            ec_util.encode(sinfo, codec, b)
        return time.perf_counter() - t0

    async def coalesced_pass(check: bool) -> tuple[float, dict]:
        disp = ECDispatcher(window=0.002, max_stripes=2048)
        t0 = time.perf_counter()
        outs = await asyncio.gather(
            *[disp.encode(sinfo, codec, b) for b in bufs]
        )
        dt = time.perf_counter() - t0
        if check:  # oracle spot-pin: coalesced bytes == per-op bytes
            ref = ec_util.encode(sinfo, codec, bufs[0])
            for s in ref:
                assert np.array_equal(
                    np.asarray(outs[0][s]), np.asarray(ref[s])
                ), f"coalesced shard {s} diverged from per-op encode"
        stats = disp.dump()
        await disp.stop()
        return dt, stats

    # this phase gates the JAX kernel path on every backend: the native
    # C fallback has no launch/compile overhead to amortize (and the
    # dispatcher deliberately routes it per-op — cache-resident small
    # buffers beat one DRAM-bound pass), so leaving it active on a cpu
    # host would measure the wrong engine.  Overridden ONLY around the
    # measurement passes (try/finally), so a failure cannot leave the
    # engine disabled for the child's later phases.
    _native.host_engine_active()  # resolve the cache before overriding
    saved_host_active = _native._HOST_ACTIVE
    # warm pass each (compiles the per-size AND per-bucket shapes), then
    # best-of-2 timed passes per side (single-core hosts are noisy); a
    # close deadline keeps whatever passes landed
    try:
        _native._HOST_ACTIVE = False
        t_per = per_op_pass()
        t_coal, stats = asyncio.run(coalesced_pass(check=True))
        passes = 0
        while passes < 2 and (
            deadline is None or deadline - time.time() > 20
        ):
            t_per = min(t_per, per_op_pass())
            t2, stats2 = asyncio.run(coalesced_pass(check=False))
            if t2 < t_coal:
                t_coal, stats = t2, stats2
            passes += 1
        if passes == 0:
            log("smallops: keeping warm-pass timings (deadline close)")
    finally:
        _native._HOST_ACTIVE = saved_host_active

    # ISSUE 9: one trace window over a short coalesced burst — the
    # dispatcher-launch device-time split (measured, not inferred).
    # 45s guard: a process whose headline already opened a window pays
    # ~nothing here, but a first-window child pays ~15-20s of profiler
    # init (see bench_device) and must not blow its budget on it
    device_trace = {"unavailable": "skipped (deadline close)"}
    if deadline is None or deadline - time.time() > 45:
        sub = bufs[:32]

        async def _window_pass():
            disp = ECDispatcher(window=0.002, max_stripes=2048)
            await asyncio.gather(
                *[disp.encode(sinfo, codec, b) for b in sub]
            )
            await disp.stop()

        saved = _native._HOST_ACTIVE
        try:
            _native._HOST_ACTIVE = False  # same engine the ratio raced
            device_trace, _burst_err = _capture_or_failover(
                lambda: asyncio.run(_window_pass()), "smallops"
            )
        finally:
            _native._HOST_ACTIVE = saved

    # ISSUE 12: the op waterfall capture + header cost ledger — a real
    # loopback cluster round so the per-hop p50/p99 and header_share
    # land in the round JSON (bench_regress gates the share)
    waterfall: dict = {"unavailable": "skipped (deadline close)"}
    header_share = None
    if deadline is None or deadline - time.time() > 25:
        try:
            waterfall = _smallops_waterfall(deadline)
            header_share = waterfall.get("header_share")
            log(f"smallops: waterfall header_share="
                f"{header_share} over {waterfall.get('ops')} ops; "
                f"ops_per_sec={waterfall.get('ops_per_sec')}")
        except Exception as e:
            log(f"smallops: waterfall capture failed: {e!r}")
            waterfall = {"unavailable": repr(e)[:200]}

    # ISSUE 19: the multi-host truth pass — ProcCluster + in-process
    # mgr, hop re-rank off `trace top`/`trace summary`.  Recorded under
    # its own key so bench_regress's smallops.proc.ops_per_sec gate
    # never compares a cross-process rate against a loopback one
    proc: dict = {"unavailable": "skipped (deadline close)"}
    if deadline is None or deadline - time.time() > 60:
        try:
            proc = _smallops_proc(deadline)
            log(f"smallops: proc ops_per_sec="
                f"{proc.get('ops_per_sec')} "
                f"hop_rank={proc.get('hop_rank')}")
        except Exception as e:
            log(f"smallops: proc capture failed: {e!r}")
            proc = {"unavailable": repr(e)[:200]}

    return {
        **({"header_share": header_share}
           if header_share is not None else {}),
        # tail-sampling overhead gate (ISSUE 18): armed-vs-off ops/sec
        # share from the same waterfall cluster, promoted so the
        # bench_regress smallops.trace_overhead_share gate can see it
        **({"trace_overhead_share": waterfall["trace_overhead_share"]}
           if waterfall.get("trace_overhead_share") is not None else {}),
        # IOPS promotion (this PR): ops/sec + op p99 from the same
        # capture ride the record top level so the bench_regress
        # smallops.ops_per_sec / smallops.op_p99 gates can see them
        **({"ops_per_sec": waterfall["ops_per_sec"]}
           if waterfall.get("ops_per_sec") is not None else {}),
        **({"op_p99_ms": waterfall["op_p99_ms"]}
           if waterfall.get("op_p99_ms") is not None else {}),
        "waterfall": waterfall,
        "proc": proc,
        "platform": str(dev),
        # cold_passes: the ratio below came from the WARM passes only
        # (deadline closed in) — per-op paid ~#distinct-size compiles
        # where coalesced paid ~#buckets, so the ratio is compile-
        # inflated and must not be read as a steady-state number
        **({"cold_passes": True} if passes == 0 else {}),
        "device_trace": device_trace,
        "ops": n_ops,
        "batch_bytes": total_bytes,
        "per_op_gbps": round(total_bytes / t_per / 1e9, 3),
        "coalesced_gbps": round(total_bytes / t_coal / 1e9, 3),
        "coalesced_vs_per_op": round(t_per / t_coal, 3),
        "dispatch": {
            "batches": stats["totals"]["batches"],
            "ops": stats["totals"]["ops"],
            "pad_stripes": stats["totals"]["pad_stripes"],
            "flush_reasons": stats["totals"]["flush_reasons"],
            "buckets": stats["buckets"],
        },
        "kernel_profile": prof.dump(),
    }


def bench_mesh(deadline: float | None) -> dict:
    """Multi-chip EC scaling (ISSUE 8 / ROADMAP 1): encode and ICI
    all-gather reconstruct GB/s vs chip count through the mesh engine,
    reported as per-chip scaling efficiency — raw speed x scale, the
    paper's headline multiplier.  Also proves the mesh lane's
    anti-compile-storm gate (a 50-way size sweep through the dispatcher
    costs at most #buckets x #mesh-slices compiles) and splits the ICI
    gather cost out of the reconstruct number via the KernelProfiler's
    ``mesh_gather`` engine.

    On a single-device backend the phase still lands (n_devices=1,
    scaling trivially flat) so the round JSON never loses the record;
    the efficiency numbers only mean hardware on a multi-chip slice.
    """
    import asyncio

    import jax

    devs = jax.devices()
    from ceph_tpu.models import registry
    from ceph_tpu.osd import ec_util
    from ceph_tpu.osd.ec_dispatch import (
        ECDispatcher, bucket_stripes_aligned,
    )
    from ceph_tpu.parallel.engine import MeshEcEngine

    prof = _kprof()
    prof.reset()
    codec = registry.instance().factory(
        "isa", {"plugin": "isa", "technique": "reed_sol_van",
                "k": str(K), "m": str(M)},
    )
    chunk = codec.get_chunk_size(OBJECT_SIZE)  # 128 KiB
    sinfo = ec_util.StripeInfo(stripe_width=chunk * K, chunk_size=chunk)
    stripes = 64  # 64 MiB logical per pass, the headline batch
    cpu_like = devs[0].platform == "cpu"
    if cpu_like or (deadline is not None
                    and deadline - time.time() < 90):
        stripes = 8  # 8 MiB: virtual-device hosts measure topology
    rng = np.random.default_rng(11)
    buf = rng.integers(
        0, 256, size=(stripes * sinfo.stripe_width,), dtype=np.uint8
    )
    full = ec_util.encode(sinfo, codec, buf)
    surv = {s: np.asarray(v) for s, v in full.items()
            if s != ERASED[0]}  # single-chunk reconstruct, config 2
    counts = []
    c = 1
    while c <= len(devs):
        counts.append(c)
        c *= 2
    if counts[-1] != len(devs):
        counts.append(len(devs))
    log(f"mesh: {len(devs)} devices, sweep {counts}, "
        f"{buf.size >> 20} MiB batch")
    ms = 0.3
    scaling = []
    eng = None
    t_rec = None
    for n in counts:
        if scaling and deadline is not None \
                and deadline - time.time() < 15:
            log(f"mesh: deadline close, kept {len(scaling)} counts")
            break
        eng = MeshEcEngine(devices=devs[:n])
        pg, shard = eng.mesh_key(K)
        t_enc = bench_loop(lambda: eng.encode(sinfo, codec, buf),
                           min_seconds=ms, deadline=deadline)
        t_rec = bench_loop(
            lambda: eng.decode_concat(sinfo, codec, surv),
            min_seconds=ms, deadline=deadline,
        )
        scaling.append({
            "devices": n, "pg": pg, "shard": shard,
            "encode_gbps": round(buf.size / t_enc / 1e9, 3),
            "reconstruct_gbps": round(buf.size / t_rec / 1e9, 3),
        })
        log(f"mesh: {n} chip(s) (pg={pg} shard={shard}) encode "
            f"{scaling[-1]['encode_gbps']:.2f} reconstruct "
            f"{scaling[-1]['reconstruct_gbps']:.2f} GB/s")
    base, top = scaling[0], scaling[-1]
    n_top = top["devices"]
    enc_eff = (
        top["encode_gbps"] / base["encode_gbps"] / n_top
        if base["encode_gbps"] > 0 else 0.0
    )
    rec_eff = (
        top["reconstruct_gbps"] / base["reconstruct_gbps"] / n_top
        if base["reconstruct_gbps"] > 0 else 0.0
    )
    # ICI-gather cost split: the reconstruct's all-gather ALONE at the
    # top mesh's survivor geometry (profiled as mesh_gather too)
    gather: dict = {}
    try:
        n_dev = len(eng.devices)
        L = stripes * sinfo.chunk_size
        quantum = 4 * n_dev
        L_p = eng._bucket(max(L, quantum), quantum)
        t_gather = bench_loop(lambda: eng.probe_gather(K, L_p),
                              min_seconds=ms, deadline=deadline)
        gather = {
            "seconds": round(t_gather, 6),
            "gbps": round(K * L_p / t_gather / 1e9, 3),
            "share_of_reconstruct": round(t_gather / t_rec, 3)
            if t_rec else None,
        }
    except Exception as e:
        log(f"mesh: gather probe failed: {e!r}")
    # the anti-compile-storm gate ON THE MESH LANE: 50 distinct sizes
    # through the dispatcher cost at most #buckets x #mesh-slices
    # compiles (one codec+geometry here -> one mesh slice)
    storm: dict = {"skipped": True}
    if deadline is None or deadline - time.time() > 20:
        small = ec_util.StripeInfo(stripe_width=64 * K, chunk_size=64)
        sizes = list(range(1, 51))
        small_bufs = [
            rng.integers(0, 256, size=(s * small.stripe_width,),
                         dtype=np.uint8)
            for s in sizes
        ]

        def _mesh_misses() -> int:
            e = prof.dump().get("engines", {}).get("mesh_encode")
            return e["jit_cache"]["misses"] if e else 0

        before = _mesh_misses()
        sweep_eng = eng

        async def _sweep():
            disp = ECDispatcher(window=0.0, max_stripes=1 << 20,
                                mesh_engine=sweep_eng)
            for b in small_bufs:
                await disp.encode(small, codec, b)
            st = disp.dump()
            await disp.stop()
            return st

        st = asyncio.run(_sweep())
        bound = len({
            bucket_stripes_aligned(s, n_top, True) for s in sizes
        })
        compiles = _mesh_misses() - before
        storm = {
            "sizes": len(sizes), "compiles": compiles,
            "bound": bound, "mesh_slices": 1,
            "ok": 0 < compiles <= bound,
            "mesh_buckets": st["mesh_buckets"],
        }
        log(f"mesh: compile storm {compiles} compiles for "
            f"{len(sizes)} sizes (bound {bound})")
    # ISSUE 9: MEASURED ICI share — a trace window over the top mesh's
    # reconstruct, with the all-gather time read from the collective
    # bucket instead of inferred from the probe_gather wall clock.
    # ``ici_share`` gates via bench_regress --metric mesh.ici_share
    # (lower is better: a reconstruct drifting gather-bound fails even
    # when headline GB/s barely moves).
    ici_share = None
    ici_measured = False
    device_trace = {"unavailable": "skipped (deadline close)"}
    # 45s guard: first-window profiler init costs ~15-20s (see
    # bench_device) — worth it for the measured ICI split only when
    # the budget actually has room
    if deadline is None or deadline - time.time() > 45:

        def _mesh_burst():
            for _ in range(3):
                eng.decode_concat(sinfo, codec, surv)

        device_trace, _burst_err = _capture_or_failover(
            _mesh_burst, "mesh-reconstruct"
        )
        rec = device_trace.get("engines", {}).get("mesh_reconstruct")
        src = rec or device_trace.get("buckets")
        if src:
            total = (src.get("fused_op", 0.0) + src.get("dma", 0.0)
                     + src.get("collective", 0.0))
            if total > 0:
                ici_share = round(src["collective"] / total, 4)
                ici_measured = True
    if ici_share is None and gather.get("share_of_reconstruct"):
        # wall-clock inference fallback (the pre-ISSUE-9 number): the
        # metric stays on the trajectory even when tracing degrades
        ici_share = gather["share_of_reconstruct"]
    return {
        "platform": str(devs[0]),
        "n_devices": len(devs),
        "batch_bytes": int(buf.size),
        "codec": f"isa reed_sol_van k{K} m{M}",
        "scaling": scaling,
        "scaling_efficiency": round(enc_eff, 3),
        "reconstruct_scaling_efficiency": round(rec_eff, 3),
        "mesh_vs_single_chip": round(
            top["encode_gbps"] / base["encode_gbps"], 3
        ) if base["encode_gbps"] > 0 else None,
        "encode_gbps": top["encode_gbps"],
        "reconstruct_gbps": top["reconstruct_gbps"],
        **({"gather": gather} if gather else {}),
        **({"ici_share": ici_share,
            "ici_share_measured": ici_measured}
           if ici_share is not None else {}),
        "device_trace": device_trace,
        "compile_storm": storm,
        "kernel_profile": prof.dump(prefix="mesh"),
    }


def bench_accel(deadline: float | None) -> dict:
    """Shared EC accelerator service (ISSUE 10 / ROADMAP 2): N
    simulated OSD feeders shipping coalesced batches to ONE accelerator
    daemon over real loopback messenger connections, vs the same N
    feeders each running a local dispatcher lane.  The shared side's
    win is CROSS-CLIENT coalescing: one device launch carries stripes
    from several OSDs, so device occupancy (stripes per launch /
    threshold) beats what any single feeder's traffic could fill —
    that is the "device count scales with traffic, not daemon count"
    claim, measured.  ``occupancy`` gates via ``bench_regress --metric
    accel.occupancy`` (ratio, threshold 0.8).
    """
    import asyncio

    import jax

    dev = jax.devices()[0]
    from ceph_tpu.accel import AccelClient, AccelDaemon
    from ceph_tpu.models import registry
    from ceph_tpu.msg import AsyncMessenger, Dispatcher
    from ceph_tpu.osd import ec_util
    from ceph_tpu.osd.ec_dispatch import ECDispatcher
    from ceph_tpu.utils import native as _native

    codec = registry.instance().factory(
        "isa", {"plugin": "isa", "technique": "reed_sol_van",
                "k": str(K), "m": str(M)},
    )
    chunk = codec.get_chunk_size(2048 * K)
    sinfo = ec_util.StripeInfo(stripe_width=chunk * K, chunk_size=chunk)
    n_feeders = 4
    ops_per_feeder = 48
    if deadline is not None and deadline - time.time() < 40:
        ops_per_feeder = 12
        log(f"accel: shrinking to {ops_per_feeder} ops/feeder "
            f"(deadline close)")
    rng = np.random.default_rng(23)
    plans = [
        [int(s) for s in rng.integers(1, 17, size=ops_per_feeder)]
        for _ in range(n_feeders)
    ]
    bufs = [
        [rng.integers(0, 256, size=(s * sinfo.stripe_width,),
                      dtype=np.uint8) for s in plan]
        for plan in plans
    ]
    total_bytes = int(sum(b.size for fb in bufs for b in fb))
    # the workload TRICKLES: each feeder keeps only `group` ops in
    # flight at a time (a realistic per-OSD concurrency), so no single
    # feeder's window can fill the device threshold — the occupancy
    # gap the SHARED accelerator closes by stacking feeders' groups
    # into one launch is exactly the claim being measured
    window, max_stripes, group = 0.003, 512, 4
    # the accelerator holds its window open longer than any one feeder
    # would: it amortizes the wait across EVERY client's traffic, so a
    # few ms of extra latency buys multi-client launches (the same
    # trade serving stacks make at the shared-tier batcher)
    accel_window = 0.01
    log(f"accel: {n_feeders} feeders x {ops_per_feeder} ops "
        f"(groups of {group}), {total_bytes >> 20} MiB total")

    async def _drive(submit, fb):
        for i in range(0, len(fb), group):
            await asyncio.gather(*[submit(b) for b in fb[i:i + group]])

    class _Feeder(Dispatcher):
        """One simulated OSD: a messenger + a dispatcher whose remote
        lane points at the shared accelerator."""

        def __init__(self, name: str, addr: str):
            self.messenger = AsyncMessenger(name, self)
            self.client = AccelClient(self.messenger, addr=addr,
                                      mode="require", deadline=60.0)
            self.dispatch = ECDispatcher(window=window,
                                         max_stripes=max_stripes,
                                         remote=self.client)

        async def ms_dispatch(self, conn, msg):
            self.client.handle(msg)

        def ms_handle_reset(self, conn):
            self.client.on_reset(conn)

        async def stop(self):
            await self.dispatch.stop()
            await self.messenger.shutdown()

    def _occ(stats: dict) -> float:
        t = stats["totals"]
        if not t["batches"]:
            return 0.0
        return t["stripes"] / (t["batches"] * max_stripes)

    async def shared_pass():
        from ceph_tpu.common import Config

        acc = AccelDaemon("accel.bench", config=Config(overrides={
            "osd_ec_dispatch_window": accel_window,
            "osd_ec_dispatch_max_stripes": max_stripes,
        }))
        await acc.start()
        feeders = [_Feeder(f"osd.{i}", acc.addr)
                   for i in range(n_feeders)]
        t0 = time.perf_counter()
        await asyncio.gather(*[
            _drive(lambda b, f=f: f.dispatch.encode(sinfo, codec, b),
                   fb)
            for f, fb in zip(feeders, bufs)
        ])
        dt = time.perf_counter() - t0
        stats = acc.dispatch.dump()
        for f in feeders:
            await f.stop()
        await acc.stop()
        return dt, stats

    async def local_pass():
        disps = [ECDispatcher(window=window, max_stripes=max_stripes)
                 for _ in range(n_feeders)]
        t0 = time.perf_counter()
        await asyncio.gather(*[
            _drive(lambda b, d=d: d.encode(sinfo, codec, b), fb)
            for d, fb in zip(disps, bufs)
        ])
        dt = time.perf_counter() - t0
        stats = [d.dump() for d in disps]
        for d in disps:
            await d.stop()
        return dt, stats

    async def fleet_pass():
        """Multi-accel phase (ISSUE 11 / ROADMAP 3): the same trickling
        feeders, SKEWED 4:1:1:1, over a TWO-accel fleet routed by the
        AccelRouter (a synthetic AccelMap — no mon in the bench
        topology) — and one accelerator is crash-killed mid-run.  The
        claims measured: aggregate fleet occupancy holds under feeder
        skew (the router's least-loaded balancing spreads the hot
        feeder), and accel death REBALANCES to the survivor with zero
        failed ops and zero local-fallback replays (inter-accel
        failover, gated via ``bench_regress --metric
        accel.fleet_occupancy``)."""
        from ceph_tpu.accel import AccelMap, AccelRouter
        from ceph_tpu.common import Config

        accs = []
        for i in range(2):
            a = AccelDaemon(f"accel.f{i}", config=Config(overrides={
                "osd_ec_dispatch_window": accel_window,
                "osd_ec_dispatch_max_stripes": max_stripes,
                # a tight capacity so the reply-piggybacked load signal
                # actually moves: with the 256-slot default the hot
                # accel's load ratio stays under the hysteresis margin
                # and the skew never spreads
                "osd_op_queue_slots": 8,
            }))
            await a.start()
            accs.append(a)
        amap = AccelMap()
        for i, a in enumerate(accs):
            amap.note_boot(a.name, a.addr, "", capacity=8)

        class _FleetFeeder(Dispatcher):
            def __init__(self, name: str):
                self.messenger = AsyncMessenger(name, self)
                self.router = AccelRouter(self.messenger, mode="prefer",
                                          deadline=60.0,
                                          retry_interval=0.05)
                self.router.apply_map(amap)
                self.dispatch = ECDispatcher(window=window,
                                             max_stripes=max_stripes,
                                             remote=self.router)

            async def ms_dispatch(self, conn, msg):
                self.router.handle(msg, conn)

            def ms_handle_reset(self, conn):
                self.router.on_reset(conn)

            async def stop(self):
                await self.dispatch.stop()
                await self.messenger.shutdown()

        # 4:1:1:1 feeder skew — feeder 0 is the hot client the router
        # must spread across the fleet
        skew_bufs = [[b for _ in range(4) for b in bufs[0]], *bufs[1:]]
        fleet_bytes = int(sum(b.size for fb in skew_bufs for b in fb))
        feeders = [_FleetFeeder(f"osd.{i}") for i in range(n_feeders)]
        total_ops = sum(len(fb) for fb in skew_bufs)
        done_ops = 0
        killed = asyncio.Event()
        victim: list[int] = []
        errors = 0

        async def _drive_counted(f, fb):
            nonlocal done_ops, errors
            for i in range(0, len(fb), group):
                outs = await asyncio.gather(*[
                    f.dispatch.encode(sinfo, codec, b)
                    for b in fb[i:i + group]
                ], return_exceptions=True)
                errors += sum(1 for o in outs if isinstance(o, Exception))
                done_ops += len(outs)
                if done_ops >= total_ops // 2 and not killed.is_set():
                    killed.set()
                    # SIGKILL the BUSIER accel mid-run: its in-flight
                    # batches must hop to the survivor (the rebalance
                    # claim), not just quietly lose an idle standby
                    busy = max(
                        range(len(accs)),
                        key=lambda i: accs[i].dispatch._totals["batches"],
                    )
                    victim.append(busy)
                    await accs[busy].stop(crash=True)

        t0 = time.perf_counter()
        await asyncio.gather(*[
            _drive_counted(f, fb) for f, fb in zip(feeders, skew_bufs)
        ])
        dt = time.perf_counter() - t0
        stats = [a.dispatch.dump() for a in accs]
        failover_next = sum(
            f.router.totals["failover_next"] for f in feeders
        )
        local_replays = sum(
            f.dispatch.dump()["totals"]["failovers"] for f in feeders
        )
        for f in feeders:
            await f.stop()
        for i, a in enumerate(accs):
            if i not in victim:
                await a.stop()
        batches = sum(s["totals"]["batches"] for s in stats)
        stripes = sum(s["totals"]["stripes"] for s in stats)
        return {
            "accels": len(accs),
            "feeder_skew": "4:1:1:1",
            "ops": total_ops,
            "batch_bytes": fleet_bytes,
            "gbps": round(fleet_bytes / dt / 1e9, 3),
            # aggregate device occupancy across the FLEET: stripes per
            # launch / threshold, summed over every accel's dispatcher
            "fleet_occupancy": round(
                stripes / (batches * max_stripes), 4
            ) if batches else 0.0,
            "per_accel_batches": [s["totals"]["batches"] for s in stats],
            # rebalance-on-accel-death evidence: the mid-run SIGKILL's
            # in-flight batches hopped to the survivor (no client op
            # failed, no local-fallback replay)
            "killed_mid_run": killed.is_set(),
            "rebalanced_batches": failover_next,
            "local_fallback_replays": local_replays,
            "failed_ops": errors,
        }

    # the JAX batch path is the engine being shared (the native C lane
    # routes per-op by design and has nothing to amortize) — same
    # override discipline as bench_smallops, try/finally scoped
    _native.host_engine_active()
    saved_host_active = _native._HOST_ACTIVE
    fleet = None
    try:
        _native._HOST_ACTIVE = False
        t_shared, acc_stats = asyncio.run(shared_pass())
        t_local, local_stats = asyncio.run(local_pass())
        if deadline is None or deadline - time.time() > 25:
            # the multi-accel phase (ISSUE 11): skipped only under a
            # tight deadline — the single-accel occupancy above is the
            # PR-10 gate and must always land
            fleet = asyncio.run(fleet_pass())
            log(f"accel fleet: occupancy {fleet['fleet_occupancy']} "
                f"over {fleet['accels']} accels, "
                f"{fleet['rebalanced_batches']} batches rebalanced on "
                f"death, {fleet['failed_ops']} failed ops")
        else:
            log("accel: skipping the fleet phase (deadline close)")
    finally:
        _native._HOST_ACTIVE = saved_host_active
    occupancy = round(_occ(acc_stats), 4)
    local_best = round(max((_occ(s) for s in local_stats),
                           default=0.0), 4)
    t = acc_stats["totals"]
    batches = t["batches"] or 1
    return {
        "platform": str(dev),
        "feeders": n_feeders,
        "ops": n_feeders * ops_per_feeder,
        "batch_bytes": total_bytes,
        "gbps_shared": round(total_bytes / t_shared / 1e9, 3),
        "gbps_local": round(total_bytes / t_local / 1e9, 3),
        # shared-device occupancy: stripes per launch / threshold, at
        # the ACCELERATOR's dispatcher (the one device everyone shares)
        "occupancy": occupancy,
        "occupancy_local_best": local_best,
        "shared_vs_best_local": round(
            occupancy / local_best, 3) if local_best else None,
        # cross-client coalescing rate: launches carrying >1 OSD's ops
        "cross_client_rate": round(
            t.get("cross_client_batches", 0) / batches, 4),
        "coalesce_ops_per_batch": round(t["ops"] / batches, 3),
        # the multi-accel fleet phase (ISSUE 11): aggregate occupancy
        # under 4:1:1:1 feeder skew + rebalance-on-accel-death; the
        # top-level key feeds bench_regress --metric
        # accel.fleet_occupancy (absent under a tight deadline — the
        # gate skips cleanly until two rounds carry it)
        **({"fleet": fleet,
            "fleet_occupancy": fleet["fleet_occupancy"]}
           if fleet is not None else {}),
        "dispatch": {
            "batches": t["batches"], "ops": t["ops"],
            "stripes": t["stripes"],
            "cross_client_batches": t.get("cross_client_batches", 0),
            "flush_reasons": acc_stats["totals"]["flush_reasons"],
            "buckets": acc_stats["buckets"],
        },
    }


def bench_qos(deadline: float | None = None) -> dict:
    """QoS starvation gate: client op wait p50/p99 through the OSD's
    dmClock scheduler under a saturating synthetic recovery storm —
    scheduler on (``osd_op_queue=mclock``) vs off (``fifo``), same
    storm both times.

    The harness drives ``ceph_tpu.osd.scheduler.OpScheduler`` directly
    (pure asyncio, no device): one service slot with a fixed per-grant
    service time models the saturated device, a 4:1 pre-queued
    background storm models recovery, and clients arrive paced while
    the storm drains.  ``protection`` is fifo-p99 / mclock-p99 — the
    factor the scheduler buys on tail latency when the cluster is
    degraded; it rides the BENCH_* trajectory and is gateable via
    ``tools/bench_regress.py --metric qos.protection``.
    """
    import asyncio

    from ceph_tpu.osd.client_ledger import ClientLedger
    from ceph_tpu.osd.scheduler import OpScheduler, QosSpec

    service_s = 0.002     # per-grant device time (slots=1 -> 500/s)
    n_client = 60
    storm = 4 * n_client  # the 4:1 background:client storm
    arrival_s = 0.003     # client inter-arrival (demand ~333/s > res)
    # synthetic tenants with a 2:1:1 skew — the per-tenant breakdown
    # below comes from the REAL ledger aggregator (ISSUE 16), so the
    # bench exercises the same top-K/p99 path the OSD op path feeds
    tenant_cycle = (101, 101, 202, 303)

    async def run_policy(policy: str) -> dict:
        sched = OpScheduler(
            {
                "client": QosSpec(reservation=100.0, weight=4.0),
                "recovery": QosSpec(reservation=10.0, weight=1.0),
            },
            policy=policy, slots=1, cut_off=10_000,
        )
        waits: list[float] = []
        ledger = ClientLedger(topk=8, window=60.0)

        async def one(klass: str, tenant: int = 0) -> None:
            t0 = time.perf_counter()
            async with sched.grant(klass):
                if klass == "client":
                    wait = time.perf_counter() - t0
                    waits.append(wait)
                    ledger.account(tenant, 0, "client", lat=wait)
                await asyncio.sleep(service_s)

        bg = [asyncio.ensure_future(one("recovery")) for _ in range(storm)]
        await asyncio.sleep(0)  # the storm queues FIRST — worst case
        cl = []
        for i in range(n_client):
            cl.append(asyncio.ensure_future(
                one("client", tenant_cycle[i % len(tenant_cycle)])
            ))
            await asyncio.sleep(arrival_s)
        await asyncio.gather(*cl)
        share = sched.share_attainment("client")
        for t in bg:  # storm drained enough; stop burning wall clock
            t.cancel()
        await asyncio.gather(*bg, return_exceptions=True)
        ws = sorted(waits)
        total = sum(r["ops"] for r in ledger.series())
        return {
            "p50_ms": round(ws[len(ws) // 2] * 1e3, 3),
            "p99_ms": round(
                ws[min(len(ws) - 1, int(len(ws) * 0.99))] * 1e3, 3
            ),
            "max_ms": round(ws[-1] * 1e3, 3),
            "share_attainment": (
                round(share, 3) if share is not None else None
            ),
            "tenants": {
                str(r["client"]): {
                    "ops": r["ops"],
                    "share": round(r["ops"] / total, 3) if total else 0.0,
                    "wait_p99_ms": round(r["p99_s"] * 1e3, 3),
                }
                for r in ledger.series() if r["class"] != "other"
            },
        }

    mclock = asyncio.run(run_policy("mclock"))
    fifo = asyncio.run(run_policy("fifo"))
    return {
        "storm": {"background": storm, "clients": n_client,
                  "service_ms": service_s * 1e3, "slots": 1},
        "mclock": mclock,
        "fifo": fifo,
        "protection": round(
            fifo["p99_ms"] / max(mclock["p99_ms"], 1e-3), 3
        ),
    }


def bench_churn(deadline: float | None = None) -> dict:
    """Live churn storm (ISSUE 15 layer 3): a REAL MiniCluster EC pool
    rides one OSD kill/rejoin cycle under sustained client load, once
    per scheduler policy.  Reports, per policy, the client p99 during
    the storm vs quiescent; the headline ``protection`` is
    fifo-storm-p99 / mclock-storm-p99 — how much client tail latency
    the dmClock classes buy while REAL recovery (peering scans, EC
    rebuild decodes/encodes under klass=recovery, pushes) competes for
    the same OSDs — and ``recovery_gbps``, the bytes the primaries
    re-pushed over the recovery wall.  Both gate the trajectory via
    ``bench_regress --metric churn.protection`` /
    ``--metric churn.recovery_gbps`` (clean-skip until two rounds
    carry them).  Unlike bench_qos (a synthetic scheduler harness),
    this is the whole storm path end to end; the invariants (zero
    failed client ops, zero lost acked writes) are asserted, not just
    measured."""
    import asyncio

    from ceph_tpu.rados.cluster import MiniCluster
    from ceph_tpu.rados.storm import ClientLoad, StormDriver

    seed_objects = 64
    seed_bytes = 64 * 1024
    payload = np.random.default_rng(23).integers(
        0, 256, size=seed_bytes, dtype=np.uint8
    ).tobytes()

    async def run_policy(policy: str) -> dict:
        async with MiniCluster(
            n_osds=4,
            # a small grant pool makes ADMISSION the contended resource
            # (the accel fleet phase's trick): recovery pushes and
            # client ops compete for the same slots, so the measured
            # difference is the scheduler's policy, not loopback noise
            config_overrides={"osd_op_queue": policy,
                              "osd_op_queue_slots": 4},
        ) as c:
            # two NAMED tenants (stable blake2b session ids): the storm
            # load splits across them so the OSD ledgers have a real
            # multi-tenant breakdown to report (ISSUE 16)
            cl = await c.client(name="bench.tenant_a")
            cl2 = await c.client(name="bench.tenant_b")
            await cl.create_pool("churn", "erasure", pg_num=8)
            io = cl.io_ctx("churn")
            io2 = cl2.io_ctx("churn")
            for i in range(seed_objects):  # the dataset recovery moves
                await io.write_full(f"seed{i}", payload)

            # quiescent client p99 (same load shape as the storm; a
            # p99 needs hundreds of samples or it degenerates to the
            # max of a handful)
            quiet = ClientLoad(io, prefix="q", objects=8, size=4096,
                               pause=0.002)
            quiet.start(writers=4)
            await asyncio.sleep(2.0)
            await quiet.stop()
            if quiet.failed:
                raise RuntimeError(f"quiescent ops failed: {quiet.failed[:3]}")

            # same 4 concurrent writers as before (comparable p99
            # series), split 2+2 across the two tenants
            load = ClientLoad(io, prefix="s", objects=8, size=4096,
                              pause=0.002)
            load.start(writers=2)
            load2 = ClientLoad(io2, prefix="t", objects=8, size=4096,
                               pause=0.002)
            load2.start(writers=2)
            driver = StormDriver(c, cl, ["churn"])

            def pushed() -> int:
                return sum(
                    o.perf.get("recovery").get("bytes_pushed")
                    for o in c.osds.values()
                )

            victim = sorted(c.osds)[-1]
            bytes0 = pushed()
            t0 = time.perf_counter()
            await c.kill_osd(victim)
            await c.wait_for_osd_down(victim)
            await asyncio.sleep(0.5)  # degraded-window writes pile up
            # disk replacement: the victim rejoins EMPTY, so recovery
            # backfills its whole shard set — real recovery volume,
            # not just the degraded-window delta
            from ceph_tpu.store import MemStore

            c.stores[victim] = MemStore()
            await c.restart_osd(victim)
            await c.wait_for_osd_up(victim)
            await driver.settle(timeout=45.0)
            recovery_wall = time.perf_counter() - t0
            moved = pushed() - bytes0
            # tenant breakdown BEFORE the loads stop: the ledger is a
            # sliding window, so read it while the storm is in-window
            tenants: dict[str, dict] = {}
            tenant_total = 0
            for o in c.osds.values():
                for row in o.client_ledger.series():
                    tenant_total += row["ops"]
                    if row["class"] == "other":
                        continue
                    t = tenants.setdefault(str(row["client"]), {
                        "ops": 0, "errs": 0, "p99_ms": 0.0,
                    })
                    t["ops"] += row["ops"]
                    t["errs"] += row["errs"]
                    t["p99_ms"] = max(
                        t["p99_ms"], round(row["p99_s"] * 1e3, 3)
                    )
            for t in tenants.values():
                t["share"] = round(t["ops"] / tenant_total, 3) \
                    if tenant_total else 0.0
            await load.stop()
            await load2.stop()
            failed = load.failed + load2.failed
            if failed:
                raise RuntimeError(f"storm ops failed: {failed[:3]}")
            lost = (await load.verify()) + (await load2.verify())
            if lost:
                raise RuntimeError(f"lost acked writes: {lost[:3]}")
            lat = sorted(load.latencies + load2.latencies)
            storm_p99 = round(
                lat[min(len(lat) - 1, int(len(lat) * 0.99))] * 1e3, 3
            ) if lat else 0.0
            return {
                "storm_p99_ms": storm_p99,
                "quiet_p99_ms": quiet.p99_ms(),
                "ops": len(lat),
                "recovery_bytes": moved,
                "recovery_wall_s": round(recovery_wall, 3),
                "tenants": dict(sorted(
                    tenants.items(), key=lambda kv: -kv[1]["ops"]
                )),
            }

    def _degradation(r: dict) -> float:
        # each policy's own storm-vs-quiescent tail blowup: normalizing
        # inside one cluster run cancels process-warmup drift between
        # the two runs (the first run pays every jit compile)
        return r["storm_p99_ms"] / max(r["quiet_p99_ms"], 1e-3)

    # best-of-2 policy pairs (the headline's best-of discipline): a
    # loopback p99 on a contended host is noisy, and a one-shot
    # protection factor would flap the bench_regress gate
    attempts = []
    mclock = fifo = None
    for _try in range(2):
        m = asyncio.run(run_policy("mclock"))
        if deadline is not None and deadline - time.time() < 30:
            if mclock is None:
                mclock, fifo = m, {"skipped": "deadline close"}
            break
        f = asyncio.run(run_policy("fifo"))
        prot = round(_degradation(f) / max(_degradation(m), 1e-3), 3)
        attempts.append(prot)
        if mclock is None or prot >= max(attempts[:-1], default=0.0):
            mclock, fifo = m, f
        if deadline is not None and deadline - time.time() < 30:
            break
    out = {
        "seed_objects": seed_objects,
        "seed_bytes": seed_bytes,
        "mclock": mclock,
        "fifo": fifo,
        # recovery throughput from the FIFO run when it exists:
        # under mclock the whole point is that recovery gets SQUEEZED
        # behind the client reservation, so its wall measures the
        # squeeze, not the recovery path's capability
        "recovery_gbps": round(
            (fifo if "recovery_bytes" in fifo else mclock)
            ["recovery_bytes"]
            / max((fifo if "recovery_wall_s" in fifo else mclock)
                  ["recovery_wall_s"], 1e-6) / 1e9, 6,
        ),
        "degradation": round(_degradation(mclock), 3),
    }
    if attempts:
        # >= 1.0 means the dmClock classes held client p99 through the
        # storm at least as well as fifo did (the ISSUE acceptance)
        out["protection"] = max(attempts)
        out["protection_attempts"] = attempts
    return out


# -- parent orchestration ----------------------------------------------------

_BEST: dict | None = None
_CHILDREN: list = []  # live Popen handles, killed from the signal handler

METRIC = "RS(8,3) 1MiB-stripe encode+reconstruct throughput (TPU)"
NATIVE_METRIC = ("RS(8,3) 1MiB-stripe encode+reconstruct throughput "
                 "(native host C++, 1 thread)")


def emit(result: dict) -> None:
    global _BEST
    _BEST = result
    print(json.dumps(result), flush=True)


def _sig_handler(signum, frame):
    log(f"signal {signum}: emitting best-so-far and exiting")
    for proc in list(_CHILDREN):  # never leave a child holding the chip
        _kill_child(proc)
    if _BEST is not None:
        print(json.dumps(_BEST), flush=True)
    sys.exit(0 if _BEST and _BEST.get("value") is not None else 1)


def _kill_child(proc) -> None:
    """SIGKILL the child's whole process group: a child that outlives
    the parent keeps holding the chip."""
    try:
        os.killpg(proc.pid, signal.SIGKILL)
    except (ProcessLookupError, PermissionError):
        pass
    try:
        proc.wait(timeout=5)
    except Exception:
        pass


def run_device_child(args, timeout: float, on_result) -> tuple[dict, str]:
    """The ONE process that holds the chip: every device phase runs in
    it, streaming a tagged JSON line per finished phase (so a later
    hang still leaves the earlier ones).  Returns ({kind: result},
    outcome); the outcome is "ok" or why the child produced nothing
    more."""
    import threading

    cmd = [sys.executable, os.path.abspath(__file__), "--_child",
           "--batch", str(args.batch), "--_deadline",
           str(time.time() + timeout - 5)]
    if not args.full:
        cmd.append("--quick")
    log(f"device child: starting (timeout {timeout:.0f}s)")
    proc = subprocess.Popen(
        cmd, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        env=_CHILD_ENV,
        start_new_session=True,  # own pgid so _kill_child gets the tree
    )
    _CHILDREN.append(proc)
    results: dict[str, dict] = {}

    def _drain_err():
        for line in proc.stderr:
            log(f"  {line.rstrip()}")

    def _drain_out():
        for line in proc.stdout:
            try:
                obj = json.loads(line)
            except json.JSONDecodeError:
                continue
            kind = obj.pop("kind", None)
            if kind:
                results[kind] = obj
                log(f"device child: phase '{kind}' answered")
                on_result(kind, obj)

    threads = [threading.Thread(target=_drain_err, daemon=True),
               threading.Thread(target=_drain_out, daemon=True)]
    for t in threads:
        t.start()
    t_start = time.time()
    try:
        proc.wait(timeout=timeout)
    except subprocess.TimeoutExpired:
        _kill_child(proc)
    for t in threads:
        t.join(timeout=3)
    _CHILDREN.remove(proc)
    rc = proc.returncode
    if "no_device" in results:
        outcome = f"no TPU: {results['no_device'].get('error')}"
    elif rc is None or rc == -signal.SIGKILL:
        outcome = f"device child timed out after {timeout:.0f}s"
    elif rc != 0:
        outcome = f"device child died rc={rc}"
    else:
        outcome = "ok"
    _phase_note("device", outcome, time.time() - t_start,
                kept=sorted(results))
    return results, outcome


_DEVICE_DEATH_ARMED = (
    os.environ.get("CEPH_TPU_BENCH_FAULT") == "device-death"
)


def _maybe_inject_device_death(engine: str) -> None:
    """Test hook for device loss after acquisition: with
    CEPH_TPU_BENCH_FAULT=device-death the FIRST engine measurement in
    the process raises a fabricated device-lost error; the headline
    race drops that engine with an engine_failover verdict."""
    global _DEVICE_DEATH_ARMED
    if _DEVICE_DEATH_ARMED:
        _DEVICE_DEATH_ARMED = False  # one-shot: the next engine must run
        from ceph_tpu.models.matrix_codec import EngineFault

        raise EngineFault(
            f"INTERNAL: Device lost (injected CEPH_TPU_BENCH_FAULT "
            f"mid-{engine})"
        )


def _maybe_inject_fault() -> None:
    """Test hook: with CEPH_TPU_BENCH_FAULT=backend-death the device
    child dies in backend start-up, before any result line."""
    if os.environ.get("CEPH_TPU_BENCH_FAULT") == "backend-death":
        print(
            'Fatal Python error: Aborted (injected CEPH_TPU_BENCH_FAULT)\n'
            '  File "jax/_src/xla_bridge.py", line 824 in backends',
            file=sys.stderr, flush=True,
        )
        os.abort()


def child_main(args) -> None:
    """The device process: acquire the chip once, refuse anything but a
    TPU, then run every device phase, one tagged JSON line each."""
    _maybe_inject_fault()  # dies HERE, like a backend start-up crash
    from ceph_tpu.utils.arch import configure_compile_cache

    import jax

    log(f"device child: compile cache {configure_compile_cache()}")
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        print(json.dumps({"kind": "no_device",
                          "error": f"jax found {dev.platform!r}"}),
              flush=True)
        sys.exit(3)
    print(json.dumps({"kind": "device", "platform": dev.platform,
                      "device_kind": dev.device_kind,
                      "count": len(devs)}), flush=True)
    deadline = args._deadline or (time.time() + 600)

    def sub_deadline(frac: float) -> float:
        return min(time.time() + frac * (deadline - time.time()), deadline)

    phases = (
        ("headline", 20, lambda: bench_device(
            args.batch, args.quick, sub_deadline(0.45))),
        ("smallops", 25, lambda: bench_smallops(sub_deadline(0.5))),
        ("mesh", 25, lambda: bench_mesh(sub_deadline(0.6))),
        ("accel", 25, lambda: bench_accel(sub_deadline(0.65))),
        ("grid", 30, lambda: bench_grid(args.quick, sub_deadline(0.75))),
        ("crush", 15, lambda: bench_crush(sub_deadline(0.85))),
        ("churn", 90, lambda: bench_churn(deadline=sub_deadline(0.95))),
        ("stack", 20, lambda: _bench_stack(deadline)),
    )
    for kind, need_s, fn in phases:
        if deadline - time.time() < need_s:
            log(f"device child: {kind} skipped (budget)")
            continue
        try:
            res = fn()
        except Exception as e:
            log(f"device child: {kind} failed: {e!r}")
            verdicts = getattr(e, "engine_failovers", None)
            if verdicts:  # every engine died mid-headline
                print(json.dumps({"kind": "engine_failover",
                                  "failovers": verdicts}), flush=True)
            continue
        print(json.dumps({"kind": kind, **res}), flush=True)


def _bench_stack(deadline: float) -> dict:
    """The codec stack on the device: ec_util's batched encode, the
    whole-stack zero-copy round trip, and the raw kernel on the same
    device for the ratio."""
    from ceph_tpu.ops.gf_jax import bytes_to_u32, make_gf_matmul_u32

    _kprof().reset()
    res = {"stack_gbps": _bench_codec_stack(deadline)}
    res["stack_e2e"] = _bench_stack_e2e(deadline)
    P, _, _ = _matrices()
    d8 = np.random.default_rng(2).integers(0, 256, size=(K, 1 << 21),
                                           dtype=np.uint8)
    t = _measure_rate("stack-raw", make_gf_matmul_u32(P, W),
                      bytes_to_u32(d8), d8.size, True, deadline)
    res["raw_gbps"] = round(d8.size / t / 1e9, 3)
    res["stack_vs_raw"] = round(res["stack_gbps"] / res["raw_gbps"], 3)
    res["kernel_profile"] = _kprof().dump()
    return res


def result_line(dev: dict, cpu: dict) -> dict:
    return {
        "metric": METRIC,
        "value": round(dev["combined_gbps"], 3),
        "unit": "GB/s",
        "vs_baseline": round(dev["combined_gbps"] / cpu["combined_gbps"], 3),
        "phase": "tpu",
        "encode_gbps": round(dev["encode_gbps"], 3),
        "reconstruct_gbps": round(dev["reconstruct_gbps"], 3),
        "native_cpu_gbps": round(cpu["combined_gbps"], 3),
        **{key: dev[key] for key in (
            "platform", "batch_bytes", "engine", "engines",
            "engine_failover", "kernel_profile",
        ) if key in dev},
    }


def no_result_line(reason: str, cpu: dict | None,
                   verdicts: list | None = None) -> dict:
    """The final line of a run that measured nothing on a chip: the
    device metric is not measured, and no host number stands in."""
    return {
        "metric": METRIC, "value": None, "unit": "GB/s",
        "phase": "no-device", "error": reason,
        **({"native_cpu_gbps": round(cpu["combined_gbps"], 3)}
           if cpu else {}),
        **({"engine_failover": verdicts} if verdicts else {}),
    }


# round-JSON keys each device phase contributes (the rest of a phase's
# record stays in its own streamed line)
_PHASE_KEYS = {
    "smallops": (
        "platform", "ops", "batch_bytes", "per_op_gbps", "coalesced_gbps",
        "coalesced_vs_per_op", "dispatch", "device_trace", "waterfall",
        "header_share", "ops_per_sec", "op_p99_ms", "trace_overhead_share",
        "proc",
    ),
    "accel": (
        "platform", "feeders", "ops", "batch_bytes", "gbps_shared",
        "gbps_local", "occupancy", "occupancy_local_best",
        "shared_vs_best_local", "cross_client_rate",
        "coalesce_ops_per_batch", "dispatch", "fleet", "fleet_occupancy",
    ),
    "mesh": (
        "platform", "n_devices", "batch_bytes", "codec", "scaling",
        "scaling_efficiency", "reconstruct_scaling_efficiency",
        "mesh_vs_single_chip", "encode_gbps", "reconstruct_gbps",
        "gather", "ici_share", "ici_share_measured", "device_trace",
        "compile_storm",
    ),
}


def assemble(dev_results: dict, cpu: dict, mc: dict | None,
             qos_res: dict, outcome: str) -> dict:
    """The final line: the TPU headline and every device phase's record,
    or — when no headline was measured on a chip — the error line."""
    head = dev_results.get("headline")
    if head is None:
        verdicts = dev_results.get("engine_failover", {}).get("failovers")
        reason = outcome if outcome != "ok" else (
            "every device engine died mid-headline" if verdicts
            else "the headline phase produced no result")
        final = no_result_line(reason, cpu, verdicts)
    else:
        final = result_line(head, cpu)
        final["device"] = dev_results.get("device")
        if mc is not None:
            final["native_multicore_gbps"] = round(mc["combined_gbps"], 3)
            final["multicore_workers"] = mc["workers"]
            final["vs_multicore"] = round(
                final["value"] / mc["combined_gbps"], 3)
        for kind, keys in _PHASE_KEYS.items():
            r = dev_results.get(kind)
            if r:
                final[kind] = {k: r[k] for k in keys if k in r}
        if dev_results.get("grid", {}).get("configs"):
            final["configs"] = dev_results["grid"]["configs"]
        if dev_results.get("crush"):
            final["crush_1m"] = dev_results["crush"]
        if dev_results.get("churn"):
            final["churn"] = dev_results["churn"]
        stack = dev_results.get("stack", {})
        for key in ("stack_gbps", "raw_gbps", "stack_vs_raw", "stack_e2e"):
            if key in stack:
                final[key] = stack[key]
    if qos_res:
        final["qos"] = qos_res
    final["phases"] = list(_PHASES)
    return final


_CHILD_ENV = dict(os.environ)  # as the caller set it, before main() pins


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--budget", type=float,
                    default=float(os.environ.get("BENCH_BUDGET", 420)),
                    help="total wall-clock budget in seconds")
    ap.add_argument("--batch", type=int, default=BATCH_OBJECTS)
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--full", action="store_true", help="longer timing loops")
    ap.add_argument("--_child", action="store_true", help=argparse.SUPPRESS)
    ap.add_argument("--_deadline", type=float, default=None, help=argparse.SUPPRESS)
    args = ap.parse_args()

    if args._child:
        child_main(args)
        return
    # the device child gets the caller's environment; the parent itself
    # is pinned to the CPU, so nothing it imports can take the chip
    os.environ["JAX_PLATFORMS"] = "cpu"

    signal.signal(signal.SIGTERM, _sig_handler)
    signal.signal(signal.SIGALRM, _sig_handler)
    signal.alarm(max(int(args.budget), 30))
    t_end = time.time() + args.budget
    quick = not args.full

    log("phase native: single-thread C++ baseline")
    t0_nat = time.time()
    cpu = bench_native(quick=quick)
    _phase_note("native", "ok", time.time() - t0_nat)
    log(f"phase native: encode {cpu['encode_gbps']:.2f} "
        f"reconstruct {cpu['reconstruct_gbps']:.2f} GB/s")
    # the host baseline under its own name, never the device metric
    print(json.dumps({
        "metric": NATIVE_METRIC, "unit": "GB/s", "phase": "native",
        "value": round(cpu["combined_gbps"], 3),
        "encode_gbps": round(cpu["encode_gbps"], 3),
        "reconstruct_gbps": round(cpu["reconstruct_gbps"], 3),
    }), flush=True)

    # the all-cores host baseline (the BASELINE.md north star is ISA-L
    # on a many-core host, not one thread)
    mc: dict | None = None
    t0_mc = time.time()
    try:
        mc = bench_native_multicore(quick=quick)
        _phase_note("native-mc", "ok", time.time() - t0_mc)
        log(f"phase native-mc: {mc['workers']} workers, combined "
            f"{mc['combined_gbps']:.2f} GB/s")
    except Exception as e:
        _phase_note("native-mc", f"failed: {e!r:.120}", time.time() - t0_mc)
        log(f"phase native-mc failed: {e!r}")

    # the QoS starvation gate (PR 5): pure asyncio, no device
    qos_res: dict = {}
    t0_qos = time.time()
    try:
        qos_res = bench_qos()
        _phase_note("qos", "ok", time.time() - t0_qos)
        log(f"phase qos: mclock p99 {qos_res['mclock']['p99_ms']}ms "
            f"vs fifo p99 {qos_res['fifo']['p99_ms']}ms "
            f"(protection {qos_res['protection']}x)")
    except Exception as e:
        _phase_note("qos", f"failed: {e!r:.120}", time.time() - t0_qos)
        log(f"phase qos failed: {e!r}")

    dev_results: dict[str, dict] = {}

    def on_result(kind: str, obj: dict) -> None:
        dev_results[kind] = obj
        if "headline" in dev_results:
            emit(assemble(dev_results, cpu, mc, qos_res, "ok"))

    dev_results, outcome = run_device_child(
        args, max(30.0, t_end - time.time() - 10), on_result)
    final = assemble(dev_results, cpu, mc, qos_res, outcome)
    emit(final)
    log("done")
    sys.exit(0 if final["value"] is not None else 1)


if __name__ == "__main__":
    main()
