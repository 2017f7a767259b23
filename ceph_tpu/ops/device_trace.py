"""Inside-the-kernel device tracing: jax.profiler trace windows, the
per-op/DMA/ICI breakdown, and the device-launch flight recorder.

ROADMAP item 5a: every layer *around* a device launch is timed (PR 1-3
counters/histograms, PR 4/8 per-lane dispatch stats) but nothing could
see *inside* one XLA/Mosaic program — "is ``mesh_reconstruct``
gather-bound or rebuild-bound?" was answered by wall-clock inference.
This module is the missing layer, in three pieces:

- :class:`DeviceTracer` — an on-demand **trace window** service that
  wraps ``jax.profiler.start_trace``/``stop_trace`` around whatever the
  process is launching (dispatcher batches included: the profiler
  session is process-wide, worker threads and all), parses the captured
  trace-event JSON (the ``*.trace.json.gz`` the XPlane exporter writes)
  into per-engine **fused-op / DMA-infeed / ICI-collective** buckets,
  and merges the result into the :class:`~ceph_tpu.ops.profiler.
  KernelProfiler` entries under the same engine names.  Attribution
  works by time overlap: while a window is open, every profiler-tapped
  kernel call reports its (engine, jit-signature, wall interval), and
  each captured HLO-op event lands in the engine whose launch interval
  contains it — the Dapper lesson (Sigelman et al., 2010) applied one
  layer further down, and the component-level visibility "The Tail at
  Scale" (Dean & Barroso, 2013) argues tail debugging needs.
- :class:`FlightRecorder` — a bounded ring of the last N device
  launches (lane, batch key, QoS class, queue-wait vs device wall,
  trace id of the slowest member op), fed by the EC dispatcher and
  consulted by the SLOW_OPS dump path so a slow op's record names the
  launch that carried it.
- the parse/classify helpers — pure functions over trace-event dicts,
  pinned by a checked-in fixture so the bucket rules cannot drift
  silently with a jax upgrade.

Degradation contract: no jax.profiler, a backend that cannot profile, a
parse failure, or a second concurrent ``start`` all return a structured
``{"unavailable": reason}`` (or ``{"error": ...}``) — never an
exception into the admin socket or the data path.  Windows are bounded
(``max_duration`` clamps the requested duration and an expiry check on
every service entry point closes an abandoned window), and the whole
feature is off-cost when no window is open: the profiler's per-call tap
is one attribute read, and jax is only imported when a window opens.

Like :mod:`ceph_tpu.ops.profiler` this module is import-light (no jax
at import time) so admin sockets and tools can serve its state without
initializing a backend.
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import shutil
import tempfile
import threading
import time
from collections import deque
from typing import Any, Hashable, Iterable

BUCKETS = ("fused_op", "dma", "collective")

# ICI/NCCL-collective HLO names (all-gather.1, all-reduce-start,
# reduce-scatter.3, collective-permute...), hyphenated and in the
# underscore spelling the installed jax's profiler writes
# (all_gather.3).  "reduce-window" / "reduce.8" are plain compute and
# must NOT match.
_COLLECTIVE_MARKS = tuple(
    spelling
    for mark in ("all-gather", "all-reduce", "reduce-scatter",
                 "all-to-all", "collective-permute",
                 "collective-broadcast", "ragged-all-to-all")
    for spelling in (mark, mark.replace("-", "_"))
) + ("allgather", "allreduce")
# DMA / host<->device transfer names: HLO copy ops, infeed/outfeed,
# TPU DMA rows, PJRT transfer events
_DMA_MARKS = (
    "infeed", "outfeed", "dma", "memcpy", "copy-start", "copy-done",
    "copy_start", "copy_done",
    "host-to-device", "device-to-host", "h2d", "d2h", "transferto",
    "transferfrom", "buffertransfer",
)
# thread/row names that mark every event on them as DMA (TPU traces put
# DMA engines on their own rows without per-event hlo args)
_DMA_THREAD_MARKS = ("dma", "infeed", "outfeed", "transfer")


def classify_trace_event(name: str, args: dict | None = None,
                         thread_name: str = "") -> str | None:
    """Bucket one trace event: ``"collective"`` / ``"dma"`` /
    ``"fused_op"`` for device-op events, None for runtime/python noise
    (``TfrtCpuExecutable::Execute``, ``$profiler.py ...`` frames) that
    would double-count the ops running beneath it."""
    low = (name or "").lower()
    tlow = (thread_name or "").lower()
    hlo = bool(args) and bool(
        args.get("hlo_op") or args.get("hlo_module")
    )
    if low.startswith("$"):
        return None  # python stack frames the profiler interleaves
    collective = any(m in low for m in _COLLECTIVE_MARKS)
    # a TPU device's "XLA Ops" row holds exactly one event per HLO op,
    # whatever args this profiler version attaches ("XLA Modules" spans
    # wrap them and stay uncounted)
    if hlo or tlow == "xla ops":
        # HLO send/recv ARE cross-chip transfers; a host runtime event
        # merely containing "send" (MessageSend...) must not be
        if collective or low.startswith(("send", "recv")):
            return "collective"
        if any(m in low for m in _DMA_MARKS) or low.startswith("copy"):
            return "dma"
        return "fused_op"
    # no hlo args: only device-row signals count — runtime scaffolding
    # (Execute/Await/ThreadpoolListener) wraps the ops counted above
    if any(m in tlow for m in _DMA_THREAD_MARKS):
        return "dma"
    if collective:
        return "collective"
    if any(m in low for m in _DMA_MARKS):
        return "dma"
    return None


def parse_trace_dir(log_dir: str) -> tuple[list[dict], dict]:
    """Load every ``*.trace.json[.gz]`` under a jax.profiler log dir
    (``plugins/profile/<run>/<host>.trace.json.gz``); returns
    ``(events, thread_names)`` where ``thread_names`` maps
    ``(pid, tid) -> name`` from the metadata events.  Raises on an
    unreadable/unparsable capture (the caller degrades it to
    ``unavailable``)."""
    paths = sorted(
        glob.glob(os.path.join(log_dir, "**", "*.trace.json.gz"),
                  recursive=True)
        + glob.glob(os.path.join(log_dir, "**", "*.trace.json"),
                    recursive=True)
    )
    if not paths:
        raise FileNotFoundError(
            f"no *.trace.json[.gz] under {log_dir!r} (profiler wrote "
            "nothing — unsupported backend?)"
        )
    events: list[dict] = []
    threads: dict[tuple, str] = {}
    for path in paths:
        opener = gzip.open if path.endswith(".gz") else open
        with opener(path, "rb") as f:
            obj = json.loads(f.read())
        for ev in obj.get("traceEvents", []):
            if ev.get("ph") == "M":
                if ev.get("name") == "thread_name":
                    threads[(ev.get("pid"), ev.get("tid"))] = (
                        (ev.get("args") or {}).get("name", "")
                    )
                continue
            if ev.get("ph") == "X" and "ts" in ev:
                events.append(ev)
    return events, threads


def summarize_events(
    events: Iterable[dict], threads: dict | None = None, *,
    intervals: Iterable[tuple] = (), anchor_offset: float | None = None,
    wall_s: float | None = None, top_ops: int = 10,
) -> dict:
    """Classify + aggregate parsed trace events into the per-engine
    breakdown.  ``intervals`` is ``[(t0, t1, engine, key), ...]`` on
    the ``time.perf_counter`` timeline; ``anchor_offset`` maps an event
    timestamp (microseconds on the trace timeline) onto that timeline
    (``pc = anchor_offset + ts/1e6``) — None disables attribution and
    everything lands in ``unattributed``."""
    threads = threads or {}
    ivs = sorted(intervals)
    buckets = {b: 0.0 for b in BUCKETS}
    engines: dict[str, dict] = {}
    unattributed = {b: 0.0 for b in BUCKETS}
    ops: dict[tuple, list] = {}
    n_op_events = 0

    def _attr(ev_t0: float, ev_t1: float):
        """Engine/key of the launch interval overlapping this event
        most (linear scan is fine: intervals are bounded and windows
        are short); residual clock skew between the trace timeline and
        the perf_counter anchor is absorbed by a nearest-interval
        fallback within 2 ms."""
        best, best_ov = None, 0.0
        near, near_d = None, 2e-3
        for t0, t1, engine, key in ivs:
            ov = min(t1, ev_t1) - max(t0, ev_t0)
            if ov > best_ov:
                best, best_ov = (engine, key), ov
            elif best is None:
                d = max(t0 - ev_t1, ev_t0 - t1)
                if d < near_d:
                    near, near_d = (engine, key), d
        return best if best is not None else near

    for ev in events:
        name = ev.get("name", "")
        tname = threads.get((ev.get("pid"), ev.get("tid")), "")
        bucket = classify_trace_event(name, ev.get("args"), tname)
        if bucket is None:
            continue
        n_op_events += 1
        dur_s = float(ev.get("dur", 0.0)) / 1e6
        buckets[bucket] += dur_s
        o = ops.setdefault((name, bucket), [0, 0.0])
        o[0] += 1
        o[1] += dur_s
        owner = None
        if anchor_offset is not None and ivs:
            t0 = anchor_offset + float(ev["ts"]) / 1e6
            owner = _attr(t0, t0 + dur_s)
        if owner is None:
            unattributed[bucket] += dur_s
            continue
        engine, key = owner
        e = engines.setdefault(engine, {
            **{b: 0.0 for b in BUCKETS}, "seconds": 0.0, "events": 0,
            "keys": {},
        })
        e[bucket] += dur_s
        e["seconds"] += dur_s
        e["events"] += 1
        ks = str(key)
        e["keys"][ks] = e["keys"].get(ks, 0.0) + dur_s
    device_s = sum(buckets.values())
    out = {
        "op_events": n_op_events,
        "buckets": {b: round(v, 6) for b, v in buckets.items()},
        "device_seconds": round(device_s, 6),
        "engines": {
            name: {
                **{b: round(e[b], 6) for b in BUCKETS},
                "seconds": round(e["seconds"], 6),
                "events": e["events"],
                # a handful of the heaviest jit signatures, so a busy
                # engine's dump names WHICH program burned the time
                "top_keys": {
                    k: round(v, 6) for k, v in sorted(
                        e["keys"].items(), key=lambda kv: -kv[1]
                    )[:5]
                },
            }
            for name, e in sorted(engines.items())
        },
        "unattributed": {b: round(v, 6)
                         for b, v in unattributed.items()},
        "top_ops": [
            {"name": n, "bucket": b, "count": c,
             "seconds": round(s, 6)}
            for (n, b), (c, s) in sorted(
                ops.items(), key=lambda kv: -kv[1][1]
            )[:top_ops]
        ],
    }
    if wall_s and wall_s > 0:
        # device-busy share of the window; >1.0 means parallel
        # execution threads (the cpu backend's eigen pool) — an
        # occupancy, not a utilization percentage
        out["occupancy"] = round(device_s / wall_s, 4)
    return out


class DeviceTracer:
    """Process-global trace-window service (one window at a time).

    Lifecycle: ``start(duration)`` opens a jax.profiler session into a
    scratch dir and arms a daemon-thread expiry timer; kernel launches
    report their (engine, key, interval) via :meth:`note_kernel` (the
    KernelProfiler calls it on every record while a window is open);
    ``stop()`` closes the session, parses the capture, attributes
    events to engines, and merges the per-engine buckets into the
    KernelProfiler.  ``status``/``dump`` serve the admin commands.

    Locking discipline: the heavy work — the cold jax import,
    start_trace/stop_trace, and the capture parse — happens OUTSIDE
    ``self._lock``, so the lock-only readers (``status()``,
    ``totals()``, which run on daemon event loops: the report tick and
    the sync admin handler) never block behind it.  An abandoned
    window is closed by the expiry timer's own thread (plus a lazy
    check in ``start``/``dump``, which run in executors), so the
    operator who started a window and walked away cannot leave
    profiler overhead armed — and no event loop pays for the close."""

    MAX_INTERVALS = 8192
    DEFAULT_DURATION = 2.0

    def __init__(self):
        self._lock = threading.Lock()
        self._active = False
        self._label = ""
        self._dir: str | None = None
        self._opened_at = 0.0
        self._deadline = 0.0
        self._timer: threading.Timer | None = None
        self._intervals: list[tuple] = []
        self._intervals_dropped = 0
        self.last: dict | None = None
        self._totals = {b: 0.0 for b in BUCKETS}
        self._consumed: dict[str, float] = {}  # consume_totals cursor
        self._windows = 0
        self._failed_windows = 0
        self._last_occupancy = 0.0

    # the KernelProfiler's fast-path gate: one attribute read per
    # kernel call when no window is open
    @property
    def active(self) -> bool:
        return self._active

    # -- window lifecycle ----------------------------------------------------

    def start(self, duration: float | None = None, label: str = "",
              max_duration: float = 30.0) -> dict:
        # the cold jax import can take SECONDS — never under the lock
        try:
            import jax.profiler  # noqa: F401 — deferred, heavy
        except Exception as e:  # swallow-ok: no jax in this process — degrade to a structured unavailable, nothing device-side was touched
            return {"unavailable": f"jax.profiler not importable: {e!r}"}
        if self._expired():
            self._close(expired=True)
        want = float(duration) if duration else self.DEFAULT_DURATION
        want = max(0.05, min(want, float(max_duration)))
        with self._lock:
            if self._active:
                return {
                    "error": "a trace window is already open "
                             f"(label={self._label!r}, "
                             f"{max(0.0, self._deadline - time.time()):.1f}s"
                             " left) — one window at a time; `kernel "
                             "trace stop` it first",
                    "busy": True,
                }
            # reserve the window NOW: one at a time holds even while
            # start_trace runs outside the lock below
            self._active = True
            self._label = label or ""
            self._dir = None
            self._opened_at = time.time()
            self._deadline = self._opened_at + want
            self._intervals = []
            self._intervals_dropped = 0
        log_dir = tempfile.mkdtemp(prefix="ceph-tpu-ktrace-")
        try:
            import jax

            jax.profiler.start_trace(log_dir)
        except Exception as e:  # swallow-ok: profiler refused (unsupported backend / session conflict) — structured unavailable, no window opened
            shutil.rmtree(log_dir, ignore_errors=True)
            with self._lock:
                self._active = False
                self._failed_windows += 1
            return {"unavailable": f"start_trace failed: {e!r}"}
        # the expiry bound runs on its own daemon thread: no event
        # loop (report tick, sync admin handler) ever pays for the
        # close of an abandoned window
        timer = threading.Timer(want + 0.05, self._expire)
        timer.daemon = True
        with self._lock:
            owned = self._active
            if owned:
                self._dir = log_dir
                self._timer = timer
                # restart the expiry clock now the session is actually
                # open: start_trace's first call pays backend init, and
                # a short window must not expire during its own open
                self._opened_at = time.time()
                self._deadline = self._opened_at + want
        if not owned:
            # a racing stop()/expiry consumed the reservation while
            # start_trace ran: the session we just opened is ownerless
            # — close it NOW or profiler overhead stays armed forever
            # and every future start() fails "already active"
            try:
                jax.profiler.stop_trace()
            except Exception:  # swallow-ok: best-effort teardown of an ownerless profiler session; the structured unavailable below reports the lost window either way
                pass
            shutil.rmtree(log_dir, ignore_errors=True)
            with self._lock:
                self._failed_windows += 1
            return {"unavailable":
                    "trace window closed while opening (racing stop)"}
        timer.start()
        # the profiler tap starts feeding note_kernel from here
        from .profiler import profiler

        profiler().trace_sink = self
        return {
            "success": "trace window open",
            "label": label or "",
            "duration_s": round(want, 3),
            "expires_in_s": round(want, 3),
        }

    def note_kernel(self, engine: str, key: Hashable, seconds: float,
                    nbytes: int = 0,
                    t_end_pc: float | None = None) -> None:
        """One profiler-tapped kernel call's launch interval (called by
        KernelProfiler.record while a window is open; bounded, so a
        storm cannot grow without limit)."""
        if not self._active:
            return
        t1 = t_end_pc if t_end_pc is not None else time.perf_counter()
        with self._lock:
            if not self._active:
                return
            if len(self._intervals) >= self.MAX_INTERVALS:
                self._intervals_dropped += 1
                return
            self._intervals.append((t1 - seconds, t1, engine, key))

    def stop(self) -> dict:
        return self._close()

    def _expired(self) -> bool:
        with self._lock:
            return self._active and time.time() > self._deadline

    def _expire(self) -> None:
        """Timer-thread body: close the window the operator abandoned
        (best effort — a racing explicit stop() wins idempotently)."""
        try:
            if self._expired():
                self._close(expired=True)
        except Exception:  # swallow-ok: expiry is best-effort observability; an explicit stop/dump still closes and reports the failure
            pass

    def _close(self, expired: bool = False) -> dict:
        """Close the open window: mark it inactive under the lock, then
        do the heavy work (stop_trace + parse) OUTSIDE it, then store
        the result.  Idempotent — a second caller sees no open
        window."""
        with self._lock:
            if not self._active:
                # no_window is the structured signal (callers racing
                # the expiry timer key on it to serve dump() instead —
                # never on the message text)
                return {"unavailable": "no trace window open",
                        "no_window": True}
            log_dir = self._dir
            label = self._label
            wall_s = time.time() - self._opened_at
            intervals = self._intervals
            dropped = self._intervals_dropped
            self._active = False
            self._dir = None
            self._intervals = []
            timer, self._timer = self._timer, None
        if timer is not None:
            timer.cancel()  # no-op when this IS the timer thread
        try:
            import jax

            pc_stop = time.perf_counter()
            jax.profiler.stop_trace()
            events, threads = parse_trace_dir(log_dir)
            # anchor the trace timeline (us) onto perf_counter: the
            # python TraceMe for stop_trace STARTS within microseconds
            # of the pc stamp above (the export work that follows would
            # skew a latest-event-end anchor by milliseconds); fall
            # back to the latest event end when a jax version stops
            # emitting the frame
            stop_ts = max(
                (float(e["ts"]) for e in events
                 if "stop_trace" in (e.get("name") or "")),
                default=None,
            ) if events else None
            if stop_ts is None:
                stop_ts = max(
                    (float(e["ts"]) + float(e.get("dur", 0.0))
                     for e in events), default=0.0,
                )
            offset = pc_stop - stop_ts / 1e6 if stop_ts else None
            summary = summarize_events(
                events, threads, intervals=intervals,
                anchor_offset=offset, wall_s=wall_s,
            )
            # self-calibration: when the anchor skewed (the stop frame
            # is emitted by the python tracer and its timing is not
            # guaranteed) and most op-event time went unattributed,
            # re-anchor on the launches themselves — the last HLO event
            # ends just before the last launch interval does (the host
            # materialization tail) — and keep whichever attribution
            # explains more of the window
            unattr = sum(summary["unattributed"].values())
            if intervals and unattr > 0.5 * max(
                summary["device_seconds"], 1e-12
            ):
                hlo_ends = [
                    float(e["ts"]) + float(e.get("dur", 0.0))
                    for e in events
                    if (e.get("args") or {}).get("hlo_op")
                    or (e.get("args") or {}).get("hlo_module")
                ]
                if hlo_ends:
                    refined = (
                        max(t1 for _t0, t1, _e, _k in intervals)
                        - max(hlo_ends) / 1e6
                    )
                    alt = summarize_events(
                        events, threads, intervals=intervals,
                        anchor_offset=refined, wall_s=wall_s,
                    )
                    if sum(alt["unattributed"].values()) < unattr:
                        alt["anchor"] = "interval-aligned"
                        summary = alt
        except Exception as e:  # swallow-ok: capture/parse failure is an observability miss, not an op error — the window closes and reports a structured unavailable
            with self._lock:
                self._failed_windows += 1
                self.last = {
                    "unavailable": f"trace capture failed: {e!r}",
                    "label": label, "wall_s": round(wall_s, 3),
                }
                return dict(self.last)
        finally:
            if log_dir:
                shutil.rmtree(log_dir, ignore_errors=True)
        result = {
            "label": label,
            "wall_s": round(wall_s, 3),
            **({"expired": True} if expired else {}),
            **({"intervals_dropped": dropped} if dropped else {}),
            "launch_intervals": len(intervals),
            **summary,
        }
        with self._lock:
            self._windows += 1
            for b in BUCKETS:
                self._totals[b] += summary["buckets"][b]
            self._last_occupancy = summary.get("occupancy", 0.0)
            self.last = result
        # fold the per-engine buckets into the KernelProfiler entries
        # (same engine names as compile/exec stats): dump_kernel_profile
        # then answers "gather-bound or rebuild-bound?" directly
        from .profiler import profiler

        profiler().merge_device_time({
            name: {b: e[b] for b in BUCKETS}
            for name, e in summary["engines"].items()
        })
        return dict(result)

    # -- admin/service views -------------------------------------------------

    def status(self) -> dict:
        """Lock-only state read — safe straight on an event loop (the
        sync admin handler, the OSD report tick): an expired-but-not-
        yet-closed window (the timer fires within ~50 ms) reports
        active with expires_in_s 0."""
        with self._lock:
            return {
                "active": self._active,
                **({"label": self._label,
                    "expires_in_s": round(
                        max(0.0, self._deadline - time.time()), 3),
                    "launch_intervals": len(self._intervals)}
                   if self._active else {}),
                "windows": self._windows,
                "failed_windows": self._failed_windows,
                "device_seconds_total": {
                    b: round(v, 6) for b, v in self._totals.items()
                },
                "last_occupancy": self._last_occupancy,
            }

    def dump(self) -> dict:
        """The last closed window's breakdown (closing an expired one
        first, so `trace start` + launch + `trace dump` round-trips
        without an explicit stop once the duration passed).  Runs the
        close itself when it races the expiry timer — callers arrive
        via executors (admin handler) or sync tools, never bare on a
        daemon event loop."""
        if self._expired():
            self._close(expired=True)
        with self._lock:
            if self._active:
                return {
                    "unavailable": "trace window still open "
                                   f"({self._deadline - time.time():.1f}s"
                                   " left) — `kernel trace stop` it "
                                   "first or wait for expiry",
                }
            if self.last is None:
                return {"unavailable": "no trace window captured yet"}
            return dict(self.last)

    def totals(self) -> dict:
        """Monotonic per-bucket device-seconds across every window this
        process captured.  Lock-only read: safe on an event loop."""
        with self._lock:
            return {
                **{b: self._totals[b] for b in BUCKETS},
                "windows": self._windows,
                "last_occupancy": self._last_occupancy,
            }

    def consume_totals(self) -> dict:
        """The not-yet-consumed slice of :meth:`totals` — advances a
        single process-global cursor, so the per-bucket seconds are
        handed out exactly ONCE across however many daemons share this
        process.  The OSD report tick feeds its ``ec.device_time_*``
        counters from here: each window's seconds land on whichever
        daemon's tick fires first, and a sum over daemons equals the
        true traced totals (every daemon pulling :meth:`totals`
        independently would report N copies of the same window).
        Lock-only; safe on an event loop."""
        with self._lock:
            out = {}
            for b in BUCKETS:
                out[b] = self._totals[b] - self._consumed.get(b, 0.0)
                self._consumed[b] = self._totals[b]
            out["windows"] = self._windows
            out["last_occupancy"] = self._last_occupancy
            return out


class FlightRecorder:
    """Ring buffer of the last N device launches (the black box the
    reference keeps for ops via OpHistory, applied to LAUNCHES): lane,
    batch key, QoS class, queue-wait vs device wall, and the trace id
    of the slowest member op.  ``lookup(trace_id)`` answers "which
    launch carried this op?" — the SLOW_OPS dump path consults it so a
    slow op's record names its launch instead of leaving the operator
    to correlate timestamps by hand."""

    def __init__(self, capacity: int = 64):
        self._lock = threading.Lock()
        self._ring: deque[dict] = deque(maxlen=max(1, int(capacity)))
        self._inflight: dict[int, dict] = {}
        self._seq = 0

    def begin(self, *, traces: Iterable[str | None] = (),
              **info: Any) -> int:
        """Open a launch record (visible to lookup/dump while the
        device call is in flight — a wedged launch must be findable
        BEFORE it completes).  Returns the token for :meth:`end`."""
        with self._lock:
            self._seq += 1
            token = self._seq
            self._inflight[token] = {
                "seq": token,
                "t": time.time(),
                "_traces": {t for t in traces if t},
                **info,
            }
            return token

    def end(self, token: int, *, device_wall_s: float | None = None,
            served: str | None = None, error: str | None = None,
            origin: str | None = None,
            remote_served: str | None = None,
            remote_queue_wait_s: float | None = None) -> None:
        """``origin`` names the lane whose FAULT caused a
        fallback-served batch ("remote" = accelerator/network trip,
        "device"/"mesh" = local device trip) — without it an operator
        reading ``dump_launch_history`` cannot tell which fault domain
        the replay answered for (ISSUE 10 satellite).
        ``remote_served`` names the engine the ACCELERATOR served a
        remote-lane batch from (device/mesh/fallback/fallback_direct —
        the reply piggybacks it), so the client-side record shows
        whether the shared device, or its host fallback, actually
        produced the bytes."""
        with self._lock:
            rec = self._inflight.pop(token, None)
            if rec is None:
                return
            if device_wall_s is not None:
                rec["device_wall_s"] = round(device_wall_s, 6)
            if served is not None:
                rec["served"] = served
            if error is not None:
                rec["error"] = error
            if origin is not None:
                rec["origin"] = origin
            if remote_served is not None:
                rec["remote_served"] = remote_served
            if remote_queue_wait_s is not None:
                # accel-side coalesce wait (reply piggyback): keeps
                # the queue-wait-vs-device split honest for remote
                # launches, and feeds the waterfall's accel hop
                rec["remote_queue_wait_s"] = round(remote_queue_wait_s, 6)
            self._ring.append(rec)

    @staticmethod
    def _public(rec: dict, in_flight: bool = False) -> dict:
        out = {k: v for k, v in rec.items() if not k.startswith("_")}
        if in_flight:
            out["in_flight"] = True
            out["age_s"] = round(time.time() - rec["t"], 3)
        return out

    def lookup(self, trace: str | None) -> dict | None:
        """The newest launch (in-flight first) that carried this trace
        id, or None."""
        if not trace:
            return None
        with self._lock:
            for rec in self._inflight.values():
                if trace in rec["_traces"]:
                    return self._public(rec, in_flight=True)
            for rec in reversed(self._ring):
                if trace in rec["_traces"]:
                    return self._public(rec)
        return None

    def dump(self) -> dict:
        """``dump_launch_history`` admin-socket body (newest last)."""
        with self._lock:
            return {
                "capacity": self._ring.maxlen,
                "in_flight": [
                    self._public(r, in_flight=True)
                    for r in self._inflight.values()
                ],
                "launches": [self._public(r) for r in self._ring],
            }


_tracer: DeviceTracer | None = None
_tracer_lock = threading.Lock()


def tracer() -> DeviceTracer:
    """The process-global window service (same singleton pattern as
    ops.profiler — every in-process daemon shares the one profiler
    session the singleton guards)."""
    global _tracer
    if _tracer is None:
        with _tracer_lock:
            if _tracer is None:
                _tracer = DeviceTracer()
    return _tracer
