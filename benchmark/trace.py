"""Reduce a profiler trace (``.xplane.pb``) to device busy time, op
times and idle gaps, read through ``jax.profiler.ProfileData``.

Fitted to a v5e trace (``benchmark/record_trace.py``): each chip is a
plane ``/device:TPU:<n>``; its line ``XLA Ops`` holds one event per
HLO op that ran, and nothing else counts as device work.  Host planes
hold the benchmark's own ``bench.*`` spans; ``bench.window`` bounds the
measured window.  Device and host events share one clock.
"""

from __future__ import annotations

import glob
import os

DEVICE_PLANE = "/device:TPU:"
OP_LINE = "XLA Ops"
SPAN = "bench."


def merge(intervals):
    """Sorted, merged (start, end) pairs."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def summarize(pd) -> dict:
    """``busy_s`` (union of op intervals inside the window, averaged over
    the chips), ``window_s``, ``device_ops`` ([name, seconds], most time
    first), ``idle_gaps`` ([label, seconds], longest first: each gap
    between busy intervals on chip 0, named after the innermost
    benchmark span that covers its middle, or ``host`` where none does),
    ``op_events`` and ``chips``."""
    spans, devices = [], []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PLANE):
            # an op event's name is its HLO text: keep the op's own name
            ops = [(e.start_ns, e.end_ns, e.name.split(" = ")[0].lstrip("%"))
                   for line in plane.lines if line.name == OP_LINE
                   for e in line.events]
            devices.append((plane.name, ops))
        else:
            spans += [(e.start_ns, e.end_ns, e.name[len(SPAN):])
                      for line in plane.lines for e in line.events
                      if e.name.startswith(SPAN)]
    windows = [(s, e) for s, e, n in spans if n == "window"]
    if not windows:
        raise ValueError("trace holds no bench.window span")
    lo, hi = windows[0]
    if not devices:
        raise ValueError("trace holds no TPU device plane")
    devices.sort()
    busy, per_op, n_events = [], {}, 0
    for _, ops in devices:
        inside = [(s, e, n) for s, e, n in ops if e > lo and s < hi]
        n_events += len(inside)
        busy.append(merge(_clip([(s, e) for s, e, _ in inside], lo, hi)))
        for s, e, n in inside:
            per_op[n] = per_op.get(n, 0) + min(e, hi) - max(s, lo)
    busy_ns = sum(e - s for b in busy for s, e in b) / len(busy)
    gaps = []
    edges = [lo] + [x for s, e in busy[0] for x in (s, e)] + [hi]
    inner = sorted((s, e, n) for s, e, n in spans if n != "window")
    for a, b in zip(edges[::2], edges[1::2]):
        if b <= a:
            continue
        mid = (a + b) / 2
        cover = [(e - s, n) for s, e, n in inner if s <= mid <= e]
        gaps.append([min(cover)[1] if cover else "host", (b - a) / 1e9])
    gaps.sort(key=lambda g: -g[1])
    return {
        "busy_s": busy_ns / 1e9,
        "window_s": (hi - lo) / 1e9,
        "device_ops": sorted(([n, t / 1e9] for n, t in per_op.items()),
                             key=lambda x: -x[1]),
        "idle_gaps": gaps,
        "op_events": n_events,
        "chips": len(devices),
    }


def summarize_file(path: str) -> dict:
    from jax.profiler import ProfileData

    return summarize(ProfileData.from_file(path))


def summarize_dir(log_dir: str) -> dict:
    found = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(found) != 1:
        raise ValueError(f"expected one .xplane.pb under {log_dir}, "
                         f"found {len(found)}")
    return summarize_file(found[0])


def idle_percent(run):
    """The device's idle share of a run's traced window, in percent:
    1 - busy union / window.  None where the trace holds no op."""
    t = run.trace
    if not t or t["window_s"] <= 0 or not t["op_events"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
