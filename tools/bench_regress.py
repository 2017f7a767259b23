#!/usr/bin/env python3
"""Bench-trajectory non-regression gate over the committed BENCH_*.json
records (the per-round driver captures of bench.py's final line).

Each round's driver writes ``BENCH_r<N>.json`` with the shape
``{"n", "cmd", "rc", "tail", "parsed"}`` where ``parsed`` is bench.py's
final JSON line (or null when the run produced none); bare final-line
JSON files are accepted too.  This tool loads the last N rounds,
compares the newest measurement against the best earlier one **with
the same phase** — the committed pre-PR-21 rounds include
"native-only" host fallbacks, which are not TPU measurements (bench.py
no longer produces them: a run with no chip prints a null value) — and
exits nonzero when the newest throughput falls below ``threshold`` x
the prior best.

Same-phase is necessary but not sufficient: a ``--batch 8`` run moves
8 MiB per launch where the default moves 64 MiB, and GB/s at 8 MiB is
not GB/s at 64 MiB (less launch amortization).  Rounds now record ``batch_bytes`` in the final line;
when both the newest round and a prior record it, a mismatch excludes
that prior from the comparison (listed in the report as
``excluded_batch_mismatch``).  Rounds predating the field are compared
as before — the ambiguity dies out as the trajectory grows.

``--metric`` takes a dotted path into the final line, so nested phase
records gate too: ``--metric qos.protection`` watches the QoS
starvation-gate protection factor (fifo p99 / mclock p99 — how much
tail latency the dmClock scheduler buys under a recovery storm, higher
is better, same direction as every throughput metric here).

``--metric stack_gbps`` is first-class: before PR 21 the codec-stack
measurement was taken on the cpu backend every round, so unlike the
headline it was comparable across phase flips — a "native-only"
fallback round still measured the same stack (bench.py now takes it in
the device child).  Metrics in ``PHASE_AGNOSTIC_METRICS`` therefore skip the
same-phase filter (and the batch_bytes filter, which only qualifies
the headline's device batches).  This is the zero-copy data path's
monotonic gate: once the stack gap closes, a PR that re-introduces
per-hop copies fails here.

Usage:
  python tools/bench_regress.py [--dir D] [--last N] [--threshold R]
                                [--metric value|qos.protection|...]

Exit codes: 0 = ok / nothing comparable; 1 = regression; 2 = no usable
bench records at all.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys

# metrics measured on the SAME backend every round (bench.py's serial
# cpu stack child), hence comparable across headline phase flips.
# stack_e2e.stack_e2e_gbps (frames + crc + striper + EC encode, one
# whole-stack pass) is promoted alongside stack_gbps (ROADMAP 3c): it
# rides the same cpu stack child.  Rounds predating the field simply
# lack the metric, so the gate reports "not comparable" (exit 0) until
# two rounds carry it — promotion can never fail a round retroactively.
PHASE_AGNOSTIC_METRICS = {"stack_gbps", "raw_cpu_gbps", "stack_vs_raw",
                          "stack_e2e.stack_e2e_gbps"}

# convenience spellings -> the dotted path inside the final line
METRIC_ALIASES = {"stack_e2e_gbps": "stack_e2e.stack_e2e_gbps",
                  "mesh_scaling_efficiency": "mesh.scaling_efficiency",
                  "mesh_ici_share": "mesh.ici_share",
                  "accel_occupancy": "accel.occupancy",
                  "accel_fleet_occupancy": "accel.fleet_occupancy",
                  "smallops_header_share": "smallops.header_share",
                  "smallops_ops_per_sec": "smallops.ops_per_sec",
                  # the p99 rides the final line as op_p99_ms; both
                  # spellings of the promoted IOPS tail metric resolve
                  "smallops_op_p99": "smallops.op_p99_ms",
                  "smallops.op_p99": "smallops.op_p99_ms",
                  "smallops_trace_overhead_share":
                      "smallops.trace_overhead_share",
                  # the ProcCluster (real-multiprocess) smallops rate
                  # rides the final line under smallops.proc — its own
                  # dotted path, so the cross-process number is never
                  # compared against the loopback one
                  "smallops_proc_ops_per_sec":
                      "smallops.proc.ops_per_sec",
                  "churn_protection": "churn.protection",
                  "churn_recovery_gbps": "churn.recovery_gbps"}

# per-metric default thresholds (used when --threshold is not given):
# mesh.scaling_efficiency is a RATIO (per-chip efficiency of the
# multi-chip EC phase, ISSUE 8) — a >20% drop between rounds carrying
# the mesh phase is a topology/sharding regression, far inside the 2x
# jitter budget the throughput metrics need.  Rounds without the mesh
# record simply lack the metric, so the gate skips cleanly (exit 0)
# until two same-phase rounds carry it.
# accel.occupancy (ISSUE 10) is the shared accelerator's device
# occupancy under an N-feeder storm — a RATIO like the mesh
# efficiency, same 20% budget; rounds predating the accel phase
# simply lack the metric, so the gate skips cleanly (exit 0) until
# two rounds carry it.
# accel.fleet_occupancy (ISSUE 11) is the MULTI-accel phase's
# aggregate occupancy under 4:1:1:1 feeder skew with a mid-run accel
# kill — the fleet-balancing analog of accel.occupancy, same ratio
# semantics, same 20% budget, same clean skip until two rounds carry
# the fleet record.
# smallops.header_share (ISSUE 12) is the measured JSON-header
# encode/decode share of small-op wall time (the cost ledger riding
# the smallops waterfall capture) — LOWER_IS_BETTER with the additive
# share slack, same shape as mesh.ici_share: a change that grows the
# header tax must fail even when GB/s barely moves, and the round that
# lands ROADMAP item 1's binary header should show up as a step DOWN.
# Rounds predating the capture lack the metric -> clean skip until two
# rounds carry it.
# smallops.ops_per_sec / smallops.op_p99 (the binary-wire-protocol
# PR): IOPS and op tail latency promoted to gated metrics now that the
# waterfall capture measures them every round — millions of users
# means IOPS, not just GB/s.  ops_per_sec is a throughput (higher is
# better, the standard 2x jitter budget on a noisy loopback capture);
# op_p99 is LOWER_IS_BETTER in milliseconds with a 0.5ms additive
# slack (a sub-ms absolute wobble on a contended CI host must not read
# as a 2x relative regression).  Both clean-skip (exit 0) until two
# rounds carry the capture.
# smallops.trace_overhead_share (ISSUE 18) is the tail-sampling tax:
# 1 - (ops/sec keep-policy-armed / ops/sec tracing-off) from the same
# waterfall cluster — LOWER_IS_BETTER with the additive share slack,
# same shape as header_share, so always-on decide-late tracing can
# never silently regress the PR-13 IOPS win.  Clean-skips (exit 0)
# until two rounds carry the capture.
# smallops.proc.ops_per_sec (ISSUE 19) is the multi-host truth pass:
# the same pipelined smallops round against a real-multiprocess
# ProcCluster (TCP between OSD processes, hop re-rank off the mgr's
# kept-trace store).  A throughput with the standard 2x jitter budget
# — and deliberately a SEPARATE dotted path from the loopback
# smallops.ops_per_sec, so the two regimes gate independently and a
# loopback-only win can never mask a cross-process regression.
# Clean-skips (exit 0) until two rounds carry the proc record.
# churn.protection (ISSUE 15) is the live-storm client protection
# factor — fifo's storm-vs-quiescent p99 blowup over mclock's under
# the SAME OSD-kill/recovery storm (a real MiniCluster cycle per
# policy, not the synthetic scheduler harness behind qos.protection).
# It is a ratio of FOUR live loopback p99s, so its round-over-round
# noise is multiplicative (measured best-of-2 spread ~1.3-2.7x on an
# idle host): the budget is 2.5x (0.4), not the occupancy metrics'
# 20% — a real regression (protection collapsing toward/under 1.0
# from a healthy ~2x) still fails.  Rounds predating the churn phase
# lack the metric, so the gate skips cleanly (exit 0) until two
# rounds carry it.  churn.recovery_gbps is the storm's measured
# recovery throughput (bytes the primaries re-pushed over the
# fifo run's recovery wall) — a throughput with the standard 2x
# jitter budget, same clean-skip semantics.
METRIC_DEFAULT_THRESHOLDS = {"mesh.scaling_efficiency": 0.8,
                             "mesh.ici_share": 0.8,
                             "accel.occupancy": 0.8,
                             "accel.fleet_occupancy": 0.8,
                             "smallops.header_share": 0.8,
                             "smallops.ops_per_sec": 0.5,
                             "smallops.op_p99_ms": 0.5,
                             "smallops.trace_overhead_share": 0.8,
                             "smallops.proc.ops_per_sec": 0.5,
                             "churn.protection": 0.4,
                             "churn.recovery_gbps": 0.5}

# metrics where GROWTH is the regression: mesh.ici_share (ISSUE 9) is
# the ICI all-gather's share of the mesh reconstruct's device time,
# measured by a jax.profiler trace window — a change that shifts the
# reconstruct from compute-bound to gather-bound must fail the gate
# even when headline GB/s barely moves.  Compared with an additive
# per-metric slack (shares are small ratios: best-prior 0.0 must not
# make a 2-percentage-point wobble fatal; p99 is absolute ms): ratio =
# (best + slack) / (current + slack), regression when ratio <
# threshold.
LOWER_IS_BETTER = {"mesh.ici_share", "smallops.header_share",
                   "smallops.op_p99_ms",
                   "smallops.trace_overhead_share"}
_SLACKS = {"mesh.ici_share": 0.1, "smallops.header_share": 0.1,
           "smallops.op_p99_ms": 0.5,
           "smallops.trace_overhead_share": 0.1}
_SHARE_SLACK = 0.1  # fallback for LOWER_IS_BETTER metrics not in _SLACKS


def load_rounds(bench_dir: str) -> list[dict]:
    """[{round, phase, metrics...}] sorted by round number (numeric:
    lexicographic sorting puts r10 before r9)."""
    rounds = []
    for path in glob.glob(os.path.join(bench_dir, "BENCH_r*.json")):
        m = re.search(r"_r(\d+)\.json$", path)
        if not m:
            continue
        try:
            with open(path) as f:
                obj = json.load(f)
        except (OSError, json.JSONDecodeError) as e:
            print(f"bench_regress: skipping {path}: {e}", file=sys.stderr)
            continue
        line = obj.get("parsed") if "parsed" in obj else obj
        if not isinstance(line, dict) or "value" not in line:
            continue  # a round with no parseable result (rc=124 etc.)
        rounds.append({
            "round": int(m.group(1)),
            "file": os.path.basename(path),
            "phase": line.get("phase", "?"),
            "line": line,
        })
    rounds.sort(key=lambda r: r["round"])
    return rounds


def metric_value(line: dict, path: str):
    """Resolve a dotted metric path inside one final line
    (``"value"`` -> line["value"], ``"qos.protection"`` ->
    line["qos"]["protection"]); None when any hop is missing."""
    cur = line
    for part in path.split("."):
        if not isinstance(cur, dict):
            return None
        cur = cur.get(part)
    return cur


def compare(rounds: list[dict], metric: str = "value",
            threshold: float = 0.5) -> dict:
    """Newest round vs the best prior SAME-PHASE round.

    Returns a report dict with ``regression`` True/False;
    ``comparable`` False when there is no earlier same-phase round to
    judge against (first round of a phase, or a phase flip)."""
    metric = METRIC_ALIASES.get(metric, metric)
    if not rounds:
        return {"comparable": False, "reason": "no bench records"}
    newest = rounds[-1]
    phase = newest["phase"]
    phase_agnostic = metric in PHASE_AGNOSTIC_METRICS
    cur = metric_value(newest["line"], metric)
    if not isinstance(cur, (int, float)):
        return {
            "comparable": False, "newest": newest["file"],
            "reason": f"newest round has no numeric {metric!r}",
        }
    priors = [
        r for r in rounds[:-1]
        if (phase_agnostic or r["phase"] == phase)
        and isinstance(metric_value(r["line"], metric), (int, float))
    ]
    # per-byte comparability: drop priors measured on a DIFFERENT batch
    # size (the 8 MiB cpu-fallback vs 64 MiB TPU trap); unrecorded
    # batch_bytes (older rounds) stays comparable.  Phase-agnostic
    # metrics skip this too — batch_bytes qualifies the headline's
    # device batches, not the cpu stack child's fixed-size loop.
    cur_bb = newest["line"].get("batch_bytes")
    excluded = []
    if cur_bb is not None and not phase_agnostic:
        excluded = [
            r["file"] for r in priors
            if r["line"].get("batch_bytes") not in (None, cur_bb)
        ]
        priors = [
            r for r in priors
            if r["line"].get("batch_bytes") in (None, cur_bb)
        ]
    if not priors:
        return {
            "comparable": False, "newest": newest["file"],
            "phase": phase,
            **({"excluded_batch_mismatch": excluded} if excluded else {}),
            "reason": (
                (f"no earlier round with {metric!r}" if phase_agnostic
                 else f"no earlier round with phase {phase!r}")
                + (" and a matching batch_bytes" if excluded else "")
            ),
        }
    lower = metric in LOWER_IS_BETTER
    if lower:
        slack = _SLACKS.get(metric, _SHARE_SLACK)
        best = min(priors, key=lambda r: metric_value(r["line"], metric))
        best_v = float(metric_value(best["line"], metric))
        ratio = (best_v + slack) / (float(cur) + slack)
    else:
        best = max(priors, key=lambda r: metric_value(r["line"], metric))
        best_v = float(metric_value(best["line"], metric))
        ratio = (float(cur) / best_v) if best_v > 0 else 1.0
    return {
        "comparable": True,
        "newest": newest["file"],
        "phase": phase,
        **({"batch_bytes": cur_bb} if cur_bb is not None else {}),
        **({"excluded_batch_mismatch": excluded} if excluded else {}),
        "metric": metric,
        **({"lower_is_better": True} if lower else {}),
        "current": float(cur),
        "best_prior": best_v,
        "best_prior_file": best["file"],
        "ratio": round(ratio, 4),
        "threshold": threshold,
        "regression": ratio < threshold,
    }


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        description="fail on bench throughput regression")
    ap.add_argument("--dir", default=os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    ap.add_argument("--last", type=int, default=5,
                    help="how many newest rounds to consider")
    ap.add_argument("--metric", default="value",
                    help="final-line key to compare; dotted paths reach "
                         "nested records, e.g. qos.protection, "
                         "stack_e2e.stack_e2e_gbps (alias: "
                         "stack_e2e_gbps), mesh.scaling_efficiency "
                         "(alias: mesh_scaling_efficiency) or "
                         "mesh.ici_share (alias: mesh_ici_share; "
                         "lower is better — growth is the regression) "
                         "(default: value)")
    ap.add_argument("--threshold", type=float, default=None,
                    help="fail when newest < threshold x prior best "
                         "(default: 0.5 = a 2x drop fails; "
                         "mesh.scaling_efficiency defaults to 0.8 = a "
                         ">20%% per-chip efficiency drop fails)")
    args = ap.parse_args(argv)

    metric = METRIC_ALIASES.get(args.metric, args.metric)
    threshold = (args.threshold if args.threshold is not None
                 else METRIC_DEFAULT_THRESHOLDS.get(metric, 0.5))
    rounds = load_rounds(args.dir)
    if not rounds:
        print(json.dumps({"error": "no usable BENCH_*.json records",
                          "dir": args.dir}))
        return 2
    report = compare(rounds[-args.last:], metric=metric,
                     threshold=threshold)
    print(json.dumps(report, indent=2))
    return 1 if report.get("regression") else 0


if __name__ == "__main__":
    sys.exit(main())
