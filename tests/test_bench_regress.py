"""Bench-regression pipeline: tools/bench_regress.py fails on a real
throughput drop but never reads a run without a chip result as a
measurement, and bench.py fails loud when it has no chip result — the
device child aborting in backend start-up, finding no TPU, or losing
every engine mid-headline — with a parseable error line and a
non-zero exit.
"""

import importlib.util
import json
import os
import pathlib
import subprocess
import sys


def _load_tool():
    path = (pathlib.Path(__file__).parent.parent
            / "tools" / "bench_regress.py")
    spec = importlib.util.spec_from_file_location("bench_regress", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules["bench_regress"] = mod
    spec.loader.exec_module(mod)
    return mod


def _write_round(tmp_path, n, phase, value, wrapped=True, parsed=True,
                 batch_bytes=None):
    line = {"metric": "m", "value": value, "unit": "GB/s",
            "phase": phase}
    if batch_bytes is not None:
        line["batch_bytes"] = batch_bytes
    obj = ({"n": n, "rc": 0, "parsed": (line if parsed else None)}
           if wrapped else line)
    (tmp_path / f"BENCH_r{n:02d}.json").write_text(json.dumps(obj))


class TestBenchRegress:
    def test_2x_drop_fails(self, tmp_path):
        br = _load_tool()
        _write_round(tmp_path, 1, "tpu", 600.0)
        _write_round(tmp_path, 2, "tpu", 650.0)
        _write_round(tmp_path, 3, "tpu", 300.0)  # 2x drop vs best prior
        rc = br.main(["--dir", str(tmp_path)])
        assert rc == 1

    def test_stable_trajectory_passes(self, tmp_path):
        br = _load_tool()
        _write_round(tmp_path, 1, "tpu", 600.0)
        _write_round(tmp_path, 2, "tpu", 662.0)
        _write_round(tmp_path, 3, "tpu", 540.0)  # jitter, not a 2x drop
        assert br.main(["--dir", str(tmp_path)]) == 0

    def test_no_device_round_is_never_compared(self, tmp_path):
        """A run that found no chip prints an error line with no value
        (bench.py exits non-zero on it): the comparator never reads it
        as a measurement, in either direction."""
        br = _load_tool()
        _write_round(tmp_path, 1, "tpu", 662.0)
        _write_round(tmp_path, 2, "no-device", None)
        report = br.compare(br.load_rounds(str(tmp_path)))
        assert report["comparable"] is False
        assert "no numeric" in report["reason"]
        assert br.main(["--dir", str(tmp_path)]) == 0

    def test_batch_mismatch_is_excluded(self, tmp_path):
        """A ``--batch 8`` run (8 MiB per launch) must not be judged
        against a 64 MiB round: same phase, different batch_bytes ->
        the prior is excluded from the comparison."""
        br = _load_tool()
        _write_round(tmp_path, 1, "tpu", 660.0, batch_bytes=64 << 20)
        # smaller batch, lower GB/s than a 2x drop would allow
        _write_round(tmp_path, 2, "tpu", 200.0, batch_bytes=8 << 20)
        report = br.compare(br.load_rounds(str(tmp_path)))
        assert report["comparable"] is False
        assert report["excluded_batch_mismatch"] == ["BENCH_r01.json"]
        assert br.main(["--dir", str(tmp_path)]) == 0

    def test_same_batch_still_gates(self, tmp_path):
        br = _load_tool()
        _write_round(tmp_path, 1, "tpu", 600.0, batch_bytes=64 << 20)
        _write_round(tmp_path, 2, "tpu", 250.0, batch_bytes=64 << 20)
        report = br.compare(br.load_rounds(str(tmp_path)))
        assert report["comparable"] is True
        assert report["regression"] is True
        assert br.main(["--dir", str(tmp_path)]) == 1

    def test_legacy_rounds_without_batch_bytes_compare(self, tmp_path):
        """Rounds predating the batch_bytes field keep gating (the
        wildcard rule), so the trajectory does not go blind at the
        transition."""
        br = _load_tool()
        _write_round(tmp_path, 1, "tpu", 600.0)  # legacy, no field
        _write_round(tmp_path, 2, "tpu", 250.0, batch_bytes=64 << 20)
        report = br.compare(br.load_rounds(str(tmp_path)))
        assert report["comparable"] is True
        assert report["regression"] is True

    def test_unparsed_rounds_skipped_and_bare_lines_accepted(
        self, tmp_path
    ):
        br = _load_tool()
        _write_round(tmp_path, 1, "tpu", 600.0, wrapped=False)
        _write_round(tmp_path, 2, "tpu", 650.0)
        _write_round(tmp_path, 3, "tpu", 0.0, parsed=False)  # rc=124
        rounds = br.load_rounds(str(tmp_path))
        assert [r["round"] for r in rounds] == [1, 2]
        assert br.main(["--dir", str(tmp_path)]) == 0

    def test_numeric_round_ordering(self, tmp_path):
        br = _load_tool()
        for n, v in ((9, 600.0), (10, 100.0)):  # r10 is newest, 6x drop
            _write_round(tmp_path, n, "tpu", v)
        assert br.main(["--dir", str(tmp_path)]) == 1

    def test_no_records_exit_2(self, tmp_path):
        br = _load_tool()
        assert br.main(["--dir", str(tmp_path)]) == 2

    def test_threshold_option(self, tmp_path):
        br = _load_tool()
        _write_round(tmp_path, 1, "tpu", 100.0)
        _write_round(tmp_path, 2, "tpu", 80.0)
        assert br.main(["--dir", str(tmp_path)]) == 0
        assert br.main(
            ["--dir", str(tmp_path), "--threshold", "0.9"]
        ) == 1

    # -- stack_gbps promotion (PR 6): phase-agnostic gating ------------------

    def _write_stack_round(self, tmp_path, n, phase, value, stack):
        line = {"metric": "m", "value": value, "unit": "GB/s",
                "phase": phase, "stack_gbps": stack,
                "batch_bytes": 1 << 26 if phase == "tpu" else 1 << 23}
        (tmp_path / f"BENCH_r{n:02d}.json").write_text(
            json.dumps({"n": n, "rc": 0, "parsed": line})
        )

    def test_stack_gbps_gates_across_phase_flips(self, tmp_path):
        """The codec-stack number is measured on the cpu backend every
        round, so a tpu->native-only flip must NOT hide a stack
        regression (and batch_bytes, which qualifies only the headline
        device batches, must not exclude priors)."""
        br = _load_tool()
        self._write_stack_round(tmp_path, 1, "tpu", 662.0, 5.8)
        self._write_stack_round(tmp_path, 2, "native-only", 6.7, 2.0)
        report_rc = br.main(
            ["--dir", str(tmp_path), "--metric", "stack_gbps"]
        )
        assert report_rc == 1  # 5.8 -> 2.0 is a real stack regression
        rep = br.compare(
            br.load_rounds(str(tmp_path)), metric="stack_gbps"
        )
        assert rep["comparable"] and rep["regression"]
        assert "excluded_batch_mismatch" not in rep

    def test_stack_gbps_improvement_passes(self, tmp_path):
        br = _load_tool()
        self._write_stack_round(tmp_path, 1, "native-only", 6.7, 1.24)
        self._write_stack_round(tmp_path, 2, "tpu", 662.0, 6.4)
        assert br.main(
            ["--dir", str(tmp_path), "--metric", "stack_gbps"]
        ) == 0

    def test_headline_metric_still_phase_gated(self, tmp_path):
        """Promotion must not loosen the default metric: the headline
        still refuses cross-phase comparison."""
        br = _load_tool()
        self._write_stack_round(tmp_path, 1, "tpu", 662.0, 5.8)
        self._write_stack_round(tmp_path, 2, "native-only", 6.7, 5.8)
        rep = br.compare(br.load_rounds(str(tmp_path)), metric="value")
        assert not rep["comparable"]

    # -- stack_e2e_gbps promotion (ISSUE 7 / ROADMAP 3c) ---------------------

    def _write_e2e_round(self, tmp_path, n, phase, value, e2e=None):
        line = {"metric": "m", "value": value, "unit": "GB/s",
                "phase": phase}
        if e2e is not None:
            line["stack_e2e"] = {"stack_e2e_gbps": e2e,
                                 "copied_bytes": {}}
        (tmp_path / f"BENCH_r{n:02d}.json").write_text(
            json.dumps({"n": n, "rc": 0, "parsed": line})
        )

    def test_stack_e2e_gates_across_phase_flips(self, tmp_path):
        """stack_e2e_gbps rides the same cpu stack child as stack_gbps,
        so it gates phase-agnostically (and through the alias)."""
        br = _load_tool()
        self._write_e2e_round(tmp_path, 1, "tpu", 662.0, e2e=1.02)
        self._write_e2e_round(tmp_path, 2, "native-only", 6.7, e2e=0.3)
        for metric in ("stack_e2e.stack_e2e_gbps", "stack_e2e_gbps"):
            rep = br.compare(br.load_rounds(str(tmp_path)),
                             metric=metric)
            assert rep["comparable"] and rep["regression"], metric
            assert br.main(
                ["--dir", str(tmp_path), "--metric", metric]
            ) == 1

    def test_stack_e2e_skips_cleanly_until_two_rounds_carry_it(
        self, tmp_path
    ):
        """Rounds predating the field must not fail the gate: with
        fewer than two rounds carrying stack_e2e the report says 'not
        comparable' and the exit code stays 0."""
        br = _load_tool()
        self._write_e2e_round(tmp_path, 1, "tpu", 662.0)  # legacy
        self._write_e2e_round(tmp_path, 2, "tpu", 650.0, e2e=1.02)
        rep = br.compare(br.load_rounds(str(tmp_path)),
                         metric="stack_e2e_gbps")
        assert rep["comparable"] is False
        assert br.main(
            ["--dir", str(tmp_path), "--metric", "stack_e2e_gbps"]
        ) == 0
        # ...and with no round carrying it at all
        self._write_e2e_round(tmp_path, 3, "tpu", 655.0)
        assert br.main(
            ["--dir", str(tmp_path), "--metric", "stack_e2e_gbps"]
        ) == 0

    def test_stack_e2e_improvement_passes(self, tmp_path):
        br = _load_tool()
        self._write_e2e_round(tmp_path, 1, "native-only", 6.7, e2e=0.5)
        self._write_e2e_round(tmp_path, 2, "tpu", 662.0, e2e=1.02)
        assert br.main(
            ["--dir", str(tmp_path), "--metric", "stack_e2e_gbps"]
        ) == 0

    # -- mesh.scaling_efficiency (ISSUE 8): 20%-drop gate --------------------

    def _write_mesh_round(self, tmp_path, n, phase, value, eff=None):
        line = {"metric": "m", "value": value, "unit": "GB/s",
                "phase": phase}
        if eff is not None:
            line["mesh"] = {"scaling_efficiency": eff,
                            "n_devices": 8, "scaling": []}
        (tmp_path / f"BENCH_r{n:02d}.json").write_text(
            json.dumps({"n": n, "rc": 0, "parsed": line})
        )

    def test_mesh_efficiency_20pct_drop_fails(self, tmp_path):
        """A >20% per-chip efficiency drop between rounds carrying the
        mesh phase fails at the metric's own 0.8 default threshold —
        far inside the 2x budget the throughput metrics get."""
        br = _load_tool()
        self._write_mesh_round(tmp_path, 1, "tpu", 660.0, eff=0.9)
        self._write_mesh_round(tmp_path, 2, "tpu", 650.0, eff=0.7)
        # 0.7/0.9 = 0.78 < 0.8 -> regression (both metric spellings)
        for metric in ("mesh.scaling_efficiency",
                       "mesh_scaling_efficiency"):
            assert br.main(
                ["--dir", str(tmp_path), "--metric", metric]
            ) == 1, metric

    def test_mesh_efficiency_small_wobble_passes(self, tmp_path):
        br = _load_tool()
        self._write_mesh_round(tmp_path, 1, "tpu", 660.0, eff=0.9)
        self._write_mesh_round(tmp_path, 2, "tpu", 650.0, eff=0.78)
        # 0.78/0.9 = 0.87 >= 0.8 -> ok
        assert br.main(
            ["--dir", str(tmp_path),
             "--metric", "mesh.scaling_efficiency"]
        ) == 0

    def test_mesh_metric_skips_rounds_without_it(self, tmp_path):
        """Rounds predating the mesh phase lack the record: the gate
        reports 'not comparable' and exits 0 until two rounds carry
        it (promotion can never fail a round retroactively)."""
        br = _load_tool()
        self._write_mesh_round(tmp_path, 1, "tpu", 660.0)  # legacy
        self._write_mesh_round(tmp_path, 2, "tpu", 650.0, eff=0.5)
        rep = br.compare(br.load_rounds(str(tmp_path)),
                         metric="mesh.scaling_efficiency")
        assert rep["comparable"] is False
        assert br.main(
            ["--dir", str(tmp_path),
             "--metric", "mesh.scaling_efficiency"]
        ) == 0

    def test_mesh_explicit_threshold_still_wins(self, tmp_path):
        br = _load_tool()
        self._write_mesh_round(tmp_path, 1, "tpu", 660.0, eff=0.9)
        self._write_mesh_round(tmp_path, 2, "tpu", 650.0, eff=0.7)
        # operator override: a 0.5 threshold tolerates the 0.78 ratio
        assert br.main(
            ["--dir", str(tmp_path),
             "--metric", "mesh.scaling_efficiency",
             "--threshold", "0.5"]
        ) == 0

    # -- mesh.ici_share (ISSUE 9): lower-is-better gate ----------------------

    def _write_ici_round(self, tmp_path, n, phase, value, ici=None):
        line = {"metric": "m", "value": value, "unit": "GB/s",
                "phase": phase}
        if ici is not None:
            line["mesh"] = {"ici_share": ici, "ici_share_measured": True,
                            "scaling_efficiency": 0.9}
        (tmp_path / f"BENCH_r{n:02d}.json").write_text(
            json.dumps({"n": n, "rc": 0, "parsed": line})
        )

    def test_ici_share_growth_is_the_regression(self, tmp_path):
        """mesh.ici_share is lower-is-better: a reconstruct drifting
        from compute-bound to gather-bound fails the gate even when
        headline GB/s barely moves.  (0.2+0.1)/(0.6+0.1) = 0.43 <
        0.8 -> regression, via both metric spellings."""
        br = _load_tool()
        self._write_ici_round(tmp_path, 1, "tpu", 660.0, ici=0.2)
        self._write_ici_round(tmp_path, 2, "tpu", 658.0, ici=0.6)
        rep = br.compare(br.load_rounds(str(tmp_path)),
                         metric="mesh.ici_share")
        assert rep["comparable"] and rep["lower_is_better"]
        assert rep["regression"] is True
        for metric in ("mesh.ici_share", "mesh_ici_share"):
            assert br.main(
                ["--dir", str(tmp_path), "--metric", metric]
            ) == 1, metric

    def test_ici_share_wobble_and_shrink_pass(self, tmp_path):
        br = _load_tool()
        self._write_ici_round(tmp_path, 1, "tpu", 660.0, ici=0.3)
        # small wobble: (0.3+0.1)/(0.35+0.1) = 0.89 >= 0.8
        self._write_ici_round(tmp_path, 2, "tpu", 658.0, ici=0.35)
        assert br.main(
            ["--dir", str(tmp_path), "--metric", "mesh.ici_share"]
        ) == 0
        # improvement (share SHRINKS): ratio > 1, never a regression
        self._write_ici_round(tmp_path, 3, "tpu", 661.0, ici=0.1)
        rep = br.compare(br.load_rounds(str(tmp_path)),
                         metric="mesh.ici_share")
        assert rep["ratio"] > 1 and not rep["regression"]

    def test_ici_share_skips_until_two_rounds_carry_it(self, tmp_path):
        """ISSUE 9 acceptance: the metric skips cleanly (exit 0) until
        two rounds carry it — promotion can never fail a round
        retroactively."""
        br = _load_tool()
        self._write_ici_round(tmp_path, 1, "tpu", 660.0)  # legacy
        self._write_ici_round(tmp_path, 2, "tpu", 650.0, ici=0.4)
        rep = br.compare(br.load_rounds(str(tmp_path)),
                         metric="mesh.ici_share")
        assert rep["comparable"] is False
        assert br.main(
            ["--dir", str(tmp_path), "--metric", "mesh.ici_share"]
        ) == 0

    def test_ici_share_zero_prior_tolerates_small_absolute_growth(
        self, tmp_path
    ):
        """The additive slack keeps a near-zero best prior from making
        percentage-point noise fatal: 0.0 -> 0.02 passes, 0.0 -> 0.3
        fails."""
        br = _load_tool()
        self._write_ici_round(tmp_path, 1, "tpu", 660.0, ici=0.0)
        self._write_ici_round(tmp_path, 2, "tpu", 659.0, ici=0.02)
        assert br.main(
            ["--dir", str(tmp_path), "--metric", "mesh.ici_share"]
        ) == 0
        self._write_ici_round(tmp_path, 3, "tpu", 659.0, ici=0.3)
        assert br.main(
            ["--dir", str(tmp_path), "--metric", "mesh.ici_share"]
        ) == 1


class TestSmallopsIopsGates:
    """The promoted IOPS metrics (binary wire protocol PR):
    smallops.ops_per_sec (ratio, higher is better) and
    smallops.op_p99 -> op_p99_ms (lower is better, 0.5ms additive
    slack) gate next to the already-armed smallops.header_share."""

    def _round(self, tmp_path, n, phase, value, ops=None, p99=None,
               share=None):
        line = {"metric": "m", "value": value, "unit": "GB/s",
                "phase": phase}
        so = {}
        if ops is not None:
            so["ops_per_sec"] = ops
        if p99 is not None:
            so["op_p99_ms"] = p99
        if share is not None:
            so["header_share"] = share
        if so:
            line["smallops"] = so
        (tmp_path / f"BENCH_r{n:02d}.json").write_text(
            json.dumps({"n": n, "rc": 0, "parsed": line})
        )

    def test_ops_per_sec_2x_drop_fails(self, tmp_path):
        br = _load_tool()
        self._round(tmp_path, 1, "tpu", 660.0, ops=200.0)
        self._round(tmp_path, 2, "tpu", 661.0, ops=90.0)
        rep = br.compare(br.load_rounds(str(tmp_path)),
                         metric="smallops.ops_per_sec", threshold=0.5)
        assert rep["comparable"] and rep["regression"] is True
        for metric in ("smallops.ops_per_sec", "smallops_ops_per_sec"):
            assert br.main(
                ["--dir", str(tmp_path), "--metric", metric]
            ) == 1, metric

    def test_ops_per_sec_improvement_and_wobble_pass(self, tmp_path):
        br = _load_tool()
        self._round(tmp_path, 1, "tpu", 660.0, ops=140.0)
        self._round(tmp_path, 2, "tpu", 661.0, ops=190.0)
        assert br.main(
            ["--dir", str(tmp_path), "--metric", "smallops.ops_per_sec"]
        ) == 0

    def test_op_p99_growth_is_the_regression(self, tmp_path):
        """Lower is better with the 0.5ms slack: 5ms -> 30ms fails,
        5ms -> 7ms passes (jitter inside the budget)."""
        br = _load_tool()
        self._round(tmp_path, 1, "tpu", 660.0, p99=5.0)
        self._round(tmp_path, 2, "tpu", 661.0, p99=30.0)
        rep = br.compare(br.load_rounds(str(tmp_path)),
                         metric="smallops.op_p99")
        assert rep["lower_is_better"] and rep["regression"] is True
        for metric in ("smallops.op_p99", "smallops_op_p99",
                       "smallops.op_p99_ms"):
            assert br.main(
                ["--dir", str(tmp_path), "--metric", metric]
            ) == 1, metric
        self._round(tmp_path, 3, "tpu", 661.0, p99=7.0)
        rep = br.compare(br.load_rounds(str(tmp_path)),
                         metric="smallops.op_p99")
        # best prior is still 5ms: (5+0.5)/(7+0.5) = 0.73 >= 0.5
        assert not rep["regression"]

    def test_iops_gates_clean_skip_until_two_rounds_carry_them(
        self, tmp_path
    ):
        """ISSUE acceptance: armed now, harmless until the capture has
        landed in two rounds — promotion can never fail a round
        retroactively."""
        br = _load_tool()
        self._round(tmp_path, 1, "tpu", 660.0)  # legacy round
        self._round(tmp_path, 2, "tpu", 650.0, ops=190.0, p99=6.0,
                    share=0.03)
        for metric in ("smallops.ops_per_sec", "smallops.op_p99",
                       "smallops.header_share"):
            rep = br.compare(br.load_rounds(str(tmp_path)),
                             metric=metric)
            assert rep["comparable"] is False, metric
            assert br.main(
                ["--dir", str(tmp_path), "--metric", metric]
            ) == 0, metric


class TestChurnGates:
    """ISSUE 15: churn.protection (live-storm client protection factor,
    ratio, 20% budget) and churn.recovery_gbps (storm recovery
    throughput, 2x budget) — registered with aliases and clean-skip
    semantics exactly like the accel/mesh metrics."""

    def _round(self, tmp_path, n, phase, value, protection=None,
               gbps=None):
        line = {"metric": "m", "value": value, "unit": "GB/s",
                "phase": phase}
        ch = {}
        if protection is not None:
            ch["protection"] = protection
        if gbps is not None:
            ch["recovery_gbps"] = gbps
        if ch:
            line["churn"] = ch
        (tmp_path / f"BENCH_r{n:02d}.json").write_text(
            json.dumps({"n": n, "rc": 0, "parsed": line})
        )

    def test_protection_collapse_fails(self, tmp_path):
        """The 2.5x budget (0.4): a protection factor collapsing from
        a healthy ~2x to well under 1.0 is the regression."""
        br = _load_tool()
        self._round(tmp_path, 1, "tpu", 660.0, protection=2.0)
        self._round(tmp_path, 2, "tpu", 661.0, protection=0.7)
        rep = br.compare(br.load_rounds(str(tmp_path)),
                         metric="churn.protection", threshold=0.4)
        assert rep["comparable"] and rep["regression"] is True
        for metric in ("churn.protection", "churn_protection"):
            assert br.main(
                ["--dir", str(tmp_path), "--metric", metric]
            ) == 1, metric

    def test_protection_wobble_and_improvement_pass(self, tmp_path):
        """The measured best-of-2 spread (1.3..2.7 on an idle host)
        stays inside the budget."""
        br = _load_tool()
        self._round(tmp_path, 1, "tpu", 660.0, protection=2.7)
        self._round(tmp_path, 2, "tpu", 661.0, protection=1.3)
        assert br.main(
            ["--dir", str(tmp_path), "--metric", "churn.protection"]
        ) == 0
        self._round(tmp_path, 3, "tpu", 661.0, protection=3.0)
        assert br.main(
            ["--dir", str(tmp_path), "--metric", "churn.protection"]
        ) == 0

    def test_recovery_gbps_2x_drop_fails(self, tmp_path):
        br = _load_tool()
        self._round(tmp_path, 1, "tpu", 660.0, gbps=0.4)
        self._round(tmp_path, 2, "tpu", 661.0, gbps=0.1)
        for metric in ("churn.recovery_gbps", "churn_recovery_gbps"):
            assert br.main(
                ["--dir", str(tmp_path), "--metric", metric]
            ) == 1, metric

    def test_churn_gates_clean_skip_until_two_rounds_carry_them(
        self, tmp_path
    ):
        """Armed now, harmless until the churn phase has landed in two
        rounds — promotion can never fail a round retroactively."""
        br = _load_tool()
        self._round(tmp_path, 1, "tpu", 660.0)  # legacy round
        self._round(tmp_path, 2, "tpu", 650.0, protection=1.8,
                    gbps=0.3)
        for metric in ("churn.protection", "churn.recovery_gbps"):
            rep = br.compare(br.load_rounds(str(tmp_path)),
                             metric=metric)
            assert rep["comparable"] is False, metric
            assert br.main(
                ["--dir", str(tmp_path), "--metric", metric]
            ) == 0, metric


def _load_bench():
    path = pathlib.Path(__file__).parent.parent / "bench.py"
    spec = importlib.util.spec_from_file_location("bench", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _run_bench(fault=None, budget=60):
    env = dict(os.environ)
    env.pop("CEPH_TPU_BENCH_FAULT", None)
    if fault:
        env["CEPH_TPU_BENCH_FAULT"] = fault
    bench = str(pathlib.Path(__file__).parent.parent / "bench.py")
    r = subprocess.run(
        [sys.executable, bench, "--budget", str(budget)],
        env=env, capture_output=True, text=True, timeout=240,
    )
    lines = [json.loads(ln) for ln in r.stdout.splitlines() if ln.strip()]
    assert lines, r.stderr[-2000:]
    return r, lines


def _assert_not_measured(r, lines):
    """A run with no chip result: non-zero exit, a parseable final
    error line under the device metric with no value, and no host
    number anywhere under the device metric's name."""
    assert r.returncode != 0, r.stderr[-2000:]
    final = lines[-1]
    assert final["metric"].endswith("(TPU)")
    assert final["value"] is None and final["phase"] == "no-device"
    assert "encode_gbps" not in final
    for line in lines[:-1]:
        assert line["metric"] != final["metric"], line
    assert lines[0]["phase"] == "native" and lines[0]["value"] > 0
    return final


class TestChildBackendDeath:
    def test_backend_abort_fails_loud(self):
        """The device child dies in backend start-up (the crash inside
        jax.devices() -> xla_bridge.backends): the run exits non-zero
        with an error line naming the dead child and the phase record,
        never a host number under the TPU metric."""
        r, lines = _run_bench("backend-death")
        final = _assert_not_measured(r, lines)
        assert "died" in final["error"], final["error"]
        phases = {p["phase"]: p for p in final["phases"]}
        assert phases["native"]["status"] == "ok"
        assert phases["device"]["status"].startswith("device child died")

    def test_no_tpu_fails_loud(self):
        """On a host whose jax finds no TPU the device child refuses to
        measure and the run exits non-zero."""
        r, lines = _run_bench()
        final = _assert_not_measured(r, lines)
        assert final["error"].startswith("no TPU"), final["error"]


class TestDeviceDeathMidPhase:
    def test_every_engine_lost_is_an_error_line(self):
        """The device dies AFTER acquisition, mid-headline, and takes
        every engine with it (the CPU's only one is XLA): the headline
        raises with the engine_failover verdicts, and the final line is
        the error line carrying them — no value under the TPU metric."""
        import time

        import pytest

        bench = _load_bench()
        bench._DEVICE_DEATH_ARMED = True
        with pytest.raises(RuntimeError) as ei:
            bench.bench_device(1, True, time.time() + 120)
        verdicts = ei.value.engine_failovers
        assert verdicts[0]["engine"] == "xla"
        assert "Device lost" in verdicts[0]["error"]
        final = bench.assemble(
            {"engine_failover": {"failovers": verdicts}},
            {"combined_gbps": 5.0}, None, {}, "ok")
        assert final["value"] is None and final["phase"] == "no-device"
        assert final["engine_failover"] == verdicts
        assert "died mid-headline" in final["error"]
