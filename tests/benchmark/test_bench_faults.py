"""The correctness check fails when the timed path is broken underneath
a run (benchmark/control.py): the control, and each fault a one-chip
codec or placement cell can have.  Tiny sizes, CPU, device check
injected."""

import json
import pathlib

import pytest

from benchmark import control, run

ROOT = pathlib.Path(__file__).parents[2]
CONFIG = json.loads((ROOT / "benchmark" / "configs" / "ec-rs83.json")
                    .read_text())
CELLS = {
    "rs83.encode": ("codec", {"objects_per_call": 2,
                              "object_bytes": 65536}),
    "rs83.crush_test": ("crush", {
        "crush": dict(CONFIG["crush"], n_osds=64, result_max=3),
        "inputs_per_call": 1024, "check_inputs": 64}),
}


def cpu(n):
    import jax

    return jax.devices()[:n]


def broken(workload, what, root):
    surface, tiny = CELLS[workload]
    args = run.parse_args(["--workload", workload, "--seed", "11",
                           "--seconds", "0.5"])
    return run.execute(args, root=root, device_check=cpu, overrides=tiny,
                       under_test=control.under_test(surface, CONFIG, what))


@pytest.mark.parametrize("workload", sorted(CELLS))
@pytest.mark.parametrize("what", ["unchanged", "half", "altered"])
def test_fault_is_not_correct(workload, what, bench_root):
    line = broken(workload, what, bench_root)
    assert line["correct"] is False
    assert any(c["value"] > c["limit"] for c in line["checks"].values())


def test_codec_control_is_not_correct(bench_root):
    line = broken("rs83.encode", "control", bench_root)
    assert line["correct"] is False
    assert line["checks"]["parity_bytes_wrong"]["value"] > 0


def test_crush_control_is_not_correct(bench_root):
    """float32 straw2 draws without the exact re-run: on the chip about
    0.3 % of EC(8+3) mappings on the 1024-OSD map differ; here 128 OSDs
    and a 6-wide rule, where seed 11 shows a few."""
    args = run.parse_args(["--workload", "rs83.crush_test", "--seed", "11",
                           "--seconds", "0.5"])
    line = run.execute(
        args, root=bench_root, device_check=cpu,
        under_test=control.under_test(
            "crush", CONFIG, "control"),
        overrides={"crush": dict(CONFIG["crush"], n_osds=128, result_max=6),
                   "inputs_per_call": 4096, "check_inputs": 4096})
    assert line["checks"]["mappings_wrong"]["value"] > 0
