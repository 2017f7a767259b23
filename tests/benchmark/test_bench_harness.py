"""The harness (benchmark/run.py) on the CPU at tiny sizes, with the
device check injected: the last line's keys, a run without a TPU, a run
from a directory holding only the benchmark, and a cell added as files
only."""

import json
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

from benchmark import run

ROOT = pathlib.Path(__file__).parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CRUSH = json.loads((ROOT / "benchmark" / "configs" /
                    "ec-rs83.json").read_text())["crush"]
TINY = {
    "rs83.encode": {"objects_per_call": 2, "object_bytes": 65536},
    "rs83.crush_test": {"crush": dict(CRUSH, n_osds=64, result_max=3),
                        "inputs_per_call": 1024, "check_inputs": 64},
}


def cpu(n):
    import jax

    return jax.devices()[:n]


def execute(workload, seed=4294967311, seconds=1.0, trace=0, **kw):
    args = run.parse_args(["--workload", workload, "--seed", str(seed),
                           "--seconds", str(seconds), "--trace", str(trace)])
    return run.execute(args, device_check=cpu, teardown_after=True, **kw)


@pytest.mark.parametrize("workload", sorted(TINY))
def test_cell_at_tiny_size(workload, bench_root):
    line = execute(workload, overrides=TINY[workload], root=bench_root)
    assert list(line)[:3] == ["correct", "attempted", "failed"]
    assert list(line)[-1] == "checks"
    assert line["correct"] is True
    assert line["attempted"] > 0 and line["failed"] == 0
    bench = json.loads((pathlib.Path(bench_root) / "BENCHMARK.json")
                       .read_text())
    want = {m["name"] for m in run.metrics_for(bench, workload, "end_to_end")}
    assert set(line["metrics"]) == want and "setup_s" in want
    for m in line["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["count"] == 1
    assert line["compiles_in_window"] == 0
    for c in line["checks"].values():
        assert c == {"value": 0, "limit": 0}


def test_every_cell_reports_setup_another_end_to_end_and_a_layer():
    for cell in BENCH["workloads"]:
        e2e = [m["name"] for m in
               run.metrics_for(BENCH, cell["name"], "end_to_end")]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert run.metrics_for(BENCH, cell["name"], "per_layer", e2e)
        for f in (f"benchmark/configs/{cell['config']}.json",
                  f"benchmark/traffic/{cell['traffic']}.json"):
            assert (ROOT / f).is_file(), f
    for m in BENCH["per_layer"]:
        assert (ROOT / "benchmark" / "metrics" / f"{m['name']}.py").is_file()


def _cli(root, *extra, env_extra=None):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update({"JAX_PLATFORMS": "cpu", **(env_extra or {})})
    return subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "rs83.encode",
         "--seed", "1", "--seconds", "1", *extra],
        capture_output=True, text=True, timeout=300, cwd=root, env=env)


def _no_result(stdout):
    for line in stdout.splitlines():
        assert '"correct"' not in line and '"metrics"' not in line, line


def test_no_tpu_exits_nonzero_without_result():
    r = _cli(ROOT)
    assert r.returncode != 0
    assert "no TPU" in r.stderr
    _no_result(r.stdout)


def test_bare_checkout_of_the_benchmark_fails(tmp_path):
    """Only BENCHMARK.json and the files under ``paths``: no program."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in BENCH["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    r = _cli(tmp_path)
    assert r.returncode != 0
    _no_result(r.stdout)


def test_cell_added_as_files_only(tmp_path, monkeypatch):
    """A configuration, a traffic mix and a per-layer metric added as new
    files and entries, with no edit to any file the benchmark has."""
    shutil.copytree(ROOT / "benchmark", tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "ec-rs42", "source": "x",
                             "file": "benchmark/configs/ec-rs42.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "rs42.encode", "config": "ec-rs42",
                               "traffic": "encode_small", "chips": 1,
                               "why": "test"})
    bench["end_to_end"][0]["workloads"].append("rs42.encode")
    bench["per_layer"].append({
        "name": "calls_per_window.codec", "unit": "calls", "better": "higher",
        "source": "program_counter", "layer": "codec",
        "moves": "codec_GBps", "workloads": ["rs42.encode"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    (tmp_path / "benchmark" / "configs" / "ec-rs42.json").write_text(
        json.dumps({"profile": {"plugin": "isa", "technique":
                                "reed_sol_van", "k": "4", "m": "2"}}))
    (tmp_path / "benchmark" / "traffic" / "encode_small.json").write_text(
        json.dumps({"surface": "codec", "objects_per_call": 3,
                    "object_bytes": 16384, "distinct_batches": 2,
                    "kept": 4}))
    (tmp_path / "benchmark" / "metrics" / "calls_per_window.codec.py"
     ).write_text("def read(run):\n    return run.work['calls'] or None\n")
    line = execute("rs42.encode", root=str(tmp_path))
    assert line["correct"] is True
    assert set(line["metrics"]) == {"codec_GBps", "setup_s"}
    fake = {"busy_s": 0.5, "window_s": 1.0, "device_ops": [["fn.1", 0.5]],
            "idle_gaps": [["codec_call", 0.25]], "op_events": 3, "chips": 1}
    monkeypatch.setattr("benchmark.trace.summarize_dir", lambda d: fake)
    line = execute("rs42.encode", trace=1, root=str(tmp_path))
    assert set(line["metrics"]) == {"calls_per_window.codec"}
    assert line["metrics"]["calls_per_window.codec"]["value"] > 0
    assert line["device"]["busy_s"] == 0.5
    assert line["breakdown"]["idle_gaps"] == [["codec_call", 0.25]]
