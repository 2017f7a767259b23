"""The served cell (rs83.rados_write) on the CPU at a tiny size: a sound
run, the control and the faults, each through the whole harness."""

import json
import pathlib

import pytest

from benchmark import control, run

ROOT = pathlib.Path(__file__).parents[2]
CONFIG = json.loads((ROOT / "benchmark" / "configs" / "ec-rs83.json")
                    .read_text())
TINY = {"object_bytes": 262144, "max_in_flight": 4, "warmup_bursts": [3, 1],
        "check_objects": 6, "blob_bytes": 1 << 21}


@pytest.fixture
def device_lane(monkeypatch):
    """Serve EC from the jax lane as on a chip (the CPU host would
    otherwise take the native C lane)."""
    from ceph_tpu.utils import native

    native.host_engine_active()
    monkeypatch.setattr(native, "_HOST_ACTIVE", False)


def cpu(n):
    import jax

    return jax.devices()[:n]


def served(what=None):
    args = run.parse_args(["--workload", "rs83.rados_write", "--seed",
                           "2147483659", "--seconds", "1.5"])
    seam = control.under_test("rados", CONFIG, what) if what else None
    return run.execute(args, device_check=cpu, overrides=TINY,
                       under_test=seam, teardown_after=True)


def test_sound_run(device_lane):
    line = served()
    assert line["correct"] is True, line["checks"]
    assert set(line["metrics"]) == {"client_MBps", "op_p95_ms", "setup_s"}
    assert line["attempted"] >= 4 and line["failed"] == 0
    assert line["metrics"]["client_MBps"]["value"] > 0
    assert line["metrics"]["op_p95_ms"]["value"] > 0


@pytest.mark.parametrize("what", ["control", "unchanged", "half",
                                  "altered"])
def test_broken_run_is_not_correct(device_lane, what):
    line = served(what)
    assert line["correct"] is False, line["checks"]
