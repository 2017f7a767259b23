"""chip_smoke.py's control flow on the CPU: every phase at a tiny size
with the device check injected (the chip run itself is
``python chip_smoke.py`` on a TPU)."""

import importlib.util
import pathlib
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).parent.parent

TINY = dict(codec_objects=2, object_scale=16, served_objects=6,
            served_object_bytes=64 << 10, served_in_flight=4,
            served_pg_num=8, crush_osds=192, crush_inputs=4096,
            crush_samples=64, mesh_stripes=4, mesh_chunk=4096)


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod  # dataclasses resolve it by name
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def device_lane(monkeypatch):
    """Serve EC from the jax lane as on a chip (the CPU host would
    otherwise take the native C lane), and keep the compile cache off."""
    from ceph_tpu.utils import arch, native

    native.host_engine_active()
    monkeypatch.setattr(native, "_HOST_ACTIVE", False)
    monkeypatch.setattr(arch, "configure_compile_cache", lambda: "off")


def _cpu(n):
    import jax

    return lambda: jax.devices()[:n]


def test_all_phases_at_tiny_size(smoke, device_lane, monkeypatch, capsys):
    # RS(2,1) for the served pool and the CRUSH EC rule: the CPU compile
    # of the 11-wide indep rule alone takes minutes
    monkeypatch.setattr(smoke, "RS83", {**smoke.RS83, "k": "2", "m": "1"})
    sizes = smoke.Sizes(**TINY)
    dev = smoke.run(sizes, device_check=_cpu(1))
    assert dev == {"platform": "cpu", "kind": "cpu", "count": 1}
    out = capsys.readouterr().out
    for phase in ("codec", "served", "crush", "trace"):
        assert f"phase {phase}: ok" in out
    assert out.count("byte-exact") >= 5 * 5  # encode + 4 decodes each
    assert "on the vectorized backend" in out
    assert '"native_direct": 0' in out


def test_four_chip_mesh_phase(smoke, device_lane, capsys):
    dev = smoke.run(smoke.Sizes(**TINY), four_chips=True,
                    device_check=_cpu(4))
    assert dev["count"] == 4
    out = capsys.readouterr().out
    assert "equal the one-chip engine's" in out
    assert out.count("spans 4 devices") == 2


def test_served_path_fails_on_host_lane(smoke, monkeypatch):
    """The served phase refuses a run that the native host lane served."""
    from ceph_tpu.utils import native

    native.host_engine_active()
    monkeypatch.setattr(native, "_HOST_ACTIVE", True)
    with pytest.raises(smoke.SmokeFailure, match="native_direct|device lane"):
        smoke.phase_served(smoke.Sizes(**{**TINY, "served_objects": 2}),
                           "cpu")


def test_no_tpu_exits_nonzero_without_result():
    r = subprocess.run(
        [sys.executable, str(ROOT / "chip_smoke.py")],
        capture_output=True, text=True, timeout=300, cwd=ROOT,
    )
    assert r.returncode != 0
    assert "no TPU" in r.stderr
    for line in r.stdout.splitlines():
        assert '"ok"' not in line, line
