"""The trace reduction (benchmark/trace.py) on a recorded v5e trace and
on planes built by hand."""

import pathlib
from types import SimpleNamespace as NS

import pytest

from benchmark import trace

FIXTURE = pathlib.Path(__file__).parent / "fixtures" / "v5e_rs83_encode.xplane.pb"


def test_recorded_v5e_trace():
    """Two 2 MiB RS(8,3) encodes recorded on a TPU v5 lite
    (benchmark/record_trace.py).  The kernel is the one op on the
    device's "XLA Ops" line; the device clock runs ~1-2 ms ahead of the
    host's here, so the first call's op lands before bench.window opens
    and only the second counts."""
    s = trace.summarize_file(str(FIXTURE))
    assert s["chips"] == 1
    assert s["op_events"] == 1
    assert s["device_ops"] == [["fn.1", pytest.approx(8.792e-6)]]
    assert s["busy_s"] == pytest.approx(8.792e-6)
    assert s["window_s"] == pytest.approx(0.0050287)
    assert [g[0] for g in s["idle_gaps"]] == ["codec_call", "codec_call"]
    assert sum(g[1] for g in s["idle_gaps"]) + s["busy_s"] == \
        pytest.approx(s["window_s"])


def _ev(name, start, end):
    return NS(name=name, start_ns=start, end_ns=end)


def _pd(devices, host):
    planes = [NS(name=f"/device:TPU:{i}", lines=[NS(name="XLA Ops", events=ops),
                                                 NS(name="XLA Modules", events=[])])
              for i, ops in enumerate(devices)]
    planes.append(NS(name="/host:CPU", lines=[NS(name="python3", events=host)]))
    return NS(planes=planes)


def test_union_clip_gaps_and_chips():
    host = [_ev("bench.window", 100, 1100), _ev("bench.call", 100, 600),
            _ev("bench.call", 600, 1100), _ev("other", 0, 2000)]
    chip0 = [_ev("%a.1 = u32[2] fusion(x)", 50, 200),   # clipped to 100
             _ev("%b = u32[2] fusion(x)", 150, 300),     # overlaps a
             _ev("%a.1 = u32[2] fusion(x)", 700, 800),
             _ev("%late = u32[2] fusion(x)", 1200, 1300)]  # outside
    chip1 = [_ev("%c = u32[2] fusion(x)", 100, 700)]
    s = trace.summarize(_pd([chip0, chip1], host))
    assert s["window_s"] == pytest.approx(1000e-9)
    # chip 0 busy [100, 300] + [700, 800] = 300 ns, chip 1 600 ns
    assert s["busy_s"] == pytest.approx(450e-9)
    assert s["device_ops"][0] == ["c", pytest.approx(600e-9)]
    assert dict(map(tuple, s["device_ops"]))["a.1"] == pytest.approx(200e-9)
    assert s["idle_gaps"] == [["call", pytest.approx(400e-9)],
                              ["call", pytest.approx(300e-9)]]
    assert s["op_events"] == 4


def test_no_window_or_no_device_is_an_error():
    with pytest.raises(ValueError, match="bench.window"):
        trace.summarize(_pd([[]], []))
    with pytest.raises(ValueError, match="TPU device plane"):
        trace.summarize(NS(planes=[NS(name="/host:CPU", lines=[
            NS(name="t", events=[_ev("bench.window", 0, 10)])])]))
