"""The trace reduction on small traces.

`data/synthetic.xplane.pb` is an XSpace written by hand (one host plane,
one `/device:TPU:0` plane) whose answer is known:

* window: `bench.window` from 100 to 1100 us;
* device ops (us): 50-150 and 200-300 `kernel_a`, 250-350 and 1050-1200
  `kernel_b`, 900-1000 `copy`, plus a `Steps` line the reduction ignores;
* host: a thread-long `Thread` event, and `PjitFunction(...)` dispatches
  at 300-350 and 600-650.

Clipped to the window the busy union is 50 + 150 + 100 + 50 = 350 us; the
gaps are 150-200 (midpoint under `Thread` alone), 350-900 (midpoint 625,
inside a dispatch) and 1000-1050 (`Thread`).
"""
from pathlib import Path

import pytest

from harness.xplane import find_xplane, reduce_trace

DATA = Path(__file__).parent / "data"


def test_synthetic_trace_reduces_exactly():
    r = reduce_trace(DATA / "synthetic.xplane.pb")
    assert r["n_devices"] == 1
    assert r["window_s"] == pytest.approx(1000e-6)
    assert r["busy_s"] == pytest.approx(350e-6)
    assert dict(r["device_ops"]) == pytest.approx(
        {"kernel_a": 150e-6, "kernel_b": 150e-6, "copy": 100e-6})
    assert dict(r["idle_gaps"]) == pytest.approx(
        {"PjitFunction(_fused_padded)": 550e-6, "Thread": 100e-6})


def test_idle_and_busy_add_up_to_the_window():
    for path in sorted(DATA.glob("*.xplane.pb")):
        r = reduce_trace(path)
        idle = sum(s for _, s in r["idle_gaps"])
        assert 0 < r["busy_s"] <= r["window_s"]
        assert r["busy_s"] + idle == pytest.approx(r["window_s"], rel=1e-6)


def test_find_xplane(tmp_path):
    with pytest.raises(FileNotFoundError):
        find_xplane(tmp_path)
    d = tmp_path / "plugins" / "profile" / "run1"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(b"")
    assert find_xplane(tmp_path).name == "host.xplane.pb"
