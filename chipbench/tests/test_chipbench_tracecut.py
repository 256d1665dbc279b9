"""The trace reduction: on a small trace recorded on the chip, and on
hand-made intervals."""
from pathlib import Path

import pytest

from chipbench import tracecut

DATA = Path(__file__).resolve().parent / "data" / "small.xplane.pb"


def test_chip_trace(monkeypatch):
    """Three jobs of a jitted program with host sleeps between them, in a
    window span (``record_trace.py`` on one TPU v5e)."""
    out = tracecut.reduce(str(DATA))
    assert out is not None
    assert 0.0 < out["busy_s"] < out["window_s"] < 1.0
    assert out["device_ops"] and all(t > 0 for _, t in out["device_ops"])
    labels = [name for name, _ in out["idle_gaps"]]
    assert labels[0] == "host_wait"  # the sleeps are the longest idle time
    assert sum(t for _, t in out["idle_gaps"]) == pytest.approx(
        out["window_s"] - out["busy_s"], rel=1e-6
    )


def test_hand_made_intervals(monkeypatch):
    ms = 1e6  # ns
    devices = {
        "/device:TPU:0": [("fusion", 10 * ms, 30 * ms), ("copy", 20 * ms, 40 * ms),
                          ("fusion", 70 * ms, 90 * ms)],
        "/device:TPU:1": [("fusion", 0 * ms, 100 * ms)],
    }
    spans = [
        ("chipbench.window", 0 * ms, 100 * ms),
        ("chipbench.job", 0 * ms, 45 * ms),
        ("chipbench.apply_delta", 45 * ms, 80 * ms),
    ]
    monkeypatch.setattr(tracecut, "read_events", lambda path: (devices, spans))
    out = tracecut.reduce("unused")
    # chip 0 busy 10-40 and 70-90 (50 ms), chip 1 busy throughout (100 ms)
    assert out["busy_s"] == pytest.approx(0.075)
    assert out["window_s"] == pytest.approx(0.1)
    # chip 0 idle 0-10 (job), 40-70 (overlaps apply_delta 25 ms, job 5 ms:
    # apply_delta), 90-100 (no span: program); averaged over two chips
    gaps = dict(out["idle_gaps"])
    assert gaps == pytest.approx({"job": 0.005, "apply_delta": 0.015, "program": 0.005})
    ops = dict(out["device_ops"])
    assert ops["fusion"] == pytest.approx((0.04 + 0.1) / 2)


def test_nothing_to_read(monkeypatch):
    monkeypatch.setattr(tracecut, "read_events", lambda path: ({"/device:TPU:0": []}, []))
    assert tracecut.reduce("unused") is None
