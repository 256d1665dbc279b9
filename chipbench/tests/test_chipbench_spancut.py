"""The span-aware trace reduction: hand-made intervals, the harness-only
case against ``tracecut``, and a chip trace recorded through the
program's Telemetry."""
from pathlib import Path

import pytest

from chipbench import spancut, tracecut

DATA = Path(__file__).resolve().parent / "data"
ms = 1e6  # ns


def _program(*threads):
    return [sp for k, thread in enumerate(threads) for sp in spancut.nest(thread, k)]


DEVICES = {
    "/device:TPU:0": [("fusion", 10 * ms, 20 * ms), ("copy", 45 * ms, 50 * ms),
                      ("fusion", 58 * ms, 60 * ms), ("fusion", 75 * ms, 80 * ms),
                      ("fusion", 100 * ms, 101 * ms)],
}
HARNESS = [
    ("chipbench.window", 0 * ms, 110 * ms),
    ("chipbench.job", 0 * ms, 50 * ms),
    ("chipbench.apply_delta", 50 * ms, 110 * ms),
]
PROGRAM = _program(
    [("repro.engine.upload", 2 * ms, 9 * ms), ("repro.engine.fetch", 21 * ms, 40 * ms)],
    [("repro.serve.delta.lock_wait", 50 * ms, 52 * ms),
     ("repro.serve.delta.normalize", 52 * ms, 58 * ms)],
    [("repro.batch", 55 * ms, 100 * ms), ("repro.serve.lock_wait", 55 * ms, 58 * ms),
     ("repro.engine.prepare", 58 * ms, 80 * ms),
     ("repro.engine.prepare.csr", 58 * ms, 75 * ms),
     ("repro.engine.prepare.upload", 75 * ms, 80 * ms),
     ("repro.serve.round", 80 * ms, 100 * ms)],
)


def test_nest_gives_depth_on_a_thread():
    depth = {n: d for n, _, _, d, _ in PROGRAM}
    assert depth["repro.batch"] == 0
    assert depth["repro.engine.prepare"] == depth["repro.serve.round"] == 1
    assert depth["repro.engine.prepare.csr"] == depth["repro.engine.prepare.upload"] == 2
    assert depth["repro.serve.delta.normalize"] == 0


def test_program_spans_name_the_gaps_inside_harness_spans():
    out = spancut.reduce_events(DEVICES, HARNESS, PROGRAM)
    # gaps 0-10 and 20-45 in job: upload 2-9 and fetch 21-40, the rest the
    # driver's own (job); 50-58: the delta's lock wait 50-52, then its
    # normalize 52-58 while the solver waits for the lock (55-58); 60-75:
    # the deepest, prepare.csr; 80-100: round; 101-110: no program span
    assert dict(out["idle_gaps"]) == pytest.approx({
        "job": 0.009, "engine.upload": 0.007, "engine.fetch": 0.019,
        "serve.delta.lock_wait": 0.002, "serve.delta.normalize": 0.006,
        "engine.prepare.csr": 0.015, "serve.round": 0.020, "apply_delta": 0.009,
    })
    assert out["busy_s"] == pytest.approx(0.023) and out["window_s"] == pytest.approx(0.110)
    assert sum(t for _, t in out["idle_gaps"]) == pytest.approx(0.087)


def test_spans_totals_and_busy_inside():
    spans = spancut.reduce_events(DEVICES, HARNESS, PROGRAM)["spans"]
    want = {  # count, seconds, device-busy seconds inside
        "engine.upload": (1, 0.007, 0.0), "engine.fetch": (1, 0.019, 0.0),
        "serve.delta.lock_wait": (1, 0.002, 0.0), "serve.delta.normalize": (1, 0.006, 0.0),
        "batch": (1, 0.045, 0.007), "serve.lock_wait": (1, 0.003, 0.0),
        "engine.prepare": (1, 0.022, 0.007), "engine.prepare.csr": (1, 0.017, 0.002),
        "engine.prepare.upload": (1, 0.005, 0.005), "serve.round": (1, 0.020, 0.0),
    }
    assert set(spans) == set(want)
    for name, (count, seconds, busy) in want.items():
        assert spans[name]["count"] == count
        assert spans[name]["seconds"] == pytest.approx(seconds), name
        assert spans[name]["busy_s"] == pytest.approx(busy, abs=1e-12), name


def test_repeated_spans_count_and_clip_to_the_window():
    program = _program([("repro.serve.round", -5 * ms, 5 * ms),
                        ("repro.serve.round", 20 * ms, 30 * ms),
                        ("repro.serve.round", 105 * ms, 120 * ms),
                        ("repro.serve.round", 130 * ms, 140 * ms)])
    r = spancut.reduce_events(DEVICES, HARNESS, program)["spans"]["serve.round"]
    assert r["count"] == 3
    assert r["seconds"] == pytest.approx(0.005 + 0.010 + 0.005)
    assert r["busy_s"] == pytest.approx(0.0)


def test_pieces_outside_program_spans_go_to_the_harness_span_there():
    devices = {"/device:TPU:0": [("fusion", 0 * ms, 10 * ms), ("fusion", 60 * ms, 70 * ms)]}
    harness = [("chipbench.window", 0 * ms, 70 * ms), ("chipbench.job", 0 * ms, 40 * ms),
               ("chipbench.host_wait", 40 * ms, 60 * ms)]
    program = _program([("repro.engine.fetch", 10 * ms, 25 * ms)])
    gaps = dict(spancut.reduce_events(devices, harness, program)["idle_gaps"])
    # the gap 10-60: the fetch, the rest of the job, the harness's wait
    assert gaps == pytest.approx({"engine.fetch": 0.015, "job": 0.015, "host_wait": 0.020})


def test_a_lock_wait_yields_to_work_on_another_thread():
    devices = {"/device:TPU:0": [("fusion", 10 * ms, 20 * ms)]}
    harness = [("chipbench.window", 0 * ms, 40 * ms)]
    program = _program(
        [("repro.batch", 0 * ms, 40 * ms), ("repro.serve.lock_wait", 0 * ms, 10 * ms),
         ("repro.serve.delta.lock_wait", 20 * ms, 30 * ms)],
        [("repro.serve.delta.normalize", 0 * ms, 10 * ms),
         ("repro.serve.assemble", 30 * ms, 40 * ms)],
    )
    gaps = dict(spancut.reduce_events(devices, harness, program)["idle_gaps"])
    # 0-10: the solver waits (depth 1) while the delta normalizes (depth 0);
    # 20-30: only a wait covers it; 30-40: batch (depth 0) and assemble
    # (depth 0, started last)
    assert gaps == pytest.approx({
        "serve.delta.normalize": 0.010, "serve.delta.lock_wait": 0.010, "serve.assemble": 0.010,
    })


def test_harness_only_trace_reduces_as_tracecut(monkeypatch):
    devices = {
        "/device:TPU:0": [("fusion", 10 * ms, 30 * ms), ("copy", 20 * ms, 40 * ms),
                          ("fusion", 70 * ms, 90 * ms)],
        "/device:TPU:1": [("fusion", 0 * ms, 100 * ms)],
    }
    harness = [
        ("chipbench.window", 0 * ms, 100 * ms),
        ("chipbench.job", 0 * ms, 45 * ms),
        ("chipbench.apply_delta", 45 * ms, 80 * ms),
    ]
    monkeypatch.setattr(tracecut, "read_events", lambda path: (devices, harness))
    out = spancut.reduce_events(devices, harness, [])
    assert out == tracecut.reduce("unused")
    assert "spans" not in out
    assert spancut.reduce_events({"/device:TPU:0": []}, [], []) is None


def test_harness_only_chip_trace_reduces_as_tracecut():
    path = str(DATA / "small.xplane.pb")
    assert spancut.reduce(path) == tracecut.reduce(path)


def test_chip_trace_of_program_spans():
    """Three jobs, each ``engine.upload`` / ``engine.loop`` / ``engine.fetch``
    recorded by the program's Telemetry inside ``chipbench.job``
    (``record_span_trace.py`` on one TPU v5e)."""
    path = str(DATA / "spans.xplane.pb")
    _, harness, program = spancut.read_events(path)
    jobs = [(s, e) for n, s, e in harness if n == "chipbench.job"]
    assert len(jobs) == 3 and len(program) == 9
    # the program's spans sit inside the harness's, on the one clock
    for _, s, e, depth, _ in program:
        assert depth == 0 and any(js <= s and e <= je for js, je in jobs)
    out = spancut.reduce(path)
    spans = out["spans"]
    assert {n: spans[n]["count"] for n in spans} == {
        "engine.upload": 3, "engine.loop": 3, "engine.fetch": 3,
    }
    # the device works inside engine.loop; the fetch's sleep is idle (the
    # margins allow the few milliseconds the device's clock may be off)
    assert spans["engine.loop"]["busy_s"] >= 0.8 * out["busy_s"] > 0.03
    assert spans["engine.fetch"]["busy_s"] <= 0.1 * spans["engine.fetch"]["seconds"]
    gaps = dict(out["idle_gaps"])
    assert gaps["engine.fetch"] >= 0.05 and gaps["job"] >= 0.025 and gaps["host_wait"] >= 0.05
    assert sum(gaps.values()) == pytest.approx(out["window_s"] - out["busy_s"], rel=1e-6)
