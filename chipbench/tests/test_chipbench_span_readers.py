"""The readers of the program's spans and counter, on hand-made facts."""
from types import SimpleNamespace

import numpy as np
import pytest

from chipbench import harness

LIVE = {"delta_every_s": 10.0, "delta_first_s": 1.5, "warmup_s": 6.0}


def _read(metric, facts, traffic=None, seconds=51.0, attempted=0):
    ctx = SimpleNamespace(
        facts=facts, cell={"traffic": traffic or {}}, seconds=seconds, attempted=attempted
    )
    return harness.load_module("metrics", metric).read(ctx)


def _trace(**spans):
    return {"busy_s": 1.0, "window_s": 51.0, "spans": spans}


def test_queue_wait_ms():
    answers = [(i, SimpleNamespace(queued_s=w)) for i, w in enumerate(np.arange(1, 101) / 1e3)]
    assert _read("queue_wait_ms.serve", {"answers": answers}) == pytest.approx(95.05)
    # the parent's results carry no queue wait
    assert _read("queue_wait_ms.serve", {"answers": [(0, SimpleNamespace())]}) is None
    assert _read("queue_wait_ms.serve", {"answers": []}) is None
    assert _read("queue_wait_ms.serve", {}) is None


def test_reprepare_ms():
    # deltas due at 11.5, 21.5, 31.5, 41.5, 51.5 s: five in [6, 57)
    facts = {"trace": _trace(**{"engine.prepare": {"count": 10, "seconds": 30.0, "busy_s": 2.0}})}
    assert _read("reprepare_ms.serve", facts, LIVE) == pytest.approx(6000.0)
    # spans recorded but no rebuild in the window
    assert _read("reprepare_ms.serve", {"trace": _trace()}, LIVE) == 0.0
    assert _read("reprepare_ms.serve", {"trace": {"busy_s": 1.0}}, LIVE) is None
    assert _read("reprepare_ms.serve", {}, LIVE) is None
    assert _read("reprepare_ms.serve", facts, {**LIVE, "delta_first_s": 60.0}) is None


def test_round_host_share():
    facts = {"trace": _trace(**{"serve.round": {"count": 40, "seconds": 8.0, "busy_s": 2.0}})}
    assert _read("round_host_share.serve", facts) == pytest.approx(75.0)
    assert _read("round_host_share.serve", {"trace": _trace()}) is None
    assert _read("round_host_share.serve", {"trace": None}) is None


def test_fetch_ms():
    facts = {"trace": _trace(**{"engine.fetch": {"count": 46, "seconds": 4.6, "busy_s": 0.0}})}
    traffic = {"job": 708}
    assert _read("fetch_ms.solve", facts, traffic, attempted=46 * 708) == pytest.approx(100.0)
    assert _read("fetch_ms.solve", {"trace": _trace()}, traffic, attempted=708) is None
    assert _read("fetch_ms.solve", {}, traffic, attempted=708) is None
    assert _read("fetch_ms.solve", facts, traffic, attempted=0) is None
