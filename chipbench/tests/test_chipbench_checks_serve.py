"""The serve cell's check: a sound run passes, the bfloat16 control and
each fault the cell can have come out not correct.

Runs the rest of a run on the CPU at a tiny size, the chip guard skipped.
The faults are planted under the timed path, in the program's serve engine.
The serve cell runs on one chip, so there is no exchange to leave out.
"""
import numpy as np
import pytest

from chipbench import harness
from chipbench.tests import tiny


def _fault(monkeypatch, kind):
    harness.setup_env()
    from repro.serve.engine import LPServeEngine
    from repro.engine.sparse import SparseCSREngine

    if kind == "unchanged":  # a round that hands back its state
        monkeypatch.setattr(
            SparseCSREngine, "round_with_residual",
            lambda self, op, F, Y: (np.asarray(F, np.float64), np.zeros(np.shape(F)[1])),
        )
    elif kind == "half":  # half of each batch's columns left unsolved (a
        # batch of one leaves its column out)
        real = LPServeEngine._solve_early_exit

        def half(self, state, Y, F0):
            res = real(self, state, Y, F0)
            k = res.F.shape[1]
            res.F[:, k // 2 :] = F0[:, k // 2 :]
            return res

        monkeypatch.setattr(LPServeEngine, "_solve_early_exit", half)
    elif kind == "altered":  # each answer's last candidate swapped, where it
        # is ranked, for the block's worst one with its own true score
        real_rank = LPServeEngine._rank

        def rank(self, spec, col, source, rounds, state):
            res = real_rank(self, spec, col, source, rounds, state)
            block = np.asarray(col[res.target_offset : res.target_offset + state.sizes[spec.target_type]])
            worst = int(np.argmin(block))
            res.candidates = np.append(res.candidates[:-1], worst)
            res.scores = np.append(res.scores[:-1], block[worst])
            return res

        monkeypatch.setattr(LPServeEngine, "_rank", rank)


def test_sound_run_is_correct():
    line = tiny.run(tiny.context("serve-dtinet-live"))
    assert line["correct"], line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"setup_s", "query_p95_ms", "delta_fresh_ms"}


def test_per_layer_counters_on_a_traced_run():
    line = tiny.run(tiny.context("serve-dtinet-live", trace=True))
    assert line["correct"], line["checks"]
    m = line["metrics"]
    # the CPU has no TPU plane: the trace readers find nothing and stay out
    assert "idle_share.serve" not in m
    assert m["window_compiles.serve"]["value"] == 0
    assert 0 <= m["cache_hit_share"]["value"] <= 100 and m["batch_size_mean"]["value"] >= 1


def test_bf16_control_is_not_correct():
    line = tiny.run(tiny.context("serve-dtinet-live", control=True))
    assert not line["correct"]
    assert line["checks"]["answer_err"]["value"] > line["checks"]["answer_err"]["limit"]


@pytest.mark.parametrize("kind", ["unchanged", "half", "altered"])
def test_fault_is_not_correct(monkeypatch, kind):
    _fault(monkeypatch, kind)
    line = tiny.run(tiny.context("serve-dtinet-live"))
    assert not line["correct"], (kind, line["checks"])
    if kind == "altered":  # true scores, wrong candidates: the rank part
        assert line["checks"]["answer_err"]["value"] > line["checks"]["answer_err"]["limit"]
