"""The solve cells' check: sound runs pass, the bfloat16 control and each
fault the cell can have come out not correct.

Runs the rest of a run on the CPU at a tiny size, the chip guard skipped.
The faults are planted under the timed path, in the program's engine.
No solve cell spans chips, so there is no exchange to leave out.
"""
import numpy as np
import pytest

from chipbench import harness
from chipbench.tests import tiny


def _fault(monkeypatch, kind):
    harness.setup_env()
    from repro.core.solver import SolveResult
    from repro.engine.sparse import SparseCSREngine

    real = SparseCSREngine.solve

    def broken(self, op, Y, F0=None):
        res = real(self, op, Y, F0)
        F = np.array(res.F)
        if kind == "unchanged":  # the step hands back the state it was given
            F = np.array(Y, dtype=np.float64) if F0 is None else np.array(F0, np.float64)
        elif kind == "half":  # half the batch left out
            F[:, F.shape[1] // 2 :] = np.asarray(Y, np.float64)[:, F.shape[1] // 2 :]
        elif kind == "altered":  # one node's score altered in every column
            F[7] += 1e-3
        return SolveResult(F, res.outer_iters, res.inner_iters, res.converged, res.per_column_iters)

    monkeypatch.setattr(SparseCSREngine, "solve", broken)


@pytest.mark.parametrize("cell", ["solve-dtinet-sweep4096", "solve-dtinet-drugs"])
def test_sound_run_is_correct(cell):
    line = tiny.run(tiny.context(cell))
    assert line["correct"], line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"setup_s", "solve_cols_per_s"}
    assert list(line)[-1] == "checks"


@pytest.mark.parametrize("cell", ["solve-dtinet-sweep4096", "solve-dtinet-drugs"])
def test_bf16_control_is_not_correct(cell):
    line = tiny.run(tiny.context(cell, control=True))
    assert not line["correct"]
    assert line["checks"]["f_err"]["value"] > line["checks"]["f_err"]["limit"]


@pytest.mark.parametrize("kind", ["unchanged", "half", "altered"])
def test_fault_is_not_correct(monkeypatch, kind):
    _fault(monkeypatch, kind)
    line = tiny.run(tiny.context("solve-dtinet-sweep4096"))
    assert not line["correct"], (kind, line["checks"])
