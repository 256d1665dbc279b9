"""Chaos acceptance: injected kills + resume/replay through the Session.

The acceptance drill for DESIGN.md §16: a FailureInjector kills the solve
mid-run and a serve batch mid-trace; the resumed solve must produce
byte-identical rankings (``max|Δ| == 0``), the serve trace must complete
with every future answered, and the ``ft.*`` counters must land in both
the telemetry digest and the serve artifact roll-up.
"""
import numpy as np
import pytest

from repro.api import RunSpec, Session, SpecError
from repro.ft import TransientWorkerError


def _spec(run_id, **ft):
    return RunSpec.from_dict(
        {
            "run_id": run_id,
            "network": {
                "kind": "scenario",
                "name": "streaming_chaos",
                "scale": 0.3,
                "seed": 5,
            },
            "solve": {
                "alg": "dhlp2",
                "sigma": 1e-4,
                "seed_mode": "fixed",
                "backend": "dense",
                "top_k": 5,
            },
            "serve": {
                "trace": "diurnal",
                "rate_qps": 60.0,
                "horizon_s": 1.0,
                "time_scale": 100.0,  # >1 compresses the replay clock
                "max_batch": 16,
                "top_k": 5,
            },
            "obs": {"level": "metrics"},
            "ft": {
                "interval": 2,
                "keep_last": 3,
                "max_retries": 2,
                "backoff_s": 0.0,
                **ft,
            },
        }
    )


def _quiet(*a, **k):
    pass


class TestSolveKillResume:
    def test_resumed_rankings_byte_identical(self, tmp_path):
        root = str(tmp_path)
        clean = Session(_spec("clean"), results_root=root).run(
            sections=["solve"], echo=_quiet
        )[0]

        spec = _spec("chaos", inject_solve_fault=[3])
        with pytest.raises(TransientWorkerError):
            Session(spec, results_root=root).run(
                sections=["solve"], echo=_quiet
            )

        # a fresh Session on the same spec + results root IS the resume
        # path (`repro run --resume` reloads the stored spec.json)
        resumed = Session(spec, results_root=root).run(
            sections=["solve"], echo=_quiet
        )[0]
        assert resumed.ft["resumed_from"] is not None
        assert resumed.ranking["candidates"] == clean.ranking["candidates"]
        assert resumed.ranking["scores"] == clean.ranking["scores"]
        assert (
            float(np.max(np.abs(resumed.F - clean.F))) == 0.0
        )  # f64, bit-exact
        assert resumed.outer_iters == clean.outer_iters

    def test_unsupported_engine_rejected(self, tmp_path, monkeypatch):
        # spec validation already pins alg/mode/seed_mode, so the session
        # guard only fires for an engine without the round contract —
        # simulate one to keep the belt-and-suspenders path covered
        import repro.ft.solve as ft_solve

        monkeypatch.setattr(
            ft_solve, "supports_checkpointed", lambda engine: False
        )
        sess = Session(_spec("badengine"), results_root=str(tmp_path))
        with pytest.raises(SpecError, match="round"):
            sess.solve()


class TestServeKillReplay:
    def test_trace_completes_with_guarded_replay(self, tmp_path):
        spec = _spec("servechaos", inject_serve_fault=[1])
        arts = Session(spec, results_root=str(tmp_path)).run(echo=_quiet)
        serve = next(a for a in arts if a.kind == "serve")
        # the injected fault was retried; every query was answered
        assert serve.ft["retries"] >= 1
        assert serve.ft["injected_faults"] == [1]
        assert serve.report["queries"] > 0
        assert serve.ft["checkpoints"] >= 1
        # the roll-up is in the written JSON summary too
        assert "ft" in serve.summary()

    def test_restore_path_replays_batch(self, tmp_path):
        # exhaust the retry budget (fault on the first attempt AND both
        # retries) so the guard takes the restore+replay path
        spec = _spec(
            "restorechaos", max_retries=1, inject_serve_fault=[1, 2]
        )
        arts = Session(spec, results_root=str(tmp_path)).run(echo=_quiet)
        serve = next(a for a in arts if a.kind == "serve")
        assert serve.ft["restores"] == 1
        assert serve.report["queries"] > 0


class TestServeFailureExit:
    # a fault on the first attempt, no retry budget, and a second fault on
    # the restore's replay: the batch fails and its queries go unanswered
    _FT = {"max_retries": 0, "inject_serve_fault": [1, 2]}

    def test_failed_batch_counted_in_artifact(self, tmp_path):
        spec = _spec("servefail", **self._FT)
        arts = Session(spec, results_root=str(tmp_path)).run(echo=_quiet)
        serve = next(a for a in arts if a.kind == "serve")
        assert serve.failures > 0
        assert serve.failures == serve.report["failed"]
        assert serve.summary()["failures"] == serve.failures
        assert serve.report["queries"] > 0  # the other batches answered

    def test_failed_serve_queries_make_repro_run_exit_1(self, tmp_path, capsys):
        import json

        from repro.launch.cli import run_main

        path = tmp_path / "spec.json"
        path.write_text(json.dumps(_spec("servefailcli", **self._FT).to_dict()))
        rc = run_main([str(path), "--results-root", str(tmp_path)])
        assert rc == 1
        assert "[serve] FAILED:" in capsys.readouterr().out

    def test_clean_serve_exits_0(self, tmp_path):
        import json

        from repro.launch.cli import run_main

        path = tmp_path / "spec.json"
        path.write_text(json.dumps(_spec("servecleancli").to_dict()))
        assert run_main([str(path), "--results-root", str(tmp_path)]) == 0


class TestTelemetryRollup:
    def test_digest_carries_ft_block(self, tmp_path):
        from repro.obs.summary import load_dir, render, summarize

        root = str(tmp_path)
        spec = _spec("digest", inject_serve_fault=[1])
        Session(spec, results_root=root).run(echo=_quiet)
        meta, events, metrics = load_dir(f"{root}/digest/telemetry")
        digest = summarize(meta, events, metrics)
        assert digest["ft"]["checkpoints"] >= 1
        assert digest["ft"]["retries"] >= 1
        assert any(
            line.startswith("ft:") for line in render(digest).splitlines()
        )
