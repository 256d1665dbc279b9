"""The program's spans on the profiler's clock (DESIGN.md §14.4).

A recorded span opens ``jax.profiler.TraceAnnotation("repro.<kind>")``
beside its JSONL record; level ``off`` opens none.  The engines and the
serve tier record their steps through the Telemetry the Session hands
them, and the scheduler stamps each answer's queue wait at every level.
"""
import threading

import numpy as np
import pytest

from repro.obs import Telemetry


@pytest.fixture
def annotations(monkeypatch):
    """Names of the profiler annotations opened, in order, and whether
    each was closed."""
    import jax.profiler

    opened = []

    class Recorder:
        def __init__(self, name, **kw):
            self.entry = [name, False]
            opened.append(self.entry)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            self.entry[1] = True

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Recorder)
    return opened


def _net(seed=0, n=(18, 12, 9)):
    from repro.core import HeteroNetwork

    rng = np.random.default_rng(seed)
    P = []
    for ni in n:
        a = (rng.random((ni, ni)) < 0.35) * rng.random((ni, ni))
        np.fill_diagonal(a, 0)
        P.append((a + a.T) / 2)
    R = {
        (i, j): (rng.random((n[i], n[j])) < 0.3).astype(float)
        for (i, j) in [(0, 1), (0, 2), (1, 2)]
    }
    return HeteroNetwork(P=P, R=R)


def _kinds(tel):
    return [e["span"] for e in tel.events() if e["kind"] == "span"]


class TestProfilerBridge:
    def test_trace_span_opens_annotation_and_keeps_record(self, annotations):
        tel = Telemetry("trace")
        with tel.span("phase", "serve"):
            with tel.trace_span("serve.round", "round:3"):
                pass
        assert annotations == [["repro.phase", True], ["repro.serve.round", True]]
        recs = {e["span"]: e for e in tel.events()}
        assert recs["serve.round"]["name"] == "round:3"
        assert recs["serve.round"]["parent"] == recs["phase"]["id"]

    def test_error_closes_annotation(self, annotations):
        tel = Telemetry("trace")
        with pytest.raises(RuntimeError):
            with tel.trace_span("engine.fetch"):
                raise RuntimeError("x")
        assert annotations == [["repro.engine.fetch", True]]
        assert tel.events()[0]["status"] == "error"

    def test_off_opens_none(self, annotations):
        tel = Telemetry("off")
        with tel.span("run"):
            with tel.trace_span("engine.loop"):
                pass
        assert annotations == [] and tel.suppressed == 2

    def test_metrics_level_annotates_structural_spans_only(self, annotations):
        tel = Telemetry("metrics")
        with tel.span("phase", "solve"):
            with tel.trace_span("engine.loop"):
                pass
        assert annotations == [["repro.phase", True]]


def _sparse(tel):
    from repro.core import LPConfig
    from repro.engine import make_engine

    eng = make_engine("sparse", LPConfig(alg="dhlp2", seed_mode="fixed", sigma=1e-6))
    eng.telemetry = tel
    return eng


class TestEngineSpans:
    def test_no_telemetry_records_nothing(self, annotations):
        eng = _sparse(None)
        res = eng.run(_net(), seeds=np.eye(39)[:, :4])
        assert res.F.shape == (39, 4) and annotations == []

    def test_prepare_and_solve_steps(self, annotations):
        tel = Telemetry("trace")
        eng = _sparse(tel)
        net = _net()
        eng.run(net, seeds=np.eye(39)[:, :4])
        eng.run(net, seeds=np.eye(39)[:, 4:6])  # cached operator: no prepare
        assert _kinds(tel) == [
            "engine.prepare.csr",
            "engine.prepare.upload",
            "engine.prepare",
            *["engine.upload", "engine.loop", "engine.fetch"] * 2,
        ]
        recs = {e["span"]: e for e in tel.events()}
        assert recs["engine.prepare.csr"]["parent"] == recs["engine.prepare"]["id"]
        assert [a[0] for a in annotations][:3] == [
            "repro.engine.prepare",
            "repro.engine.prepare.csr",
            "repro.engine.prepare.upload",
        ]

    def test_session_hands_its_telemetry_to_its_engines(self):
        from repro.api import NetworkSpec, ObsSpec, RunSpec, Session

        spec = RunSpec(
            network=NetworkSpec(kind="drugnet", params=dict(n_drug=12, n_disease=9, n_target=7)),
            obs=ObsSpec(level="trace"),
        )
        sess = Session(spec)
        assert sess.engine.telemetry is sess.telemetry
        assert sess.eval_engine.telemetry is sess.telemetry


class TestServeSpans:
    def _engine(self, tel):
        from repro.core import LPConfig
        from repro.serve import LPServeEngine, ServeConfig

        cfg = ServeConfig(
            lp=LPConfig(alg="dhlp2", seed_mode="fixed", sigma=1e-6),
            engine="sparse",
            early_exit=True,
            pipeline_depth=2,
            max_wait_s=1e-3,
        )
        return LPServeEngine(_net(), cfg, telemetry=tel)

    def test_batch_and_delta_steps(self):
        from repro.core.network import GraphDelta
        from repro.serve import QuerySpec

        tel = Telemetry("trace")
        eng = self._engine(tel)
        eng.start()
        try:
            futs = [eng.submit(QuerySpec(entity=e, target_type=2, top_k=3)) for e in range(4)]
            for f in futs:
                f.result(timeout=60)
            eng.apply_delta(GraphDelta(assoc=(((0, 2), 1, 2, 1.0),)))
            eng.submit(QuerySpec(entity=5, target_type=2, top_k=3)).result(timeout=60)
        finally:
            eng.stop()
        kinds = set(_kinds(tel))
        assert {
            "batch", "serve.assemble", "serve.lock_wait", "serve.round", "serve.rank",
            "serve.delta.lock_wait", "serve.delta.normalize", "serve.delta.invalidate",
            "engine.prepare", "engine.prepare.csr", "engine.prepare.upload",
        } <= kinds
        recs = tel.events()
        batches = {e["id"] for e in recs if e.get("span") == "batch"}
        rounds = [e for e in recs if e.get("span") == "serve.round"]
        assert rounds and all(e["parent"] in batches for e in rounds)

    def test_queued_s_stamped_at_every_level(self):
        from repro.serve import QuerySpec

        for tel in (None, Telemetry("off")):
            eng = self._engine(tel)
            eng.start()
            try:
                futs = [
                    eng.submit(QuerySpec(entity=e, target_type=2, top_k=3)) for e in range(6)
                ]
                results = [f.result(timeout=60) for f in futs]
            finally:
                eng.stop()
            assert all(0.0 <= r.queued_s <= r.latency_s for r in results)

    def test_queued_s_ends_before_the_engine_lock(self):
        """A batch held behind the engine lock waits there, not in the
        scheduler: its queue wait excludes the lock wait."""
        from repro.serve import QuerySpec

        eng = self._engine(None)
        eng.start()
        try:
            eng.submit(QuerySpec(entity=0, target_type=2, top_k=3)).result(timeout=60)
            release = threading.Event()
            with eng._lock:
                fut = eng.submit(QuerySpec(entity=1, target_type=2, top_k=3))
                threading.Timer(0.3, release.set).start()
                release.wait(5)
            res = fut.result(timeout=60)
        finally:
            eng.stop()
        assert res.latency_s >= 0.25
        assert res.queued_s < res.latency_s - 0.2
