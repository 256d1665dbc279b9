"""Session resolution, artifacts, deprecation shims, scenario disk cache.

Covers the ISSUE-5 acceptance surface: shim CLIs produce identical
artifacts to the spec-driven driver, one prepared engine is shared
across solve→serve, the scenario disk cache round-trips, and the
deleted ``sparse_coo`` backend stays gone from spec resolution.
"""
from __future__ import annotations

import json
import os

import numpy as np
import pytest

from repro.api import (
    EvalSpec,
    NetworkSpec,
    RunSpec,
    ServeSpec,
    Session,
    SolveSpec,
    SpecError,
)

TINY = {"n_drug": 30, "n_disease": 20, "n_target": 15}


def tiny_spec(**kw) -> RunSpec:
    return RunSpec(
        network=NetworkSpec(kind="drugnet", seed=0, params=dict(TINY)),
        solve=SolveSpec(
            alg="dhlp2", sigma=1e-3, backend="dense", top_k=5,
            rank_pair=(0, 2), **kw.pop("solve_kw", {}),
        ),
        **kw,
    )


# --------------------------------------------------------------- resolution
def test_session_solve_matches_direct_engine():
    from repro.core.solver import LPConfig
    from repro.data.drugnet import DrugNetSpec, make_drugnet
    from repro.engine import make_engine

    session = Session(tiny_spec())
    art = session.solve()

    net = make_drugnet(DrugNetSpec(seed=0, **TINY)).network
    cfg = LPConfig(alg="dhlp2", sigma=1e-3)
    res = make_engine("dense", cfg).run(net.normalize())
    np.testing.assert_array_equal(art.F, res.F)
    assert art.converged and art.outer_iters == res.outer_iters


def test_session_shares_one_prepared_engine_across_solve_and_serve():
    spec = tiny_spec(
        solve_kw={"seed_mode": "fixed"},
        serve=ServeSpec(requests=4, max_batch=4),
    )
    session = Session(spec)
    session.solve()
    prepared = session.engine._op_cache
    assert prepared is not None and prepared[1].norm is session.norm
    serve_engine = session.serve_engine()
    # the serve engine runs the SAME engine object on the SAME normalized
    # view — its first query hits the already-prepared operator
    assert serve_engine._engine is session.engine
    assert serve_engine.state.norm is session.norm
    from repro.serve import QuerySpec

    serve_engine.query(QuerySpec(entity=0, target_type=2, top_k=3))
    assert session.engine._op_cache is prepared  # no re-prepare happened


def test_session_auto_backend_resolution():
    spec = RunSpec(network=NetworkSpec(kind="drugnet", params=dict(TINY)))
    assert Session(spec).backend == "dense"  # tiny net → dense policy


def test_session_run_writes_artifacts(tmp_path):
    spec = tiny_spec(run_id="t-art", eval=EvalSpec(max_entities=4))
    arts = Session(spec, results_root=str(tmp_path)).run(echo=lambda _: None)
    run_dir = tmp_path / "t-art"
    assert (run_dir / "spec.json").exists()
    assert (run_dir / "solve.json").exists()
    assert (run_dir / "solve_outputs.npz").exists()
    assert (run_dir / "eval.json").exists()
    with open(run_dir / "spec.json") as f:
        assert RunSpec.from_dict(json.load(f)) == spec
    with open(run_dir / "eval.json") as f:
        metrics = json.load(f)["metrics"]
    assert 0.0 <= metrics["recovery_auc"] <= 1.0
    assert {a.kind for a in arts} == {"solve", "eval"}


def test_file_network_round_trip(tmp_path):
    from repro.core.network import HeteroNetwork
    from repro.data.drugnet import DrugNetSpec, make_drugnet

    net = make_drugnet(DrugNetSpec(seed=0, **TINY)).network
    path = str(tmp_path / "net.npz")
    net.save_npz(path)
    loaded = HeteroNetwork.load_npz(path)
    assert loaded.sizes == net.sizes
    for pair, name, m in net.relations():
        np.testing.assert_array_equal(loaded.blocks[pair][name].toarray(), m.toarray())
    assert tuple(loaded.type_names) == tuple(net.type_names)

    spec = RunSpec(
        network=NetworkSpec(kind="file", path=path),
        solve=SolveSpec(backend="dense", top_k=3),
    )
    art = Session(spec).solve()
    assert art.converged
    # file networks carry no truth: evaluate refuses at runtime too
    with pytest.raises(SpecError, match="ground truth"):
        Session(spec).evaluate()


# ------------------------------------------------- retired launch shims
@pytest.mark.parametrize("name", ["solve", "serve", "scenario", "bench"])
def test_launch_module_entry_points_removed(name, capsys):
    """The ``repro.launch.*`` module shims are retired: they exit 2 with
    a migration hint instead of forwarding."""
    import importlib

    mod = importlib.import_module(f"repro.launch.{name}")
    with pytest.raises(SystemExit) as exc:
        mod.main()
    assert exc.value.code == 2
    err = capsys.readouterr().err
    assert f"repro.launch.{name} has been removed" in err
    assert "repro run" in err
    assert f"repro {name}" in err


# ------------------------------------------- legacy-surface subcommands
def _run_shim(main_fn, argv):
    with pytest.warns(DeprecationWarning, match="repro run"):
        rc = main_fn(argv)
    assert rc == 0


def test_solve_shim_identical_to_spec_driver(tmp_path):
    from repro.launch.cli import solve_main

    out = str(tmp_path / "shim.npz")
    argv = [
        "--drugs", "30", "--diseases", "20", "--targets", "15",
        "--sigma", "1e-3", "--backend", "dense", "--top-k", "5",
        "--out", out,
    ]
    _run_shim(solve_main, argv)

    art = Session(tiny_spec()).solve()
    shim = np.load(out)
    np.testing.assert_array_equal(
        shim["drug_target"], art.outputs.interactions[(0, 2)]
    )
    np.testing.assert_array_equal(
        shim["sim_drug"], art.outputs.similarities[0]
    )
    # the ranking the old CLI printed == the artifact's ranking
    order = np.argsort(-shim["drug_target"][0], kind="stable")[:5]
    assert art.ranking["candidates"] == [int(x) for x in order]


def test_serve_shim_runs_and_warns(capsys):
    from repro.launch.cli import serve_main

    argv = [
        "--drugs", "30", "--diseases", "20", "--targets", "15",
        "--requests", "6", "--max-batch", "4",
    ]
    _run_shim(serve_main, argv)
    out = capsys.readouterr().out
    assert "queries" in out and "QPS" in out


def test_scenario_shim_recovery_and_agreement(capsys):
    from repro.launch.cli import scenario_main

    argv = [
        "--solve", "bipartite", "--scale", "0.25",
        "--backends", "dense,sparse",
    ]
    _run_shim(scenario_main, argv)
    out = capsys.readouterr().out
    assert "agree_vs_dense=True" in out


def test_run_driver_flags_build_valid_spec(capsys):
    from repro.launch.cli import run_main

    rc = run_main([
        "--network", "drugnet", "--param", "n_drug=30",
        "--param", "n_disease=20", "--param", "n_target=15",
        "--backend", "dense", "--top-k", "5", "--dry-run",
    ])
    assert rc == 0
    spec = RunSpec.from_json(capsys.readouterr().out)
    assert spec.network.params["n_drug"] == 30
    assert spec.sections() == ("solve",)


def test_run_driver_rejects_builder_flags_with_spec_file(tmp_path):
    from repro.launch.cli import run_main

    p = tmp_path / "s.json"
    p.write_text(tiny_spec().to_json())
    with pytest.raises(SystemExit):
        run_main([str(p), "--backend", "sparse"])
    # zero-valued flags are real values, not absent ones (0 == False trap)
    with pytest.raises(SystemExit):
        run_main([str(p), "--seed", "0"])


def test_run_driver_sub_flags_require_stage_trigger(capsys):
    from repro.launch.cli import run_main

    assert run_main(["--network", "drugnet", "--folds", "4", "--dry-run"]) == 2
    assert "--eval" in capsys.readouterr().err
    assert run_main(["--network", "drugnet", "--requests", "9", "--dry-run"]) == 2
    assert "--serve" in capsys.readouterr().err


def test_trace_serve_couples_builder_horizon():
    # scenarios that schedule their own timed deltas must schedule them
    # within THIS spec's replay horizon (else tail deltas silently never
    # apply); the session forwards serve.horizon_s into the builder
    import repro.scenarios as sc  # noqa: F401 - scenario registry import

    from repro.api import ServeSpec

    spec = RunSpec(
        network=NetworkSpec(kind="scenario", name="streaming", scale=0.4),
        solve=SolveSpec(seed_mode="fixed", backend="dense"),
        serve=ServeSpec(trace="poisson", rate_qps=25.0, horizon_s=1.5),
    )
    session = Session(spec)
    assert session.bundle.deltas, "streaming bundle must carry deltas"
    assert max(d.t for d in session.bundle.deltas) < 1.5


def test_save_npz_returns_openable_path(tmp_path):
    from repro.core.network import HeteroNetwork
    from repro.data.drugnet import DrugNetSpec, make_drugnet

    net = make_drugnet(DrugNetSpec(seed=0, **TINY)).network
    returned = net.save_npz(str(tmp_path / "bare_name"))  # no .npz suffix
    assert returned.endswith(".npz")
    assert HeteroNetwork.load_npz(returned).sizes == net.sizes


# ------------------------------------------------------ scenario disk cache
def test_scenario_disk_cache_round_trip(tmp_path, monkeypatch):
    import repro.scenarios as sc
    import repro.scenarios.base as base

    monkeypatch.setenv("REPRO_SCENARIO_CACHE_DIR", str(tmp_path))
    monkeypatch.setattr(base, "CACHE_MIN_EDGES", 0)
    first = sc.generate("bipartite", scale=0.25, seed=3)
    files = list(tmp_path.glob("bipartite-*.pkl"))
    assert len(files) == 1
    # second generation loads the pickle — identical bundle content
    second = sc.generate("bipartite", scale=0.25, seed=3)
    np.testing.assert_array_equal(
        first.network.blocks[(0, 1)][""].toarray(),
        second.network.blocks[(0, 1)][""].toarray(),
    )
    # a different seed is a different cache key
    sc.generate("bipartite", scale=0.25, seed=4)
    assert len(list(tmp_path.glob("bipartite-*.pkl"))) == 2
    # cache=False bypasses read AND write
    sc.generate("bipartite", scale=0.3, seed=3, cache=False)
    assert len(list(tmp_path.glob("bipartite-*.pkl"))) == 2


def test_scenario_cache_disabled_by_env(tmp_path, monkeypatch):
    import repro.scenarios as sc
    import repro.scenarios.base as base

    monkeypatch.setenv("REPRO_SCENARIO_CACHE_DIR", str(tmp_path))
    monkeypatch.setenv("REPRO_SCENARIO_CACHE", "0")
    monkeypatch.setattr(base, "CACHE_MIN_EDGES", 0)
    sc.generate("bipartite", scale=0.25, seed=0)
    assert not list(tmp_path.glob("*.pkl"))


def test_small_bundles_not_cached_by_default(tmp_path, monkeypatch):
    import repro.scenarios as sc

    monkeypatch.setenv("REPRO_SCENARIO_CACHE_DIR", str(tmp_path))
    sc.generate("bipartite", scale=0.25, seed=0)  # far below CACHE_MIN_EDGES
    assert not list(tmp_path.glob("*.pkl"))


# ------------------------------------------------------- bipartite scenario
def test_bipartite_scenario_registered_and_recoverable():
    import repro.scenarios as sc

    assert "bipartite" in sc.available_scenarios()
    bundle = sc.generate("bipartite", scale=0.25, seed=0)
    assert bundle.network.num_types == 2
    assert {p for p in bundle.network.blocks if p[0] != p[1]} == {(0, 1)}
    out = sc.recovery_auc(bundle, "dense", max_entities=6)
    assert out["recovery_auc"] > 0.8


# ------------------------------------------------------ sparse_coo removal
def test_sparse_coo_backend_deleted():
    from repro.core.solver import LPConfig
    from repro.engine import UnknownBackendError, make_engine, select_backend

    with pytest.raises(UnknownBackendError):
        make_engine("sparse_coo", LPConfig(alg="dhlp2"))
    # the auto policy is unchanged by the deletion
    assert select_backend(100) == "dense"
    assert select_backend(1_000_000) == "sparse"
