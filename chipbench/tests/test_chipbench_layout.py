"""The benchmark is data: BENCHMARK.json keeps the contract, and a new
config, cell or per-layer metric is added by adding files alone."""
import json
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from chipbench import harness

ROOT = Path(harness.__file__).resolve().parents[1]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    return harness.load_benchmark(ROOT)


def test_top_level_keys(bench):
    assert set(bench) == {
        "command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer",
    }
    assert bench["paths"] == ["chipbench"] and bench["command"][1] == "chipbench/run.py"
    assert 1 <= bench["run_seconds"] <= 51
    assert (ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024


def test_every_name_unit_and_entry(bench):
    configs = {c["name"] for c in bench["configs"]}
    cells = {w["name"] for w in bench["workloads"]}
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and (ROOT / c["file"]).is_file()
        assert c["file"] == f"chipbench/configs/{c['name']}.json"
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4) and len(w["why"]) <= 200
        traffic = harness.load_json("traffic", w["traffic"])
        assert (harness.BENCH_DIR / "drivers" / f"{traffic['driver']}.py").is_file()
    assert len({(w["config"], w["traffic"]) for w in bench["workloads"]}) == len(cells)
    assert {w["config"] for w in bench["workloads"]} == configs
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(names) == len(set(names))
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in {"host_clock", "device_trace"}
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert m["source"] in SOURCES and m["moves"] in {e["name"] for e in bench["end_to_end"]}
        assert (harness.BENCH_DIR / "metrics" / f"{m['name']}.py").is_file()
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= cells
    assert bench["end_to_end"][0]["name"] == "setup_s" and bench["end_to_end"][0]["bound"] == 0.25


def test_each_cell_reports_setup_another_end_to_end_and_a_layer(bench):
    for w in bench["workloads"]:
        e2e = [m["name"] for m in harness.cell_metrics(bench, w["name"], "end_to_end")]
        assert "setup_s" in e2e and len(e2e) >= 2
        layer = harness.cell_metrics(bench, w["name"], "per_layer")
        assert layer and all(m["moves"] in e2e for m in layer)


def test_new_config_cell_and_metric_from_files_alone(tmp_path, bench):
    """Add a config, a traffic mix, a cell and a per-layer metric by adding
    files and entries; the harness finds each by name, no file edited."""
    copy = tmp_path / "chipbench"
    shutil.copytree(harness.BENCH_DIR, copy, ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in copy.rglob("*") if p.is_file()}
    cfg = json.loads((copy / "configs" / "dtinet.json").read_text())
    cfg["name"] = "dtinet-2x"
    (copy / "configs" / "dtinet-2x.json").write_text(json.dumps(cfg))
    mix = json.loads((copy / "traffic" / "sweep4096.json").read_text())
    (copy / "traffic" / "sweep4096-twice.json").write_text(json.dumps(mix))
    (copy / "metrics" / "jobs_run.py").write_text("def read(ctx):\n    return ctx.facts.get('jobs')\n")
    new = dict(bench)
    new["workloads"] = bench["workloads"] + [
        {"name": "solve-2x-allseeds", "config": "dtinet-2x", "traffic": "sweep4096-twice",
         "chips": 1, "why": "a later cell"}
    ]
    new["per_layer"] = bench["per_layer"] + [
        {"name": "jobs_run", "unit": "jobs", "better": "higher", "source": "host_clock",
         "layer": "engine superstep", "moves": "solve_cols_per_s", "workloads": ["solve-2x-allseeds"]}
    ]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(new))
    code = (
        "import sys; sys.path.insert(0, sys.argv[1]); from chipbench import harness as h;"
        "b = h.load_benchmark(h.ROOT); c = h.Context.load(b, 'solve-2x-allseeds', 1, 1, 1);"
        "c.facts['jobs'] = 3; c.clock.close();"
        "print(c.config['name'], c.cell['driver'], h.load_module('drivers', c.cell['driver']).__name__,"
        " sorted(h.per_layer(c)))"
    )
    out = subprocess.run(
        [sys.executable, "-c", code, str(tmp_path)], capture_output=True, text=True,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"}, timeout=120,
    )
    assert out.returncode == 0, out.stderr
    assert out.stdout.split()[:3] == ["dtinet-2x", "solve", "chipbench_drivers_solve"]
    assert "'jobs_run'" in out.stdout
    after = {p: p.read_bytes() for p in copy.rglob("*") if p.is_file() and p in before}
    assert after == before
