"""The Hetionet and four-chip DTINet cells, end to end at the tiny size.

The four-chip cell runs in a child process with four virtual CPU devices
(the edge-sharded engine's mesh); the rest runs here, the chip guard
skipped.
"""
import json
import os
import subprocess
import sys

import pytest

from chipbench import harness
from chipbench.tests import tiny


def test_hetionet_sound_run_is_correct():
    line = tiny.run(tiny.context("solve-hetionet-compounds"))
    assert line["correct"], line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"setup_s", "solve_cols_per_s"}


def test_hetionet_traced_run_reads_normalize_s():
    line = tiny.run(tiny.context("solve-hetionet-compounds", trace=True))
    assert line["correct"], line["checks"]
    m = line["metrics"]
    assert {"prepare_s", "compile_s", "normalize_s"} <= set(m)
    assert 0 < m["normalize_s"]["value"] < m["prepare_s"]["value"]


def test_hetionet_bf16_control_is_not_correct():
    line = tiny.run(tiny.context("solve-hetionet-compounds", control=True))
    assert not line["correct"]
    assert line["checks"]["f_err"]["value"] > line["checks"]["f_err"]["limit"]


def test_hetionet_refused_by_a_program_of_one_relation_per_pair(monkeypatch):
    """A program without relation blocks fails the run before any network
    is built, never handed a merged or dense one."""
    harness.setup_env()
    import numpy as np

    from repro.core import network

    class OneRelationPerPair:
        """Takes only one matrix a block, as ``HeteroNetwork`` did before
        relation blocks: a ``{relation: matrix}`` block is no matrix."""

        def __init__(self, P, R, type_names=None):
            for m in [*P, *R.values()]:
                np.asarray(m, dtype=np.float64)

    monkeypatch.setattr(network, "HeteroNetwork", OneRelationPerPair)
    ctx = tiny.context("solve-hetionet-compounds")
    driver = harness.load_module("drivers", "solve_relations")
    try:
        with pytest.raises(RuntimeError, match="one relation per pair"):
            driver.run(ctx)
    finally:
        ctx.clock.close()
    assert "prepare_s" not in ctx.facts


_SHARDED = """
import json, sys
sys.path.insert(0, sys.argv[1])
from chipbench.tests import tiny
ctx = tiny.context("solve-dtinet-sharded4")
line = tiny.run(ctx)
import jax
print(json.dumps({"line": line, "devices": len(jax.devices()), "backend": ctx.cell["backend"]}))
"""


def test_sharded4_runs_on_four_devices():
    env = {
        "JAX_PLATFORMS": "cpu",
        "PATH": os.environ.get("PATH", "/usr/bin:/bin"),
        "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
    }
    out = subprocess.run(
        [sys.executable, "-c", _SHARDED, str(harness.ROOT)],
        capture_output=True, text=True, timeout=600, env=env,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert got["devices"] == 4 and got["backend"] == "sharded"
    line = got["line"]
    assert line["correct"], line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"setup_s", "solve_cols_per_s"}
