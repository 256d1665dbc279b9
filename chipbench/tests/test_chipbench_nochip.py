"""Without a TPU a run exits non-zero and prints no result."""
import shutil
import subprocess
import sys

from chipbench import harness


def _run(cwd):
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "solve-dtinet-sweep4096",
         "--seed", "3000000021", "--seconds", "1", "--trace", "0"],
        cwd=cwd, capture_output=True, text=True, timeout=120,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"},
    )


def test_no_tpu_exits_2_and_prints_nothing():
    out = _run(harness.ROOT)
    assert out.returncode == 2
    assert out.stdout == ""
    assert "needs a TPU" in out.stderr


def test_benchmark_alone_exits_nonzero(tmp_path):
    shutil.copy(harness.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(harness.BENCH_DIR, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = _run(tmp_path)
    assert out.returncode != 0
    assert out.stdout == ""
