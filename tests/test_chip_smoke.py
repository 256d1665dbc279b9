"""``chip_smoke.py`` on the CPU: its phases at a small scale, its guard,
and the compile-cache helper every entry point calls.

The phases run with Pallas in interpret mode, so the kernel phase cannot
find a Mosaic custom call here; everything else it checks — convergence,
agreement with the dense engine and the float64 reference, serve answers
against the solve — is the same code the chip run executes.
"""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


@pytest.fixture(scope="module")
def smoke():
    clock = chip_smoke.CompileClock()
    cfg = chip_smoke.SmokeConfig(scale=0.05, kernel_columns=64, requests=60)
    yield chip_smoke.Smoke(cfg, clock)
    clock.close()


@pytest.mark.parametrize("alg", ["dhlp2", "dhlp1"])
def test_solve_phase_agrees_with_dense_and_reference(smoke, alg):
    rec = chip_smoke.phase_solve(smoke, alg)
    assert rec["ok"], rec
    assert rec["columns"] == rec["network"]["nodes"] == 981
    assert rec["dense"]["max_abs_diff"] <= rec["atol"]
    assert rec["reference"]["max_abs_diff"] <= rec["atol"]
    assert len(rec["reference"]["columns"]) == 16
    assert rec["compile_s"] >= 0 and rec["run_s"] >= 0
    json.dumps(rec)  # one JSON line per phase


def test_kernel_phase_agrees_with_sparse(smoke):
    rec = chip_smoke.phase_kernel(smoke)
    assert rec["converged"]
    assert rec["columns"] == 64
    assert rec["sparse_max_abs_diff"] <= rec["atol"]
    # interpret mode lowers no Mosaic call, and the phase demands one
    assert rec["tpu_custom_call"] is False
    assert rec["ok"] is False


def test_serve_phase_answers_match_the_solve(smoke):
    rec = chip_smoke.phase_serve(smoke)
    assert rec["ok"], rec
    assert rec["failed"] == 0 and rec["queries"] == 60 and rec["deltas"] == 2
    assert rec["answers_checked"] == rec["queries"]  # each on its own version
    assert rec["answer_max_abs_diff"] <= rec["atol"]


def test_failed_phase_is_reported_not_raised():
    lines = []

    def boom():
        raise RuntimeError("device lost")

    ok = chip_smoke.run_phases(
        [("good", lambda: {"phase": "good", "ok": True}), ("boom", boom)],
        emit=lines.append,
    )
    assert ok is False
    recs = [json.loads(line) for line in lines]
    assert recs[0]["ok"] is True
    assert recs[1] == {"phase": "boom", "ok": False,
                       "error": "RuntimeError: device lost"}


def _run(code_or_script, env_extra, args=()):
    env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", **env_extra)
    cmd = [sys.executable]
    cmd += [code_or_script] if code_or_script.endswith(".py") else ["-c", code_or_script]
    return subprocess.run(
        cmd + list(args), capture_output=True, text=True, env=env, cwd=REPO,
        timeout=300,
    )


def test_main_refuses_a_cpu_device():
    proc = _run(os.path.join(REPO, "chip_smoke.py"), {})
    assert proc.returncode == 2
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["ok"] is False and last["platform"] == "cpu"
    assert '"ok": true' not in proc.stdout
    assert "cpu" in proc.stderr


class TestCompileCache:
    PROBE = (
        "import sys; sys.path.insert(0, 'src'); import jax; "
        "from repro.compile_cache import enable_compile_cache; "
        "print(enable_compile_cache()); print(jax.config.jax_compilation_cache_dir)"
    )

    def test_fixed_path_in_the_checkout_by_default(self):
        proc = _run(self.PROBE, {})
        assert proc.returncode == 0, proc.stderr
        returned, configured = proc.stdout.split()
        assert returned == configured == os.path.join(REPO, ".jax_cache")

    def test_environment_directory_wins_and_nothing_is_set(self, tmp_path):
        proc = _run(self.PROBE, {"JAX_COMPILATION_CACHE_DIR": str(tmp_path)})
        assert proc.returncode == 0, proc.stderr
        returned, configured = proc.stdout.split()
        # JAX read the variable itself; the helper set no other directory
        assert returned == configured == str(tmp_path)

    def test_compiled_programs_land_in_the_environment_directory(self, tmp_path):
        code = (
            "import sys; sys.path.insert(0, 'src'); import jax, jax.numpy as jnp; "
            "from repro.compile_cache import enable_compile_cache; "
            "enable_compile_cache(); "
            "jax.config.update('jax_persistent_cache_min_compile_time_secs', 0); "
            "jax.jit(lambda x: jnp.sin(x) * 2)(jnp.ones(8)).block_until_ready()"
        )
        proc = _run(code, {"JAX_COMPILATION_CACHE_DIR": str(tmp_path)})
        assert proc.returncode == 0, proc.stderr
        assert any(tmp_path.iterdir())
