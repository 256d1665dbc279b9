"""Compile the main path's kernels and solve loops for a described v5e chip.

Nothing runs: the TPU compiler, which is installed even where no chip is
attached, lowers each program for one chip of a ``v5e:2x2`` topology and
raises what the chip's compiler would raise (misaligned blocks, VMEM
overflow, an operation Mosaic cannot lower).  The topology is described
inside a module fixture, never at import, so every test worker collects
the same tests and only the worker running this file loads the TPU
library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else it logs under /tmp
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep the cache out of these compiles
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


# the widest-slot bucket of the powerlaw scenario at scale 1.0 (777 rows of
# width 216) gathering from the full 4,396-node panel, one 128-column seat
# block — the engine's default panel shape
_M, _W, _N, _S = 777, 216, 4396, 128


@pytest.mark.parametrize(
    "kernel,storage",
    [
        ("csr_round_residual", jnp.float32),
        ("csr_round_residual", jnp.bfloat16),
        ("csr_round", jnp.float32),
        ("csr_aggregate", jnp.float32),
    ],
)
def test_csr_kernel_compiles_under_mosaic(one_chip, kernel, storage):
    from repro.kernels.segment_reduce import kernel as K

    nbr = _spec((_M, _W), jnp.int32, one_chip)
    wgt = _spec((_M, _W), storage, one_chip)
    F = _spec((_N, _S), storage, one_chip)
    rows = _spec((_M, _S), jnp.float32, one_chip)
    if kernel == "csr_round_residual":
        fn = lambda *a: K.csr_round_residual(*a, c=0.25, interpret=False)  # noqa: E731
        args = (nbr, wgt, F, rows, rows)
    elif kernel == "csr_round":
        fn = lambda *a: K.csr_round(*a, c=0.25, interpret=False)  # noqa: E731
        args = (nbr, wgt, F, rows)
    else:
        fn = lambda *a: K.csr_aggregate(*a, interpret=False)  # noqa: E731
        args = (nbr, wgt, F)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_lp_round_compiles_under_mosaic(one_chip):
    from repro.kernels.lp_blockspmm.kernel import lp_round

    n, s = 1024, 256
    A = _spec((n, n), jnp.float32, one_chip)
    F = _spec((n, s), jnp.float32, one_chip)
    fn = lambda A, F, base: lp_round(A, F, base, c=1.0, interpret=False)  # noqa: E731
    compiled = jax.jit(fn).lower(A, F, F).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("alg", ["dhlp2", "dhlp1"])
def test_sparse_plan_loop_compiles_for_one_chip(one_chip, alg):
    """The fused-superstep loops at powerlaw scale-0.2 shapes, every node a
    seed column — the solve ``chip_smoke.py`` runs, at a fifth the size."""
    import repro.scenarios as sc
    from repro.core.solver import LPConfig
    from repro.engine import make_engine
    from repro.engine import sparse as sp

    norm = sc.generate("powerlaw", scale=0.2, seed=0, cache=False).network.normalize()
    eng = make_engine("sparse", LPConfig(alg=alg, seed_mode="fixed", autotune=False))
    op = eng.prepare(norm)
    n = norm.num_nodes
    Y = _spec((n, n), jnp.float32, one_chip)
    shapes = lambda tree: jax.tree.map(  # noqa: E731
        lambda a: _spec(a.shape, a.dtype, one_chip), tree
    )
    common = dict(
        sigma=1e-3, max_iter=1000, seed_mode="fixed", use_kernel=False,
        storage="f32", bn=256, bs=128, bd=16,
    )
    if alg == "dhlp2":
        lowered = sp._dhlp2_plan_loop.lower(
            *shapes(eng._fused_plan(op)), Y, Y, beta2=0.25, momentum=0.0, **common
        )
    else:
        lowered = sp._dhlp1_plan_loop.lower(
            *shapes(op.payload.split_plan), Y, Y, alpha=0.5, max_inner=1000,
            **common,
        )
    mem = lowered.compile().memory_analysis()
    assert mem.argument_size_in_bytes > n * n * 4  # Y and F0 at least
