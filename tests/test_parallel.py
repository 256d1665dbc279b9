"""Distributed engine tests: sharded LP (multi-device via subprocess),
compressed collectives, sharding hints, momentum acceleration."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))

_CHILD = r"""
import os, sys, json
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
sys.path.insert(0, %(src)r)
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.core import HeteroNetwork, HeteroLP, LPConfig
from repro.parallel.lp_sharded import ShardedHeteroLP
from repro.parallel.hints import auto_mesh
from repro.parallel.collectives import (
    compressed_psum, psum_scatter_then_gather, ring_allreduce_ppermute,
)

rng = np.random.default_rng(2)
n = (15, 11, 8)
Pm = []
for ni in n:
    a = (rng.random((ni, ni)) < 0.3) * rng.random((ni, ni)); np.fill_diagonal(a, 0)
    Pm.append((a + a.T) / 2)
R = {(i, j): (rng.random((n[i], n[j])) < 0.3).astype(float)
     for (i, j) in [(0, 1), (0, 2), (1, 2)]}
net = HeteroNetwork(P=Pm, R=R)
norm = net.normalize()
mesh = auto_mesh((2, 4), ("data", "model"))
cfg = LPConfig(alg="dhlp2", seed_mode="fixed", sigma=1e-6, max_iter=3000)
dense = HeteroLP(cfg).run(net)
out = {}
sh = ShardedHeteroLP(cfg).run(norm, mesh)
out["sharded_err"] = float(np.max(np.abs(sh.F - dense.F)))
st = ShardedHeteroLP(cfg, stale_sync=4).run(norm, mesh)
out["stale_err"] = float(np.max(np.abs(st.F - dense.F)))
out["stale_iters"] = int(st.outer_iters)
out["sync_iters"] = int(sh.outer_iters)
bf = ShardedHeteroLP(cfg, compression="bf16").run(norm, mesh)
out["bf16_err"] = float(np.max(np.abs(bf.F - dense.F)))

# DHLP-1 sharded (nested inner/outer loops) vs dense
cfg1 = LPConfig(alg="dhlp1", sigma=1e-6, max_iter=500, max_inner=300)
d1 = HeteroLP(cfg1).run(net)
s1 = ShardedHeteroLP(cfg1).run(norm, mesh)
out["dhlp1_err"] = float(np.max(np.abs(s1.F - d1.F)))
out["dhlp1_inner_match"] = bool(s1.inner_iters == d1.inner_iters)

# collectives: all variants of all-reduce agree
# (per-shard block must have leading dim divisible by 8 for reduce-scatter)
x = np.arange(256, dtype=np.float32).reshape(64, 4)
def body(xs):
    return (
        compressed_psum(xs, "d"),
        psum_scatter_then_gather(xs, "d"),
        ring_allreduce_ppermute(xs, "d"),
    )
m1 = auto_mesh((8,), ("d",))
f = jax.jit(jax.shard_map(body, mesh=m1, in_specs=P("d", None),
                             out_specs=(P("d", None),) * 3, check_vma=False))
a, b, c = f(x)
out["psum_ok"] = bool(np.allclose(np.asarray(a), np.asarray(b)) and
                      np.allclose(np.asarray(a), np.asarray(c)))
print("RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def child_results():
    code = _CHILD % {"src": SRC}
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=900,
    )
    for line in reversed(proc.stdout.splitlines()):
        if line.startswith("RESULT "):
            return json.loads(line[len("RESULT "):])
    raise AssertionError(f"child failed:\n{proc.stderr[-3000:]}")


class TestShardedLP:
    def test_matches_dense(self, child_results):
        assert child_results["sharded_err"] < 1e-5

    def test_stale_sync_same_fixed_point(self, child_results):
        assert child_results["stale_err"] < 1e-3
        # staleness trades iterations for collectives
        assert child_results["stale_iters"] >= child_results["sync_iters"]

    def test_bf16_compression_bounded_error(self, child_results):
        assert child_results["bf16_err"] < 5e-3

    def test_ring_and_scatter_gather_match_psum(self, child_results):
        assert child_results["psum_ok"]

    def test_sharded_dhlp1_matches_dense(self, child_results):
        assert child_results["dhlp1_err"] < 1e-5
        assert child_results["dhlp1_inner_match"]


class TestHints:
    def test_noop_without_mesh(self):
        import jax.numpy as jnp
        from repro.parallel.hints import BATCH, TP, shard_hint, set_ambient_mesh

        set_ambient_mesh(None)
        x = jnp.ones((4, 8))
        y = shard_hint(x, BATCH, TP)
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))

    def test_applies_with_mesh(self):
        import jax
        import jax.numpy as jnp
        from repro.parallel.hints import (
            BATCH, auto_mesh, shard_hint, set_ambient_mesh,
        )

        mesh = auto_mesh((1,), ("data",))
        set_ambient_mesh(mesh)
        try:
            x = jnp.ones((4, 8))
            y = jax.jit(lambda a: shard_hint(a, BATCH, None))(x)
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
        finally:
            set_ambient_mesh(None)

    def test_rank_mismatch_raises(self):
        import jax
        import jax.numpy as jnp
        from repro.parallel.hints import (
            auto_mesh, shard_hint, set_ambient_mesh,
        )

        mesh = auto_mesh((1,), ("data",))
        set_ambient_mesh(mesh)
        try:
            with pytest.raises(ValueError):
                shard_hint(jnp.ones((2, 2)), None)
        finally:
            set_ambient_mesh(None)


class TestMomentum:
    def test_same_fixed_point_fewer_iters(self):
        from repro.core import HeteroLP, HeteroNetwork, LPConfig

        rng = np.random.default_rng(5)
        P = []
        for ni in (14, 10, 8):
            a = (rng.random((ni, ni)) < 0.4) * rng.random((ni, ni))
            np.fill_diagonal(a, 0)
            P.append((a + a.T) / 2)
        R = {(i, j): (rng.random((P[i].shape[0], P[j].shape[0])) < 0.4).astype(float)
             for (i, j) in [(0, 1), (0, 2), (1, 2)]}
        net = HeteroNetwork(P=P, R=R)
        base = HeteroLP(LPConfig(alg="dhlp2", seed_mode="fixed",
                                 sigma=1e-6)).run(net)
        accel = HeteroLP(LPConfig(alg="dhlp2", seed_mode="fixed",
                                  sigma=1e-6, momentum=0.2)).run(net)
        np.testing.assert_allclose(accel.F, base.F, atol=1e-4)
        assert accel.outer_iters < base.outer_iters
