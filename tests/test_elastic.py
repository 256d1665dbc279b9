"""Elastic re-mesh end-to-end: checkpoint written single-device, restored
into a DIFFERENT device count with new shardings (the failover path of
DESIGN.md §6), plus int8-compressed gradient all-reduce."""
import json
import os
import subprocess
import sys

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.checkpoint import CheckpointManager

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))

_RESTORE_CHILD = r"""
import os, sys, json
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
sys.path.insert(0, %(src)r)
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.checkpoint import CheckpointManager
from repro.parallel.hints import auto_mesh

cm = CheckpointManager(%(root)r)
like = {"w": jnp.zeros((64, 32)), "b": jnp.zeros((32,))}
mesh = auto_mesh((8,), ("data",))
sh = {"w": NamedSharding(mesh, P("data", None)),
      "b": NamedSharding(mesh, P())}
step, restored = cm.restore_latest(like, shardings=sh)
w = restored["w"]
out = {
    "step": step,
    "n_shards": len(w.sharding.device_set),
    "checksum": float(jnp.sum(w)),
    "is_sharded": not w.sharding.is_fully_replicated,
}
print("RESULT " + json.dumps(out))
"""

_INT8_CHILD = r"""
import os, sys, json
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, %(src)r)
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.parallel.collectives import grad_allreduce
from repro.parallel.hints import auto_mesh

mesh = auto_mesh((4,), ("d",))
rng = np.random.default_rng(0)
g = rng.standard_normal((16, 8)).astype(np.float32)

def body(gs, key):
    return grad_allreduce({"g": gs}, "d", compression="int8", key=key)["g"]

f = jax.jit(jax.shard_map(body, mesh=mesh,
                             in_specs=(P("d", None), P()),
                             out_specs=P("d", None), check_vma=False))
out = np.asarray(f(g, jax.random.PRNGKey(0)))
# exact per-shard sums for comparison
want = g.reshape(4, 4, 8).sum(axis=0)
want_full = np.concatenate([want] * 4, axis=0)
rel = np.abs(out - want_full).max() / (np.abs(want_full).max() + 1e-9)
print("RESULT " + json.dumps({"rel_err": float(rel)}))
"""


def _run_child(code_tpl, **kw):
    code = code_tpl % {"src": SRC, **kw}
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=600,
    )
    for line in reversed(proc.stdout.splitlines()):
        if line.startswith("RESULT "):
            return json.loads(line[len("RESULT "):])
    raise AssertionError(f"child failed:\n{proc.stderr[-3000:]}")


class TestElasticRestore:
    def test_single_device_save_multi_device_restore(self, tmp_path):
        # write on THIS process (1 device)
        rng = np.random.default_rng(1)
        tree = {"w": jnp.asarray(rng.random((64, 32)).astype(np.float32)),
                "b": jnp.asarray(rng.random(32).astype(np.float32))}
        cm = CheckpointManager(str(tmp_path))
        cm.save(42, tree)
        # restore on a fabricated 8-device mesh in a subprocess
        out = _run_child(_RESTORE_CHILD, root=str(tmp_path))
        assert out["step"] == 42
        assert out["n_shards"] == 8
        assert out["is_sharded"] is True
        np.testing.assert_allclose(
            out["checksum"], float(np.asarray(tree["w"]).sum()), rtol=1e-6
        )


class TestInt8Collective:
    def test_int8_allreduce_bounded_error(self):
        out = _run_child(_INT8_CHILD)
        # int8 + stochastic rounding: ~1% relative error is expected
        assert out["rel_err"] < 0.05
