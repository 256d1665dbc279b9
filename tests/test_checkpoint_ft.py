"""Checkpoint store + fault-tolerance runtime behaviour."""
import os
import shutil
import time

import numpy as np
import jax.numpy as jnp
import pytest

from repro.checkpoint import CheckpointManager
from repro.ft import (
    ElasticController,
    FailureInjector,
    StepGuard,
    StragglerWatch,
    TransientWorkerError,
    is_retryable,
)


class _Tel:
    """Minimal telemetry double: records counter/gauge calls."""

    def __init__(self):
        self.counts = {}
        self.gauges = {}

    def count(self, name, n=1):
        self.counts[name] = self.counts.get(name, 0) + n

    def gauge(self, name, value):
        self.gauges[name] = value


def tree(seed=0):
    rng = np.random.default_rng(seed)
    return {
        "w": jnp.asarray(rng.random((8, 4)).astype(np.float32)),
        "b": [jnp.asarray(rng.random(4).astype(np.float32)),
              jnp.asarray(np.int32(seed))],
    }


class TestCheckpoint:
    def test_save_restore_roundtrip(self, tmp_path):
        cm = CheckpointManager(str(tmp_path), keep_last=2)
        t = tree(1)
        cm.save(5, t)
        step, restored = cm.restore_latest(tree(0))
        assert step == 5
        np.testing.assert_allclose(np.asarray(restored["w"]),
                                   np.asarray(t["w"]))
        assert int(restored["b"][1]) == 1

    def test_keep_last_gc(self, tmp_path):
        cm = CheckpointManager(str(tmp_path), keep_last=2)
        for s in [1, 2, 3, 4]:
            cm.save(s, tree(s))
        assert cm.steps() == [3, 4]

    def test_atomicity_partial_ignored(self, tmp_path):
        cm = CheckpointManager(str(tmp_path), keep_last=3)
        cm.save(1, tree(1))
        # fabricate a partial (tmp) checkpoint — must be invisible
        os.makedirs(tmp_path / "step_00000009.tmp")
        assert cm.steps() == [1]

    def test_corrupt_latest_falls_back(self, tmp_path):
        cm = CheckpointManager(str(tmp_path), keep_last=3)
        cm.save(1, tree(1))
        cm.save(2, tree(2))
        # corrupt step 2's manifest
        with open(tmp_path / "step_00000002" / "manifest.json", "w") as f:
            f.write("{broken")
        step, restored = cm.restore_latest(tree(0))
        assert step == 1

    def test_async_write(self, tmp_path):
        cm = CheckpointManager(str(tmp_path), keep_last=2, async_write=True)
        cm.save(7, tree(7))
        cm.wait()
        assert cm.steps() == [7]

    def test_shape_mismatch_raises(self, tmp_path):
        cm = CheckpointManager(str(tmp_path))
        cm.save(1, tree(1))
        bad = {"w": jnp.zeros((3, 3)), "b": [jnp.zeros(4), jnp.int32(0)]}
        with pytest.raises(ValueError):
            cm.restore(1, bad)

    def test_elastic_restore_with_shardings(self, tmp_path):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from repro.parallel.hints import auto_mesh

        cm = CheckpointManager(str(tmp_path))
        t = tree(3)
        cm.save(1, t)
        mesh = auto_mesh((1,), ("data",))
        sh = jax.tree_util.tree_map(
            lambda _: NamedSharding(mesh, P()), t
        )
        _, restored = cm.restore_latest(t, shardings=sh)
        np.testing.assert_allclose(np.asarray(restored["w"]),
                                   np.asarray(t["w"]))


class TestCheckpointLifecycle:
    def test_close_drains_async_queue(self, tmp_path):
        cm = CheckpointManager(str(tmp_path), async_write=True)
        cm.save(3, tree(3))
        cm.close()
        # the queued snapshot is durable even though wait() was never called
        assert cm.steps() == [3]

    def test_save_after_close_raises(self, tmp_path):
        cm = CheckpointManager(str(tmp_path))
        cm.close()
        with pytest.raises(RuntimeError, match="closed"):
            cm.save(1, tree(1))

    def test_close_idempotent_context_manager(self, tmp_path):
        with CheckpointManager(str(tmp_path), async_write=True) as cm:
            cm.save(1, tree(1))
        cm.close()  # second close is a no-op
        assert cm.steps() == [1]

    def test_async_write_error_surfaces_on_wait(self, tmp_path):
        cm = CheckpointManager(str(tmp_path), async_write=True)
        # a regular file squatting on the step's tmp path makes the
        # writer thread fail; the error must surface on wait(), not die
        # silently in the daemon
        open(tmp_path / "step_00000005.tmp", "w").close()
        cm.save(5, tree(5))
        with pytest.raises(OSError):
            cm.wait()
        assert cm.steps() == []


class TestRestoreValidation:
    def test_dtype_mismatch_rejected_cast_opts_in(self, tmp_path):
        cm = CheckpointManager(str(tmp_path))
        cm.save(1, {"w": np.ones((2, 2), np.float32)})
        like64 = {"w": np.zeros((2, 2), np.float64)}
        with pytest.raises(ValueError, match="dtype"):
            cm.restore(1, like64)
        out = cm.restore(1, like64, cast=True)
        assert np.asarray(out["w"]).dtype == np.float64

    def test_treedef_mismatch_rejected(self, tmp_path):
        cm = CheckpointManager(str(tmp_path))
        cm.save(1, {"a": np.ones(3), "b": np.zeros(3)})
        # same leaf count + shapes, different structure
        with pytest.raises(ValueError, match="treedef"):
            cm.restore(1, [np.ones(3), np.zeros(3)])

    def test_tmp_checkpoint_not_restored(self, tmp_path):
        cm = CheckpointManager(str(tmp_path))
        cm.save(1, tree(1))
        cm.save(2, tree(2))
        # crash mid-write of step 3: fully-formed leaves still under the
        # .tmp name (the atomic rename never happened) — invisible
        shutil.copytree(
            tmp_path / "step_00000002", tmp_path / "step_00000003.tmp"
        )
        step, _ = cm.restore_latest(tree(0))
        assert step == 2

    def test_restore_latest_flat_roundtrip(self, tmp_path):
        cm = CheckpointManager(str(tmp_path))
        leaves = [np.arange(4, dtype=np.int64), np.ones((3, 2))]
        cm.save(7, leaves, metadata={"version": 9})
        step, out, meta = cm.restore_latest_flat()
        assert step == 7
        assert meta["version"] == 9
        np.testing.assert_array_equal(out[0], leaves[0])
        np.testing.assert_array_equal(out[1], leaves[1])

    def test_restore_latest_flat_empty_root(self, tmp_path):
        cm = CheckpointManager(str(tmp_path))
        assert cm.restore_latest_flat() == (None, None, {})


class TestStepGuard:
    def test_retries_transient(self):
        calls = {"n": 0}

        def flaky():
            calls["n"] += 1
            if calls["n"] < 3:
                raise TransientWorkerError("boom")
            return "ok"

        g = StepGuard(max_retries=3, backoff_s=0.0)
        assert g.run(flaky) == "ok"
        assert g.retries == 2

    def test_fatal_not_retried(self):
        def fatal():
            raise ValueError("shape mismatch")

        g = StepGuard(max_retries=3, backoff_s=0.0)
        with pytest.raises(ValueError):
            g.run(fatal)
        assert g.retries == 0

    def test_restore_path(self):
        state = {"restored": False}

        def always_fails_until_restore():
            if not state["restored"]:
                raise TransientWorkerError("dead worker")
            return "recovered"

        def restore():
            state["restored"] = True
            return 0, None

        g = StepGuard(max_retries=1, backoff_s=0.0, restore_fn=restore)
        assert g.run(always_fails_until_restore) == "recovered"
        assert g.restores == 1

    def test_is_retryable_classification(self):
        assert is_retryable(TransientWorkerError("x"))
        assert is_retryable(RuntimeError("gRPC UNAVAILABLE: socket closed"))
        assert not is_retryable(ValueError("bad shape"))

    def test_injectable_clock_no_wall_sleep(self):
        sleeps = []
        calls = {"n": 0}

        def flaky():
            calls["n"] += 1
            if calls["n"] < 4:
                raise TransientWorkerError("x")
            return "ok"

        g = StepGuard(max_retries=3, backoff_s=0.1, sleep=sleeps.append)
        t0 = time.perf_counter()
        assert g.run(flaky) == "ok"
        # the injected clock recorded the exponential schedule; no wall
        # time was spent sleeping
        assert sleeps == pytest.approx([0.1, 0.2, 0.4])
        assert time.perf_counter() - t0 < 0.09

    def test_second_exhaustion_reraises(self):
        def dead():
            raise TransientWorkerError("still dead")

        g = StepGuard(
            max_retries=1,
            backoff_s=0.0,
            restore_fn=lambda: (0, None),
            sleep=lambda s: None,
        )
        with pytest.raises(TransientWorkerError):
            g.run(dead)
        assert g.restores == 1  # one restore per run(), then re-raise

    def test_replay_after_restore_gets_fresh_budget(self):
        state = {"restored": False}
        calls = {"n": 0}

        def fn():
            calls["n"] += 1
            # pre-restore: always fails.  post-restore: fails once more
            # (a transient during the replay), then succeeds.
            if not state["restored"]:
                raise TransientWorkerError("dead")
            if calls["n"] < 4:
                raise TransientWorkerError("replay hiccup")
            return "recovered"

        def restore():
            state["restored"] = True

        g = StepGuard(
            max_retries=1, backoff_s=0.0, restore_fn=restore,
            sleep=lambda s: None,
        )
        assert g.run(fn) == "recovered"
        assert g.restores == 1
        assert g.retries >= 2  # pre-restore retry + guarded replay retry

    def test_telemetry_counters(self):
        tel = _Tel()
        state = {"ok": False}

        def fn():
            if not state["ok"]:
                raise TransientWorkerError("x")
            return 1

        g = StepGuard(
            max_retries=1,
            backoff_s=0.0,
            restore_fn=lambda: state.__setitem__("ok", True),
            sleep=lambda s: None,
            telemetry=tel,
        )
        assert g.run(fn) == 1
        assert tel.counts == {"ft.retries": 1, "ft.restores": 1}


class TestStragglerWatch:
    def test_flags_outlier(self):
        w = StragglerWatch(threshold=2.0)
        for _ in range(10):
            assert not w.observe(0.1)
        assert w.observe(0.5)
        assert w.slow_steps == 1

    def test_mean_tracks(self):
        w = StragglerWatch()
        for _ in range(50):
            w.observe(0.2)
        assert abs(w.mean_step_time - 0.2) < 0.02

    def test_ewma_discounts_outliers(self):
        w = StragglerWatch(alpha=0.1, threshold=2.0)
        for _ in range(20):
            w.observe(0.1)
        w.observe(1.0)  # flagged → quarter-weight EWMA update
        assert w.mean_step_time < 0.15  # one outlier barely moves the mean

    def test_telemetry_counts_flags(self):
        tel = _Tel()
        w = StragglerWatch(threshold=2.0, telemetry=tel)
        for _ in range(5):
            w.observe(0.1)
        w.observe(0.5)
        assert tel.counts.get("ft.straggler_flags") == 1
        assert tel.gauges["ft.step_time_mean"] == pytest.approx(
            w.mean_step_time
        )


class TestElastic:
    def test_no_change_no_plan(self):
        c = ElasticController()
        assert c.plan(256, 256) is None

    def test_shrink_to_power_of_two(self):
        c = ElasticController()
        plan = c.plan(250, 256)
        assert plan["to"] == 128
        assert c.history

    def test_below_minimum_raises(self):
        c = ElasticController(min_devices=8)
        with pytest.raises(RuntimeError):
            c.plan(4, 256)


class TestFailureInjector:
    def test_fires_once(self):
        inj = FailureInjector(fail_at=(3,))
        inj.maybe_fail(2)
        with pytest.raises(TransientWorkerError):
            inj.maybe_fail(3)
        inj.maybe_fail(3)  # second pass: already fired


class TestRemesh:
    def test_remesh_end_to_end(self, tmp_path):
        """save-unsharded → restore-with-new-shardings, full circle."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from repro.ft import remesh
        from repro.parallel.hints import auto_mesh

        cm = CheckpointManager(str(tmp_path))
        t = tree(5)

        def make_shardings(n):
            mesh = auto_mesh((n,), ("data",))
            return jax.tree_util.tree_map(
                lambda _: NamedSharding(mesh, P()), t
            )

        tel = _Tel()
        restored, plan = remesh(
            cm,
            t,
            healthy_devices=1,
            current_devices=2,
            make_shardings=make_shardings,
            step=4,
            telemetry=tel,
        )
        assert plan["from"] == 2 and plan["to"] == 1
        np.testing.assert_allclose(
            np.asarray(restored["w"]), np.asarray(t["w"])
        )
        assert cm.steps() == [4]  # the pre-remesh snapshot is durable
        assert cm.manifest(4)["metadata"]["elastic"] == plan
        assert tel.counts.get("ft.remeshes") == 1
        assert tel.gauges["ft.mesh_devices"] == 1

    def test_remesh_no_change_is_identity(self, tmp_path):
        from repro.ft import remesh

        cm = CheckpointManager(str(tmp_path))
        t = tree(1)
        out, plan = remesh(cm, t, healthy_devices=4, current_devices=4)
        assert plan is None and out is t
        assert cm.steps() == []  # no snapshot for a no-op plan


def _small_net(seed=0, n=(12, 9, 7)):
    from repro.core import HeteroNetwork

    rng = np.random.default_rng(seed)
    P = []
    for ni in n:
        a = (rng.random((ni, ni)) < 0.4) * rng.random((ni, ni))
        np.fill_diagonal(a, 0)
        P.append((a + a.T) / 2)
    R = {
        (i, j): (rng.random((n[i], n[j])) < 0.3).astype(float)
        for (i, j) in [(0, 1), (0, 2), (1, 2)]
    }
    return HeteroNetwork(P=P, R=R)


class TestCheckpointedSolve:
    def test_crash_resume_byte_identical(self, tmp_path):
        from repro.core import LPConfig
        from repro.engine import make_engine
        from repro.ft import checkpointed_solve

        norm = _small_net().normalize()
        cfg = LPConfig(alg="dhlp2", seed_mode="fixed", sigma=1e-5)
        engine = make_engine("dense", cfg)

        clean, _ = checkpointed_solve(
            engine, norm,
            manager=CheckpointManager(str(tmp_path / "clean")), interval=3,
        )

        cm = CheckpointManager(str(tmp_path / "crash"))
        inj = FailureInjector(fail_at=(4,))
        with pytest.raises(TransientWorkerError):
            checkpointed_solve(engine, norm, manager=cm, interval=3,
                               injector=inj)
        assert cm.steps()  # a durable barrier predates the kill

        # same injector still armed: a resumed run never re-fires
        resumed, stats = checkpointed_solve(
            engine, norm, manager=cm, interval=3, injector=inj
        )
        assert stats["resumed_from"] == 3
        assert float(np.max(np.abs(resumed.F - clean.F))) == 0.0
        assert resumed.outer_iters == clean.outer_iters
        np.testing.assert_array_equal(
            resumed.per_column_iters, clean.per_column_iters
        )

    def test_checkpoint_cadence_and_final_barrier(self, tmp_path):
        from repro.core import LPConfig
        from repro.engine import make_engine
        from repro.ft import checkpointed_solve

        norm = _small_net(seed=2).normalize()
        cfg = LPConfig(alg="dhlp2", seed_mode="fixed", sigma=1e-5)
        cm = CheckpointManager(str(tmp_path), keep_last=100)
        res, stats = checkpointed_solve(
            make_engine("dense", cfg), norm, manager=cm, interval=4
        )
        assert res.converged
        steps = cm.steps()
        # every interval boundary plus the converged step is durable
        assert steps[-1] == res.outer_iters
        assert all(s % 4 == 0 for s in steps[:-1])
        assert stats["checkpoints"] == len(steps)
        assert stats["resumed_from"] is None


class TestDataPipelines:
    def test_lm_determinism_and_sharding(self):
        from repro.data.lm import LMDataConfig, sample_batch

        cfg = LMDataConfig(vocab=1000, batch=8, seq_len=32)
        a = sample_batch(cfg, step=3)
        b = sample_batch(cfg, step=3)
        np.testing.assert_array_equal(a["tokens"], b["tokens"])
        # shards are disjoint slices of the global batch
        s0 = sample_batch(cfg, step=3, shard=0, num_shards=2)
        s1 = sample_batch(cfg, step=3, shard=1, num_shards=2)
        np.testing.assert_array_equal(
            np.concatenate([s0["tokens"], s1["tokens"]]), a["tokens"]
        )
        # labels are next-token shifted
        np.testing.assert_array_equal(a["labels"][:, :-1], a["tokens"][:, 1:])

    def test_ctr_batch(self):
        from repro.data.recsys import CTRDataConfig, sample_ctr_batch

        cfg = CTRDataConfig(n_sparse=5, n_dense=3, vocab_per_field=100)
        b = sample_ctr_batch(cfg, 64)
        assert b["sparse"].shape == (64, 5)
        assert b["sparse"].max() < 100
        assert set(np.unique(b["labels"])) <= {0.0, 1.0}

    def test_planted_graph_learnable(self):
        from repro.data.graphs import planted_partition_graph

        d = planted_partition_graph(200, 800, 4, 16, seed=1)
        assert d.feats.shape == (200, 16)
        assert d.edges.num_nodes == 200
        # homophily: most edges connect same-class nodes
        e = d.edges
        same = (d.labels[e.src] == d.labels[e.dst]).mean()
        assert same > 0.5
