"""The relation-mean reference against the program's oracle and solve."""
import numpy as np
import scipy.sparse as sp

from chipbench import deploy, harness, reference, relgen, relref
from chipbench.tests import tiny


def _config(scale):
    cfg = harness.load_json("configs", "hetionet")
    cfg["generator"]["scale"] = scale
    return cfg


def test_operator_agrees_with_the_programs_oracle_at_the_tiny_scale():
    harness.setup_env()
    from repro.core.reference import relation_mean_operator

    net = relgen.generate(_config(tiny.TINY_SCALE))
    relations = [
        (r.i, r.j, sp.csr_matrix((np.ones(r.rows.size), (r.rows, r.cols)),
                                 shape=(net.sizes[r.i], net.sizes[r.j])))
        for r in net.relations
    ]
    want = relation_mean_operator(net.sizes, relations, 0.5)
    got = relref.operator(net, 0.5)
    assert np.abs(got.toarray() - want).max() <= 1e-12
    assert got.nnz == net.operator_nnz


def test_reference_agrees_with_session_solve():
    ctx = tiny.context("solve-hetionet-compounds")
    ctx.config["generator"]["scale"] = 0.05
    driver = harness.load_module("drivers", "solve_relations")
    try:
        net = driver.network(ctx)[0]
        sess = driver.session(ctx, net)
        cols = np.random.default_rng(0).choice(net.num_nodes, 24, replace=False)
        Y = np.zeros((net.num_nodes, cols.size), np.float32)
        Y[cols, np.arange(cols.size)] = 1.0
        F = sess.engine.run(sess.norm, seeds=Y).F
    finally:
        ctx.clock.close()
    alpha = ctx.config["solver"]["alpha"]
    ref = reference.solve(relref.operator(net, alpha), cols, alpha)
    gap = np.abs(F - ref).max()
    assert 0.0 < gap < 1e-6, gap  # sigma 1e-7 stops within a few sigma
    # every relation reached the program as its own block
    assert sess.network.num_relations == len(net.relations) + sum(
        1 for t in range(len(net.sizes)) if not any(r.i == r.j == t for r in net.relations)
    )
    assert ctx.facts["normalize_s"] < ctx.facts["prepare_s"]
    assert deploy.type_index(net, "Compound") == 3
