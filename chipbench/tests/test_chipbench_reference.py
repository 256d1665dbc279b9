"""The float64 reference against the program's ``Session.solve``."""
import numpy as np

from chipbench import deploy, harness, reference
from chipbench.tests import tiny


def test_reference_agrees_with_session_solve_at_scale_005():
    ctx = tiny.context("solve-dtinet-sweep4096")
    ctx.config["generator"]["scale"] = 0.05
    try:
        net = deploy.network(ctx)[0]
        sess = deploy.session(ctx, net)
        art = sess.solve()
    finally:
        ctx.clock.close()
    cols = np.random.default_rng(0).choice(net.num_nodes, 24, replace=False)
    alpha = ctx.config["solver"]["alpha"]
    ref = reference.solve(reference.operator(net, alpha), cols, alpha)
    gap = np.abs(art.F[:, cols] - ref).max()
    # sigma 1e-7 stops within a few sigma of the fixed point
    assert gap < 1e-6, gap
    assert gap > 0.0
    assert art.F.shape == (net.num_nodes, net.num_nodes)
    assert harness.load_json("configs", "dtinet")["solver"]["alpha"] == alpha
