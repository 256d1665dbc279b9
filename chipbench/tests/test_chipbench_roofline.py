"""The superstep's least work, against a hand count."""
import pytest

from chipbench import roofline


def test_three_node_hand_count():
    # a path 0-1-2 with self loops: 3 diagonal + 4 off-diagonal nonzeros
    nnz, n = 7, 3
    # 5 supersteps over 2 columns, one column stopping after 3: 8 column-iterations
    work = roofline.superstep_work(nnz, n, supersteps=5, col_iters=8)
    # operator: 7 nonzeros x (4 B index + 4 B weight) per superstep = 56 B x 5
    # columns: 3 rows x 4 B x 3 passes = 36 B per column-iteration x 8
    assert work["bytes"] == 5 * 56 + 8 * 36
    # 2 flops per nonzero + 4 per row, per column-iteration
    assert work["flops"] == 8 * (2 * 7 + 4 * 3)
    least = roofline.least_time(work, "TPU v5 lite")
    assert least["bound"] == "bytes"
    assert least["seconds"] == pytest.approx((5 * 56 + 8 * 36) / 819e9)


def test_flops_bound_and_unknown_chip():
    work = {"bytes": 1.0, "flops": 197e12}
    assert roofline.least_time(work, "TPU v5 lite") == {"seconds": 1.0, "bound": "flops"}
    with pytest.raises(KeyError):
        roofline.least_time(work, "TPU v9 imaginary")
