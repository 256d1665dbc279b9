"""End-to-end behaviour tests: the paper's §6.2.2/§6.2.3 experiments.

* deleted-interaction recovery (Table 3): hide one known drug-target edge,
  run both algorithms, assert the hidden target ranks in the top-k for that
  drug.
* pseudo-new-drug (Table 4): hide ALL of a drug's target interactions,
  assert they are recovered in the top-k (the "new drug" capability the
  paper highlights over prior methods).
"""
import numpy as np
import pytest

from repro.core import HeteroLP, LPConfig, extract_outputs, rank_of
from repro.data.drugnet import DrugNetSpec, make_drugnet


@pytest.fixture(scope="module")
def drugnet():
    return make_drugnet(
        DrugNetSpec(n_drug=50, n_disease=35, n_target=25, n_clusters=5,
                    seed=7)
    )


def _predict(net, alg):
    norm = net.normalize()
    res = HeteroLP(LPConfig(alg=alg, alpha=0.5, sigma=1e-3)).run(net)
    assert res.converged
    return extract_outputs(res.F, norm)


def _pick_drug_with_targets(R, min_t=3):
    counts = (R > 0).sum(axis=1)
    drug = int(np.argmax(counts >= min_t))
    assert counts[drug] >= min_t
    return drug


@pytest.mark.parametrize("alg", ["dhlp1", "dhlp2"])
def test_deleted_interaction_recovery(drugnet, alg):
    net = drugnet.network
    R = net.support((0, 2))
    drug = _pick_drug_with_targets(R)
    target = int(np.argwhere(R[drug] > 0)[0][0])
    mask = np.zeros_like(R, dtype=bool)
    mask[drug, target] = True
    masked = net.with_masked_fold((0, 2), mask)
    out = _predict(masked, alg)
    scores = out.interactions[(0, 2)][drug]
    # the deleted target must out-rank the unlinked ones (Table 3: rank ≤ 3
    # among all targets; we allow top-5 for the synthetic net)
    assert rank_of(scores, target) <= 5


@pytest.mark.parametrize("alg", ["dhlp1", "dhlp2"])
def test_pseudo_new_drug_recovery(drugnet, alg):
    net = drugnet.network
    R = net.support((0, 2))
    drug = _pick_drug_with_targets(R)
    true_targets = np.argwhere(R[drug] > 0).ravel()
    mask = np.zeros_like(R, dtype=bool)
    mask[drug, :] = R[drug] > 0
    masked = net.with_masked_fold((0, 2), mask)
    out = _predict(masked, alg)
    scores = out.interactions[(0, 2)][drug]
    k = len(true_targets) + 3
    top = np.argsort(-scores, kind="stable")[:k]
    recovered = len(set(top.tolist()) & set(true_targets.tolist()))
    # most hidden targets reappear near the top via disease/similarity paths
    assert recovered >= max(1, len(true_targets) // 2)


def test_outputs_include_updated_similarities(drugnet):
    """Second output of the paper: new similarity matrices."""
    out = _predict(drugnet.network, "dhlp2")
    assert len(out.similarities) == 3
    for s, n in zip(out.similarities, drugnet.network.sizes):
        assert s.shape == (n, n)
        assert np.isfinite(s).all()


def test_ranked_candidates_api(drugnet):
    out = _predict(drugnet.network, "dhlp2")
    top = out.ranked_candidates((0, 2), entity=0, top_k=10)
    assert top.shape == (10,)
    # reversed pair indexes the transposed block
    top_rev = out.ranked_candidates((2, 0), entity=0, top_k=10)
    assert top_rev.shape == (10,)
