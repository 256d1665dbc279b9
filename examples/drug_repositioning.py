"""End-to-end drug-repositioning study — the paper's full evaluation
pipeline (Fig. 2 steps A-G + §6.2) in one script:

  1. build the gold-standard-scale heterogeneous network,
  2. 10-fold cross-validation of DHLP-1 / DHLP-2 (AUC, AUPR, BestACC —
     paper Table 2),
  3. deleted-interaction recovery (Table 3),
  4. pseudo-new-drug prediction (Table 4),
  5. final ranked candidate lists for every drug (step G).

    PYTHONPATH=src python examples/drug_repositioning.py [--gpcr-scale]
"""
import argparse

import numpy as np

from repro.core import HeteroLP, LPConfig, extract_outputs, rank_of
from repro.data.drugnet import DrugNetSpec, make_drugnet
from repro.eval import cross_validate, summarize


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--gpcr-scale", action="store_true",
                    help="full 223/150/95 sizes + 10 folds (slower)")
    ap.add_argument("--folds", type=int, default=None)
    args = ap.parse_args()

    if args.gpcr_scale:
        spec = DrugNetSpec()          # 223 drugs / 150 diseases / 95 targets
        folds = args.folds or 10
    else:
        spec = DrugNetSpec(n_drug=60, n_disease=40, n_target=30,
                           n_clusters=6)
        folds = args.folds or 5
    dn = make_drugnet(spec)
    net = dn.network
    print(f"== network: {net.sizes} nodes/type, {net.num_edges} edges ==")

    # ---- Table 2: k-fold CV ------------------------------------------------
    print(f"\n== {folds}-fold cross-validation (drug-target) ==")
    for alg in ["dhlp1", "dhlp2"]:
        def solver_fn(masked, _alg=alg):
            norm = masked.normalize()
            res = HeteroLP(LPConfig(alg=_alg, sigma=1e-3)).run(masked)
            return extract_outputs(res.F, norm).interactions[(0, 2)]

        summary = summarize(
            cross_validate(net, (0, 2), solver_fn, k=folds, seed=0)
        )
        print(f"  {alg}: AUC={summary['auc']:.4f} "
              f"AUPR={summary['aupr']:.4f} "
              f"BestACC={summary['best_acc']:.4f}")

    # ---- Table 3: deleted interaction --------------------------------------
    print("\n== deleted-interaction recovery ==")
    R = net.support((0, 2))
    drug = int(np.argmax((R > 0).sum(axis=1) >= 3))
    target = int(np.argwhere(R[drug] > 0)[0][0])
    mask = np.zeros_like(R, dtype=bool)
    mask[drug, target] = True
    masked = net.with_masked_fold((0, 2), mask)
    for alg in ["dhlp1", "dhlp2"]:
        res = HeteroLP(LPConfig(alg=alg, sigma=1e-3)).run(masked)
        out = extract_outputs(res.F, masked.normalize())
        r = rank_of(out.interactions[(0, 2)][drug], target)
        print(f"  {alg}: deleted target ranked #{r} of {R.shape[1]}")

    # ---- Table 4: pseudo new drug -------------------------------------------
    print("\n== pseudo-new-drug prediction ==")
    true_targets = np.argwhere(R[drug] > 0).ravel()
    mask4 = np.zeros_like(R, dtype=bool)
    mask4[drug, :] = R[drug] > 0
    masked4 = net.with_masked_fold((0, 2), mask4)
    for alg in ["dhlp1", "dhlp2"]:
        res = HeteroLP(LPConfig(alg=alg, sigma=1e-3)).run(masked4)
        out = extract_outputs(res.F, masked4.normalize())
        scores = out.interactions[(0, 2)][drug]
        k = len(true_targets) + 3
        top = set(np.argsort(-scores)[:k].tolist())
        hit = len(top & set(true_targets.tolist()))
        print(f"  {alg}: recovered {hit}/{len(true_targets)} "
              f"hidden targets in top-{k}")

    # ---- step G: candidate lists --------------------------------------------
    print("\n== final ranked candidates (first 3 drugs) ==")
    res = HeteroLP(LPConfig(alg="dhlp2", sigma=1e-3)).run(net)
    out = extract_outputs(res.F, net.normalize())
    for d in range(3):
        print(f"  drug {d}: targets {out.ranked_candidates((0, 2), d, 5).tolist()}")


if __name__ == "__main__":
    main()
