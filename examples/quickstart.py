"""Quickstart: one declarative RunSpec, solved and ranked via the Session API.

    PYTHONPATH=src python examples/quickstart.py

Everything here is also reachable without Python: put the same spec in a
JSON file and run ``python -m repro run spec.json`` (see
``examples/specs/quickstart_run.json`` for a solve+eval+serve composite).
"""
import numpy as np

from repro.api import NetworkSpec, RunSpec, Session, SolveSpec


def main() -> None:
    # 1. declare the job: a small drug/disease/target network + a DHLP-2
    #    solve reporting drug 0's top-5 target candidates
    spec = RunSpec(
        network=NetworkSpec(
            kind="drugnet",
            seed=7,
            params={
                "n_drug": 40,
                "n_disease": 25,
                "n_target": 20,
                "n_clusters": 5,
            },
        ),
        solve=SolveSpec(
            alg="dhlp2",
            alpha=0.5,
            sigma=1e-3,
            rank_pair=(0, 2),
            entity=0,
            top_k=5,
        ),
    )
    print(f"spec round-trips as JSON:\n{spec.to_json()[:160]}...\n")

    # 2. resolve it once; the Session shares one prepared engine across
    #    every stage it runs
    session = Session(spec)
    net = session.network
    print(f"network: {dict(zip(('drugs', 'diseases', 'targets'), net.sizes))}, "
          f"{net.num_edges} edges")

    art = session.solve()
    print(f"converged in {art.outer_iters} rounds on {art.backend} "
          f"({art.supersteps} BSP supersteps equivalent)")

    # 3. outputs: the ranking artifact + full interaction matrices
    drug = art.ranking["entity"]
    known = np.argwhere(net.support((0, 2))[drug]).ravel()
    print(f"drug {drug}: known targets {known.tolist()}, "
          f"top-5 predicted {art.ranking['candidates']}")

    # 4. DHLP-1 (distributed MINProp) is one field away
    spec1 = RunSpec(network=spec.network, solve=SolveSpec(alg="dhlp1"))
    res1 = Session(spec1).solve()
    print(f"dhlp1: outer={res1.outer_iters} inner={res1.inner_iters}")


if __name__ == "__main__":
    main()
