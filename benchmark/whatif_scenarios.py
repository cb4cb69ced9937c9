"""The scenario set of a what-if deployment, made by the yardstick: a pure
function of the configuration's ``scenarios`` block, the node count and the
scenario count, as plain numpy arrays. The engine adapter
(``engines/whatif.py``) turns them into the program's ``Scenario`` objects
and the plain reference (``references/greedy_waves_scenarios.py``) into
node tables; neither sees the other's.

The sampler is a copy of the program's ``sim.whatif.uniform_scenarios`` (the
same draws in the same order), so that no PR can move the traffic. Scenario
0 is the unperturbed base. The set does not depend on ``--seed``: as with
the cluster and the arrivals, a set of its own per seed changes the work per
batch.
"""

from __future__ import annotations

import numpy as np

NONE = np.zeros(0, np.int64)


def probabilities(spec: dict, nodes: int, deployed_nodes: int) -> dict:
    """The deployment's probabilities; on a node table smaller than the
    deployment's (rehearsals and tests, never a number) those under
    ``rehearse``, so that a handful of scenarios holds every kind."""
    return spec["rehearse"] if nodes < deployed_nodes else spec


def sample(config: dict, nodes: int, count: int) -> list:
    """``count`` scenarios over ``nodes`` nodes: dicts of ``down`` (node
    ids whose allocatable is 0 in every resource), ``scaled`` and
    ``factor`` (node ids whose cpu capacity is multiplied), ``tainted``
    (node ids that carry the injected NoSchedule taint no task tolerates)."""
    spec = config["scenarios"]
    p = probabilities(spec, nodes, config["cluster"]["nodes"])
    rng = np.random.default_rng(spec["seed"])
    out = [{"down": NONE, "scaled": NONE, "factor": 1.0, "tainted": NONE}]
    for _ in range(count - 1):
        sc = dict(out[0])
        if rng.random() < p["pNodeDown"]:
            k = int(rng.integers(1, max(2, nodes // 50)))
            sc["down"] = rng.choice(nodes, size=k, replace=False)
        if rng.random() < p["pCapacity"]:
            k = int(rng.integers(1, max(2, nodes // 10)))
            sc["scaled"] = rng.choice(nodes, size=k, replace=False)
            sc["factor"] = float(rng.choice(spec["capacityFactors"]))
        if rng.random() < p["pTaint"]:
            k = int(rng.integers(1, max(2, nodes // 20)))
            sc["tainted"] = rng.choice(nodes, size=k, replace=False)
        out.append(sc)
    return out
