"""The rollout plans of a rolling maintenance drain, made by the yardstick: a
pure function of the configuration's ``scenarios`` block, the node table's
zones and the plan count, as plain numpy. The engine adapter
(``engines/whatif_drain.py``) turns a plan into the program's timeline of
``NodeEvent``s and the plain reference (``references/drain_scenarios.py``)
into the nodes that are out at each boundary; neither sees the other's.

Plan 0 is the base: no maintenance. Every other plan draws, in this order,
from ``scenarios.seed``: ``step`` (nodes that leave a boundary,
``maxUnavailable``), ``order`` (``zone``: one zone after the other, node
index order inside it; ``striped``: round-robin over the zones), ``outFor``
(boundaries a node is out: its reboot and health check), ``first`` (the
boundary the rollout starts at) and the place in the walk it starts from.
At boundary ``b >= first`` the plan's next ``step`` nodes LEAVE (cordon +
evict, in walk order: the order their tasks join the queue in) and the nodes
that left at ``b - outFor`` are BACK, empty. The walk ends when every node
was out once. The set does not depend on ``--seed``, as the cluster and the
arrivals do not: a set of its own per seed changes the work per batch.
"""

from __future__ import annotations

import numpy as np

NONE = np.zeros(0, np.int64)


def values(spec: dict, nodes: int, deployed_nodes: int) -> dict:
    """The deployment's value sets; on a node table smaller than the
    deployment's (rehearsals and tests, never a number) those under
    ``rehearse``, which fit a handful of nodes."""
    return {**spec, **spec["rehearse"]} if nodes < deployed_nodes else spec


def walk(order: str, zone: np.ndarray, start: int) -> np.ndarray:
    """Every node once, in the plan's order, from place ``start`` on."""
    N = len(zone)
    if order == "zone":
        seq = np.lexsort((np.arange(N), zone))
    elif order == "striped":
        within = np.zeros(N, np.int64)
        for z in np.unique(zone):
            at = np.nonzero(zone == z)[0]
            within[at] = np.arange(len(at))
        seq = np.lexsort((zone, within))
    else:
        raise ValueError(f"unknown order {order!r}")
    return np.roll(seq, -start)


def sample(config: dict, zone: np.ndarray, count: int) -> list:
    """``count`` plans over the nodes of ``zone``: dicts of ``step``,
    ``order``, ``outFor``, ``first`` and ``walk`` (node ids)."""
    spec = config["scenarios"]
    v = values(spec, len(zone), config["cluster"]["nodes"])
    rng = np.random.default_rng(spec["seed"])
    out = [{"step": 0, "order": "none", "outFor": 0, "first": 0, "walk": NONE}]
    lo, hi = v["firstBoundary"]
    for _ in range(count - 1):
        step = int(rng.choice(v["steps"]))
        order = str(rng.choice(v["orders"]))
        out_for = int(rng.choice(v["outFor"]))
        first = int(rng.integers(lo, hi + 1))
        start = int(rng.integers(0, len(zone)))
        out.append({"step": step, "order": order, "outFor": out_for,
                    "first": first, "walk": walk(order, zone, start)})
    return out


def moves(plan: dict, boundaries: int) -> list:
    """[(leave, back)] per boundary: node ids, ``leave`` in walk order."""
    left = [NONE] * boundaries
    for b in range(boundaries):
        k = b - plan["first"]
        if plan["step"] and k >= 0:
            left[b] = plan["walk"][k * plan["step"]:(k + 1) * plan["step"]]
    return [(left[b], left[b - plan["outFor"]] if b >= plan["outFor"] else NONE)
            for b in range(boundaries)]


def out_at(plan: dict, boundaries: int, nodes: int) -> np.ndarray:
    """[boundaries, nodes] bool: the nodes that are out DURING chunk ``b``
    (from boundary ``b``'s events until the next boundary's)."""
    out = np.zeros((boundaries, nodes), bool)
    now = np.zeros(nodes, bool)
    for b, (leave, back) in enumerate(moves(plan, boundaries)):
        now[leave] = True
        now[back] = False
        out[b] = now
    return out
