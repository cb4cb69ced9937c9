"""The rollout plans of a maintenance drain under disruption budgets, made by
the yardstick: a pure function of the configuration's ``scenarios`` block, the
node table's zones, the plan count and the number of chunk boundaries, as
plain numpy. The engine adapter (``engines/whatif_budget_drain.py``) turns a
plan into the program's timeline of ``NodeEvent``s and its
``DisruptionBudget``, the plain reference
(``references/budget_drain_scenarios.py``) into what each node may do at each
boundary; neither sees the other's. Over ``drain_plans`` (``walk``, ``values``).

Plan 0 is the base: no maintenance, no failure. Every other plan draws, in
this order, from ``scenarios.seed``: ``step`` (nodes CORDONED a boundary),
``order`` (``zone`` / ``striped``: ``drain_plans.walk``), ``grace`` (boundaries
from a node's cordon to its deadline: a ``kubectl drain --timeout``),
``outFor`` (boundaries a drained node is out), ``first`` (the boundary the
rollout starts at), ``share`` (an application's ``maxUnavailable`` as a share
of its tasks), the place in the walk it starts from, and a FAILURE STORM:
Poisson(``stormMean``) single-node failures, each at a boundary drawn
uniformly from 1 on and back 1 or 2 boundaries later, and in every
``rackEvery``-th plan one rack loss: ``rackNodes`` nodes of one zone, next to
each other in it, down together and back 1 or 2 boundaries later. A failure
of a node that an earlier one of the plan (the rack's first, then the singles
in draw order) still holds down, or gives back at that very boundary, is
dropped: a timeline names a node's ``node_up`` once per ``node_down``.

At boundary ``b >= first`` the plan's next ``step`` nodes of its walk are
cordoned; what happens to them then is the rule's (the reference's head), not
the plan's. The set does not depend on ``--seed``.
"""

from __future__ import annotations

import numpy as np

import drain_plans

NONE = np.zeros(0, np.int64)


def max_unavailable(share: float, expected: np.ndarray) -> np.ndarray:
    """[A] ``max(1, floor(share x expected_a))``: ``expected_a`` the
    application's residents and arriving tasks in the trace."""
    return np.maximum(1, np.floor(share * np.asarray(expected, np.float64))
                      ).astype(np.int64)


def storm(rng, v: dict, zone: np.ndarray, boundaries: int, rack: bool) -> list:
    """[(down boundary, up boundary, node)], the rack's nodes first."""
    N, drawn = len(zone), []
    hi = max(boundaries - 1, 1)
    if rack:
        z = int(rng.integers(0, int(zone.max()) + 1))
        members = np.nonzero(zone == z)[0]
        width = min(int(v["rackNodes"]), len(members))
        at = int(rng.integers(0, len(members) - width + 1))
        down = int(rng.integers(1, hi + 1))
        up = down + int(rng.integers(1, 3))
        drawn += [(down, up, int(n)) for n in members[at:at + width]]
    for _ in range(int(rng.poisson(v["stormMean"]))):
        down = int(rng.integers(1, hi + 1))
        up = down + int(rng.integers(1, 3))
        drawn.append((down, up, int(rng.integers(0, N))))
    kept, held = [], {}
    for down, up, node in drawn:
        if any(d <= up and down <= u for d, u in held.get(node, ())):
            continue
        held.setdefault(node, []).append((down, up))
        kept.append((down, up, node))
    return kept


def sample(config: dict, zone: np.ndarray, count: int, boundaries: int) -> list:
    """``count`` plans over the nodes of ``zone``: dicts of ``step``,
    ``order``, ``grace``, ``outFor``, ``first``, ``share``, ``walk`` (node
    ids) and ``failures`` ([(down, up, node)])."""
    spec = config["scenarios"]
    v = drain_plans.values(spec, len(zone), config["cluster"]["nodes"])
    rng = np.random.default_rng(spec["seed"])
    out = [{"step": 0, "order": "none", "grace": 0, "outFor": 1, "first": 0,
            "share": 1.0, "walk": NONE, "failures": []}]
    lo, hi = v["firstBoundary"]
    for i in range(1, count):
        step = int(rng.choice(v["steps"]))
        order = str(rng.choice(v["orders"]))
        grace = int(rng.choice(v["grace"]))
        out_for = int(rng.choice(v["outFor"]))
        first = int(rng.integers(lo, hi + 1))
        share = float(rng.choice(v["shares"]))
        start = int(rng.integers(0, len(zone)))
        out.append({
            "step": step, "order": order, "grace": grace, "outFor": out_for,
            "first": first, "share": share,
            "walk": drain_plans.walk(order, zone, start),
            "failures": storm(rng, v, zone, boundaries,
                              rack=i % int(v["rackEvery"]) == 0)})
    return out


def moves(plan: dict, boundaries: int) -> list:
    """[(ups, downs, cordons)] per boundary: node ids in the order the
    timeline names them (``downs``: the rack's nodes, then the singles in
    draw order; ``cordons``: walk order)."""
    ups = [[] for _ in range(boundaries)]
    downs = [[] for _ in range(boundaries)]
    for down, up, node in plan["failures"]:
        if down < boundaries:
            downs[down].append(node)
            if up < boundaries:
                ups[up].append(node)
    out = []
    for b in range(boundaries):
        k = b - plan["first"]
        cord = NONE
        if plan["step"] and k >= 0:
            cord = plan["walk"][k * plan["step"]:(k + 1) * plan["step"]]
        out.append((np.asarray(ups[b], np.int64), np.asarray(downs[b], np.int64),
                    np.asarray(cord, np.int64)))
    return out
