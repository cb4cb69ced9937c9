"""The plain reference for a what-if batch: ``greedy_waves`` (its
``schedule``, ``State``, ``pick``, ``judge``; numpy only, nothing of the
program imported) held against every scenario on that scenario's OWN
cluster.

A scenario's node table is the base table with the perturbations of
``whatif_scenarios.sample`` applied: a down node has allocatable 0 in every
resource, a scaled node its cpu capacity times the factor (float32, as the
cluster is), and a node with the injected taint is marked ``injected``: no
task tolerates that taint. A down or injected node can take no task at all,
so the table the choices are judged on leaves those nodes out (the order of
the others, and with it "the lowest index among equals", stays): nothing
divides by a capacity of 0, and a task found on such a node is counted in
full, over every task of every scenario, not in a sample.

The check is ``greedy_waves``' own, teacher-forced (one ``schedule`` for all
scenarios: the trace is common): ``samples`` (scenario, task) pairs drawn
from the seed, ``PER_SCENARIO`` in every scenario and each scenario's last
task among them, the rest over all pairs alike; the share of choices that
cannot be the reference's pick is pooled over all scenarios.

Controls, each of which has to come out not correct: ``bf16`` (the reference
in bfloat16 in the program's place, on the scenario's own table) and
``unperturbed`` (every scenario judged on the base table, the reference of
scenario 0 in every scenario's place: the check sees the perturbations).
"""

from __future__ import annotations

import numpy as np

import whatif_scenarios
from references import greedy_waves as G

PER_SCENARIO = 16


def node_table(base: dict, sc: dict) -> dict:
    """The scenario's cluster: ``base`` (the trace's node table) under the
    perturbations ``sc``."""
    nodes = {k: (v.copy() if isinstance(v, np.ndarray) else v)
             for k, v in base.items()}
    nodes["cpu"][sc["scaled"]] *= np.float32(sc["factor"])
    for r in G.RESOURCES:
        nodes[r][sc["down"]] = 0.0
    nodes["injected"] = np.zeros(len(nodes["cpu"]), bool)
    nodes["injected"][sc["tainted"]] = True
    return nodes


def takes_tasks(nodes: dict) -> np.ndarray:
    """[N] bool: nodes that can take a task at all in this scenario."""
    return (nodes["pods"] > 0) & ~nodes["injected"]


def judged_on(nodes: dict, assign: np.ndarray):
    """(table, assignments) with the nodes that take no task left out and
    the assignments renumbered; a task on such a node reads -2."""
    keep = takes_tasks(nodes)
    table = {k: (v[keep] if isinstance(v, np.ndarray) else v)
             for k, v in nodes.items()}
    new = np.where(keep, np.cumsum(keep) - 1, -2)
    return table, np.where(assign >= 0, new[np.clip(assign, 0, None)], assign)


def draw(rng, scenarios: int, sched: dict, samples: int) -> np.ndarray:
    """[n, 2] (scenario, task) pairs, sorted, without repeats."""
    P = len(sched["rank"])
    per = min(PER_SCENARIO, P)
    pairs = [(s, int(k)) for s in range(scenarios)
             for k in np.append(rng.choice(P, size=per, replace=False),
                                sched["seq"][-1])]
    rest = max(0, samples - len(pairs))
    flat = rng.choice(scenarios * P, size=min(rest, scenarios * P), replace=False)
    pairs += [(int(f // P), int(f % P)) for f in flat]
    return np.unique(np.asarray(pairs, np.int64), axis=0)


def check(trace: dict, config: dict, answers: dict, seed: int,
          samples: int, control=None) -> list:
    """Rows (name, value, limit); ``limit`` None is printed for the record."""
    if control not in (None, "bf16", "unperturbed"):
        raise ValueError(f"unknown control {control!r}")
    base, tasks, eng = trace["nodes"], trace["tasks"], config["engine"]
    weights, limits = config["scheduler"]["weights"], config["limits"]
    assigns = np.asarray(answers["assignments"], np.int64)
    S, P = assigns.shape
    scen = whatif_scenarios.sample(config, len(base["cpu"]), S)
    sched = G.schedule(tasks, eng["waveWidth"], eng["chunkWaves"])
    gang = tasks["group_id"]
    pairs = draw(np.random.default_rng(seed), S, sched, samples)
    short = [[] for _ in range(S)]
    edge = behind = on_blocked = placed_off = 0
    base_table = node_table(base, scen[0])
    for s in range(S):
        own = node_table(base, scen[s])
        on_blocked += int((~takes_tasks(own))[assigns[s][assigns[s] >= 0]].sum())
        placed_off = max(placed_off, abs(int((assigns[s] >= 0).sum())
                                         - int(answers["placed"][s])))
        nodes, assign = judged_on(
            base_table if control == "unperturbed" else own, assigns[s])
        # a rolled-back gang's binds were seen by the slots after it in its
        # wave and are in no answer: those slots cannot be rebuilt
        broken = np.unique(gang[(assign < 0) & (gang != G.PAD)])
        in_broken = np.isin(gang, broken) & (gang != G.PAD)
        first = np.full(int(sched["wave"].max()) + 1, np.iinfo(np.int64).max)
        np.minimum.at(first, sched["wave"][in_broken], sched["slot"][in_broken])
        usable = ~in_broken & (sched["slot"] < first[sched["wave"]])
        drawn = pairs[pairs[:, 0] == s, 1]
        behind += int((~usable[drawn]).sum())
        state_of = np.where(assign >= 0, assign, -1)  # -2 binds nothing
        for k in drawn[usable[drawn]].tolist():
            st = G.State(nodes, tasks, sched, state_of, k)
            lo, hi, sure, maybe = G.pick(nodes, tasks, trace, st, k, weights)
            if lo is None:
                edge += 1
                continue
            choice = int(assign[k])
            if control == "bf16":
                choice = G.pick_bf16(nodes, tasks, trace, st, k, weights)
            short[s].append(100.0 if choice == -2 else
                            G.judge(choice, lo, hi, sure, maybe))
    per = [np.asarray(x) for x in short]
    pooled = np.concatenate(per) if sum(map(len, per)) else np.asarray([100.0])
    worst = max((float((x > 0).mean()) if len(x) else 1.0) for x in per)
    return [
        ("ref.choices_not_the_references_share",
         float((pooled > 0).mean()), limits["choices_not_the_references_share"]),
        ("ref.choices_compared_short_of_min",
         float(max(0, limits["choices_compared_min"] - len(pooled))), 0),
        ("ref.scenario_choices_compared_short_of_min",
         float(max(0, limits["choices_compared_min_per_scenario"]
                   - min(map(len, per)))), 0),
        ("ref.placements_on_down_or_injected_taint_nodes", float(on_blocked), 0),
        ("ref.placed_differs_from_answers_max", float(placed_off), 0),
        ("ref.worst_scenario_choices_not_the_references_share", worst, None),
        ("ref.choice_short_by_points_max", float(pooled.max()), None),
        ("ref.samples_on_a_zone_score_edge", float(edge), None),
        ("ref.samples_behind_a_rolled_back_gang", float(behind), None),
    ]
