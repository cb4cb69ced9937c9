"""A Borg cell that is full, made by the yardstick from the seed.

``generators/borg.py``'s node table and task columns (imported, not copied)
with two differences, both the configuration's (``workload``):

* **A window cut out of the day, not the day thinned.** The arriving tasks
  come at the deployment's own rate, ``deployedTasksPerDay`` a day over the
  deployment's ``cluster.nodes`` machines (on a smaller node table, a
  rehearsal, the rate is that table's share), with ``borg.py``'s diurnal
  factor, the window ending where it crosses 1; so ``tasks`` of them span ``tasks / rate`` seconds, a few mean
  durations, and the work that is running at once is the deployment's.
* **A resident set**: long-running tasks that hold the machines before the
  window starts (column ``bound_node``, arrival 0, duration counted from
  0). Node by node a fill is drawn in ``resident.fill +- resident.band`` of
  the node's cpu and tasks of the upper cpu buckets (``resident.cpuBuckets``
  at ``borg.py``'s own odds, renormalised; memory from its buckets as they
  are; tiers at ``resident.tierProbs``) are bound there until the next
  would pass the fill, then the largest buckets that still fit under it
  (memory: a bucket down on every resident of a node whose memory they
  would pass, until they fit). A
  node that carries the ``dedicated=batch`` taint holds only tasks that
  tolerate it (the two lowest tiers, as ``borg.py`` has it). Durations are
  exponential with mean ``resident.meanDuration``. No resident is a gang
  member.

The residents stand first in every column, the arriving tasks behind them.
Every ``--seed`` gets the same cluster, residents, arrival times, gang
layout and multiset of arriving tasks (all of ``baseSeed``) and deals the
arriving tasks onto the arrival slots in another order.

``to_program`` hands the trace to the system under test through its
external-trace ingest, ``sim.borg.encoded_from_cols``, with the
``bound_node`` column; the only place this file touches the program.
"""

from __future__ import annotations

import numpy as np

from generators import borg

PAD = borg.PAD
DAY = 86400.0


def window_arrivals(tasks: int, rate: float, seed: int) -> np.ndarray:
    """[tasks] f64, sorted: a Poisson stream of ``rate`` a second from the
    window's start, under ``borg.py``'s diurnal factor (which multiplies
    the time since the window's start); the window ends where that factor
    crosses 1 on its way up, so the window as a whole spans ``tasks /
    rate`` seconds, the day's mean rate (a phase drawn from the seed would
    make it anything from half to one and a half times that)."""
    rng = np.random.default_rng(seed)
    phase = -tasks / rate
    t = np.cumsum(rng.exponential(1.0 / rate, size=tasks))
    t *= 1.0 + 0.5 * np.sin((t + phase) * (2 * np.pi / DAY))
    return np.sort(t).astype(np.float64)


def resident_set(nodes: dict, wl: dict, seed: int) -> dict:
    """The task columns of the residents, node by node."""
    spec = wl["resident"]
    rng = np.random.default_rng(seed)
    buckets = np.asarray(spec["cpuBuckets"], np.float32)
    odds = np.asarray([borg.CPU_PROBS[list(borg.CPU_BUCKETS).index(b)]
                       for b in buckets])
    odds = odds / odds.sum()
    down = np.sort(buckets)[::-1]
    N = len(nodes["cpu"])
    fill = rng.uniform(spec["fill"] - spec["band"], spec["fill"] + spec["band"],
                       size=N)
    cpu, at = [], []
    for n in range(N):
        target = float(fill[n]) * float(nodes["cpu"][n])
        draw = rng.choice(buckets, size=int(target) + 1, p=odds)
        took = draw[np.cumsum(draw) <= target]
        left = target - float(took.sum())
        extra = []
        for b in down:  # the largest buckets that still fit under the fill
            while b <= left and len(took) + len(extra) < borg.PODS_PER_NODE:
                extra.append(b)
                left -= float(b)
        took = np.concatenate([took, np.asarray(extra, np.float32)])
        cpu.append(took.astype(np.float32))
        at.append(np.full(len(took), n, np.int32))
    cpu, at = np.concatenate(cpu), np.concatenate(at)
    R = len(cpu)
    tier = rng.choice(len(borg.PRIORITY_TIERS), size=R, p=spec["tierProbs"])
    on_tainted = nodes["tainted"][at]
    # a tainted node holds only tasks that tolerate it: the two lowest tiers
    tier = np.where(on_tainted, rng.integers(0, 2, size=R), tier)
    mem = rng.choice(borg.MEM_BUCKETS, size=R, p=borg.MEM_PROBS).astype(np.float32)
    for _ in borg.MEM_BUCKETS:  # a node's residents fit its memory too
        over = np.bincount(at, mem.astype(np.float64), N) > spec["fill"] * nodes["mem"]
        shrink = over[at] & (mem > borg.MEM_BUCKETS[0])
        if not shrink.any():
            break
        mem = np.where(shrink, mem / 2, mem).astype(np.float32)
    apps = wl["numApps"]
    app_probs = 1.0 / (np.arange(apps) + 2.0)
    app_probs /= app_probs.sum()
    return {
        "arrival": np.zeros(R, np.float64),
        "cpu": cpu,
        "mem": mem,
        "priority": borg.PRIORITY_TIERS[tier].astype(np.int32),
        "group_id": np.full(R, PAD, np.int32),
        "app_id": rng.choice(apps, size=R, p=app_probs).astype(np.int32),
        "tolerates": on_tainted.astype(np.int32),
        "duration": rng.exponential(spec["meanDuration"], size=R).astype(np.float32),
        "bound_node": at,
    }


def generate(config: dict, nodes: int, tasks: int, seed: int) -> dict:
    wl, cl = config["workload"], config["cluster"]
    table = borg.node_table(nodes, wl["baseSeed"], cl["zones"], cl["taintFraction"])
    cols = borg.task_columns(tasks, wl["baseSeed"], wl)
    rate = wl["deployedTasksPerDay"] / DAY * nodes / cl["nodes"]
    cols["arrival"] = window_arrivals(tasks, rate, wl["baseSeed"] + 1)
    deal = np.random.default_rng(seed).permutation(tasks)
    for k in borg.DEALT:
        cols[k] = cols[k][deal]
    cols["bound_node"] = np.full(tasks, PAD, np.int32)
    res = resident_set(table, wl, wl["baseSeed"] + 2)
    return {
        "nodes": table,
        "tasks": {k: np.concatenate([res[k], cols[k]]) for k in res},
        "resident": len(res["cpu"]),
        "spread_apps": int(wl["numApps"] * wl["spreadAppFraction"]),
        "spread_max_skew": 5,
    }


def to_program(trace: dict, config: dict):
    """(EncodedCluster, EncodedPods) for the engines."""
    from kubernetes_simulator_tpu.sim import borg as program

    wl, n = config["workload"], trace["nodes"]
    spec = program.BorgSpec(
        nodes=len(n["cpu"]), tasks=len(trace["tasks"]["arrival"]),
        seed=wl["baseSeed"], gang_fraction=wl["gangFraction"],
        max_gang=wl["maxGang"], num_apps=wl["numApps"],
        spread_app_fraction=wl["spreadAppFraction"],
        toleration_fraction=wl["tolerationFraction"],
        mean_duration=wl["meanDuration"])
    ec, ep, _ = program.encoded_from_cols(spec, trace["tasks"])
    # The ingest makes the cluster itself, from (nodes, seed): it has to be
    # the node table the reference holds; and it has to have taken the
    # resident set.
    have = {tuple(np.asarray(ec.allocatable)[:, r]) for r in range(ec.num_resources)}
    for k in ("cpu", "mem", "pods"):
        if tuple(n[k]) not in have:
            raise RuntimeError(f"the program's cluster differs from the "
                               f"yardstick's node table in {k!r}")
    if not np.array_equal(np.asarray(ep.bound_node), trace["tasks"]["bound_node"]):
        raise RuntimeError("the program's ingest did not take the resident "
                           "set (the bound_node column)")
    return ec, ep
