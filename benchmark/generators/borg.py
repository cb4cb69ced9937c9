"""The Borg-2019-shaped deployment, made by the yardstick from the seed.

``generate`` gives a plain trace: a node table and the task columns of the
columnar trace schema (arrival, cpu, mem, priority, group_id, app_id,
tolerates, duration), numpy only. The sampler is a copy of the program's
(``sim/borg.py`` ``_sample_cols`` and the node loop of ``sim/synthetic.py``
``make_cluster``) so that no PR can move the traffic; the originals stay
for the program's own callers (PERF.md §7).

Every seed gets the same work: the cluster, arrival times, gang layout and
multiset of tasks are those of the configuration's ``baseSeed``; ``seed``
deals the tasks onto the arrival slots in another order. A seed of its own
for each trace moves the diurnal phase and with it the work per batch.

``to_program`` hands the trace to the system under test through its
external-trace ingest, ``sim.borg.encoded_from_cols``, the only place this
file touches the program.
"""

from __future__ import annotations

import numpy as np

PAD = -1
PRIORITY_TIERS = np.array([0, 100, 200, 360, 450], dtype=np.int32)
TIER_PROBS = np.array([0.25, 0.35, 0.15, 0.2, 0.05])
CPU_BUCKETS = np.array([0.1, 0.25, 0.5, 1.0, 2.0, 4.0, 8.0], dtype=np.float32)
CPU_PROBS = np.array([0.2, 0.25, 0.2, 0.15, 0.1, 0.07, 0.03])
MEM_BUCKETS = np.array([0.25, 0.5, 1.0, 2.0, 4.0, 8.0, 16.0], dtype=np.float32) * 2**30
MEM_PROBS = np.array([0.15, 0.2, 0.25, 0.15, 0.12, 0.08, 0.05])
MACHINE_SHAPES = [(16, 64), (32, 128), (64, 256), (96, 384)]  # cores, GiB
PODS_PER_NODE = 110.0
DEALT = ("cpu", "mem", "priority", "app_id", "tolerates", "duration")


def node_table(nodes: int, seed: int, zones: int, taint_fraction: float) -> dict:
    rng = np.random.default_rng(seed)
    cpu, mem = np.zeros(nodes, np.float32), np.zeros(nodes, np.float32)
    tainted = np.zeros(nodes, bool)
    for i in range(nodes):
        c, m = MACHINE_SHAPES[rng.integers(len(MACHINE_SHAPES))]
        cpu[i], mem[i] = float(c), float(m) * 2**30
        tainted[i] = bool(taint_fraction) and rng.random() < taint_fraction
    return {
        "cpu": cpu, "mem": mem,
        "pods": np.full(nodes, PODS_PER_NODE, np.float32),
        "zone": (np.arange(nodes) % zones).astype(np.int32),
        "zones": min(zones, nodes), "tainted": tainted,
    }


def task_columns(tasks: int, seed: int, wl: dict) -> dict:
    rng = np.random.default_rng(seed)
    P, apps = tasks, wl["numApps"]
    app_probs = 1.0 / (np.arange(apps) + 2.0)
    app_probs /= app_probs.sum()
    app = rng.choice(apps, size=P, p=app_probs).astype(np.int32)
    tier = rng.choice(len(PRIORITY_TIERS), size=P, p=TIER_PROBS)
    tol = ((tier <= 1) & (rng.random(P) < wl["tolerationFraction"])).astype(np.int32)
    cpu = rng.choice(CPU_BUCKETS, size=P, p=CPU_PROBS).astype(np.float32)
    mem = rng.choice(MEM_BUCKETS, size=P, p=MEM_PROBS).astype(np.float32)
    # Diurnal-bursty arrivals over a virtual day.
    phase = rng.random() * 86400
    arrival = np.cumsum(rng.exponential(86400.0 / P, size=P))
    arrival *= 1.0 + 0.5 * np.sin((arrival + phase) * (2 * np.pi / 86400.0))
    arrival = np.sort(arrival).astype(np.float64)
    # Alloc sets: contiguous gangs.
    group_id = np.full(P, PAD, dtype=np.int32)
    i = g = 0
    while i < P:
        if rng.random() < wl["gangFraction"] / max(wl["maxGang"] / 2, 1):
            size = min(int(rng.integers(2, wl["maxGang"] + 1)), P - i)
            group_id[i:i + size] = g
            g += 1
            i += size
        else:
            i += 1
    return {
        "arrival": arrival, "cpu": cpu, "mem": mem,
        "priority": PRIORITY_TIERS[tier].astype(np.int32),
        "group_id": group_id, "app_id": app, "tolerates": tol,
        "duration": rng.exponential(wl["meanDuration"], size=P).astype(np.float32),
    }


def generate(config: dict, nodes: int, tasks: int, seed: int) -> dict:
    wl, cl = config["workload"], config["cluster"]
    cols = task_columns(tasks, wl["baseSeed"], wl)
    deal = np.random.default_rng(seed).permutation(tasks)
    for k in DEALT:
        cols[k] = cols[k][deal]
    return {
        "nodes": node_table(nodes, wl["baseSeed"], cl["zones"], cl["taintFraction"]),
        "tasks": cols,
        # which apps' tasks carry the zone spread constraint (ScheduleAnyway,
        # own app label), and its maxSkew: sim/borg.py's templates
        "spread_apps": int(wl["numApps"] * wl["spreadAppFraction"]),
        "spread_max_skew": 5,
    }


def to_program(trace: dict, config: dict):
    """(EncodedCluster, EncodedPods) for the engines."""
    from kubernetes_simulator_tpu.sim import borg

    wl, n = config["workload"], trace["nodes"]
    spec = borg.BorgSpec(
        nodes=len(n["cpu"]), tasks=len(trace["tasks"]["arrival"]),
        seed=wl["baseSeed"], gang_fraction=wl["gangFraction"],
        max_gang=wl["maxGang"], num_apps=wl["numApps"],
        spread_app_fraction=wl["spreadAppFraction"],
        toleration_fraction=wl["tolerationFraction"],
        mean_duration=wl["meanDuration"])
    ec, ep, _ = borg.encoded_from_cols(spec, trace["tasks"])
    # The ingest makes the cluster itself, from (nodes, seed): it has to be
    # the node table the reference holds.
    have = {tuple(np.asarray(ec.allocatable)[:, r]) for r in range(ec.num_resources)}
    for k in ("cpu", "mem", "pods"):
        if tuple(n[k]) not in have:
            raise RuntimeError(f"the program's cluster differs from the "
                               f"yardstick's node table in {k!r}")
    return ec, ep
