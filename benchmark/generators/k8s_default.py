"""The default-plugin-set deployment (BASELINE.json configs 2 and 3), made by
the yardstick from the seed.

``generate`` gives a plain trace: the node table and the pod columns
(arrival, cpu, mem, priority, app, leader, tolerates, kind, na_weight,
spread_skew, spread_dns), numpy only. The node loop is ``generators/borg.py``'s
copy of the program's ``make_cluster`` and the pod loop a copy of the draws of
the program's ``sim/synthetic.py`` ``make_workload`` as ``config2()`` calls it
(the same draws in the same order), so that no PR can move the traffic; the
originals stay for the program's own callers.

A pod's ``kind`` is the one affinity term ``make_workload`` gives it: NONE,
AFFINITY (required pod affinity to its own app, by zone), ANTI (required
anti-affinity to its app's leaders, by ``kubernetes.io/hostname``) or
NODE_PREF (preferred node affinity to ``tier=hot``, weight ``na_weight``).
``spread_skew`` 0 is no topology spread constraint; otherwise one over the
zones on its own app, ``spread_dns`` telling DoNotSchedule from ScheduleAnyway.

Every seed gets the same work: the cluster, the arrival times and the multiset
of pods are those of the configuration's ``baseSeed``; ``seed`` deals the pods
onto the arrival slots in another order.

``to_program`` hands the trace to the system under test as the objects its
ingest takes (``models.core`` ``Node`` / ``Pod``, ``models.encode.encode``),
the only place this file touches the program.
"""

from __future__ import annotations

import numpy as np

from generators import borg

NONE, AFFINITY, ANTI, NODE_PREF = 0, 1, 2, 3
CPU_CHOICES = [0.25, 0.5, 1.0, 2.0, 4.0]
MEM_GIB_CHOICES = [0.5, 1.0, 2.0, 8.0]
PRIORITY_CHOICES = [0, 0, 0, 100, 1000]
SKEW_CHOICES = [1, 2, 5]
ZONE_KEY = "topology.kubernetes.io/zone"
HOST_KEY = "kubernetes.io/hostname"
DEALT = ("cpu", "mem", "priority", "app", "leader", "tolerates", "kind",
         "na_weight", "spread_skew", "spread_dns")


def node_table(nodes: int, seed: int, cl: dict) -> dict:
    table = borg.node_table(nodes, seed, cl["zones"], cl["taintFraction"])
    table["hot"] = np.arange(nodes) % cl["hotEvery"] == 0
    return table


def pod_columns(pods: int, seed: int, wl: dict) -> dict:
    """``make_workload(pods, seed, with_affinity=True, with_spread=True,
    with_tolerations=True)``: one pass, a pod's draws in its order."""
    rng = np.random.default_rng(seed + 1)
    cols = {
        "arrival": np.zeros(pods, np.float64),
        "cpu": np.zeros(pods, np.float32), "mem": np.zeros(pods, np.float32),
        "priority": np.zeros(pods, np.int32), "app": np.zeros(pods, np.int32),
        "leader": np.zeros(pods, bool), "tolerates": np.zeros(pods, bool),
        "kind": np.zeros(pods, np.int8), "na_weight": np.zeros(pods, np.int32),
        "spread_skew": np.zeros(pods, np.int32),
        "spread_dns": np.zeros(pods, bool),
    }
    t = 0.0
    for i in range(pods):
        t += float(rng.exponential(1.0 / wl["arrivalRate"]))
        cols["arrival"][i] = t
        cols["app"][i] = int(rng.integers(wl["numApps"]))
        cols["leader"][i] = not rng.random() < wl["workerFraction"]
        cols["cpu"][i] = float(rng.choice(CPU_CHOICES))
        cols["mem"][i] = float(rng.choice(MEM_GIB_CHOICES)) * 2**30
        cols["priority"][i] = int(rng.choice(PRIORITY_CHOICES))
        cols["tolerates"][i] = rng.random() < wl["tolerationFraction"]
        r = rng.random()
        if r < wl["affinityFraction"]:
            cols["kind"][i] = AFFINITY
        elif r < wl["affinityFraction"] + wl["antiAffinityFraction"]:
            cols["kind"][i] = ANTI
        elif r < (wl["affinityFraction"] + wl["antiAffinityFraction"]
                  + wl["nodeAffinityFraction"]):
            cols["kind"][i] = NODE_PREF
            cols["na_weight"][i] = int(rng.integers(1, 100))
        if rng.random() < wl["spreadFraction"]:
            cols["spread_skew"][i] = int(rng.choice(SKEW_CHOICES))
            cols["spread_dns"][i] = rng.random() < wl["doNotScheduleFraction"]
    return cols


def generate(config: dict, nodes: int, tasks: int, seed: int) -> dict:
    wl, cl = config["workload"], config["cluster"]
    cols = pod_columns(tasks, wl["baseSeed"], wl)
    deal = np.random.default_rng(seed).permutation(tasks)
    for k in DEALT:
        cols[k] = cols[k][deal]
    return {"nodes": node_table(nodes, wl["baseSeed"], cl), "tasks": cols}


def program_objects(trace: dict, config: dict):
    """(Cluster, [Pod]) as ``make_cluster`` / ``make_workload`` build them."""
    from kubernetes_simulator_tpu.models import core as M

    n, t, cl = trace["nodes"], trace["tasks"], config["cluster"]
    taint = cl["taint"]
    nodes = []
    for i in range(len(n["cpu"])):
        cores = int(n["cpu"][i])
        nodes.append(M.Node(
            name=f"node-{i}",
            capacity={"cpu": float(n["cpu"][i]), "memory": float(n["mem"][i]),
                      "pods": int(n["pods"][i])},
            labels={
                ZONE_KEY: f"zone-{int(n['zone'][i])}",
                "topology.kubernetes.io/rack": f"rack-{i % cl['racks']}",
                "node.kubernetes.io/instance-type": f"type-{cores}",
                "tier": "hot" if n["hot"][i] else "standard",
            },
            taints=([M.Taint(taint["key"], taint["value"], taint["effect"])]
                    if n["tainted"][i] else []),
        ))
    hot = M.NodeSelectorTerm((M.MatchExpression.make("tier", "In", ["hot"]),))
    pods = []
    for i in range(len(t["arrival"])):
        app = f"app-{int(t['app'][i])}"
        own_app = M.LabelSelector.make({"app": app})
        pod = M.Pod(
            name=f"pod-{i}",
            labels={"app": app, "role": "leader" if t["leader"][i] else "worker"},
            requests={"cpu": float(t["cpu"][i]), "memory": float(t["mem"][i])},
            priority=int(t["priority"][i]), arrival_time=float(t["arrival"][i]),
        )
        if t["tolerates"][i]:
            pod.tolerations.append(M.Toleration(
                key=taint["key"], operator="Equal", value=taint["value"]))
        if t["kind"][i] == AFFINITY:
            pod.pod_affinity = M.PodAffinitySpec(required=(
                M.PodAffinityTerm(label_selector=own_app, topology_key=ZONE_KEY),))
        elif t["kind"][i] == ANTI:
            pod.pod_anti_affinity = M.PodAffinitySpec(required=(M.PodAffinityTerm(
                label_selector=M.LabelSelector.make({"app": app, "role": "leader"}),
                topology_key=HOST_KEY),))
        elif t["kind"][i] == NODE_PREF:
            pod.node_affinity = M.NodeAffinitySpec(preferred=(
                M.PreferredSchedulingTerm(weight=int(t["na_weight"][i]), term=hot),))
        if t["spread_skew"][i]:
            pod.topology_spread.append(M.TopologySpreadConstraint(
                max_skew=int(t["spread_skew"][i]), topology_key=ZONE_KEY,
                when_unsatisfiable=("DoNotSchedule" if t["spread_dns"][i]
                                    else "ScheduleAnyway"),
                label_selector=own_app))
        pods.append(pod)
    return M.Cluster(nodes=nodes), pods


def to_program(trace: dict, config: dict):
    """(EncodedCluster, EncodedPods) for the engines."""
    from kubernetes_simulator_tpu.models.encode import encode

    ec, ep = encode(*program_objects(trace, config))
    # The encoded cluster has to be the node table the reference holds.
    r, n = ec.vocab._r, trace["nodes"]
    for k, name in (("cpu", "cpu"), ("mem", "memory"), ("pods", "pods")):
        if not np.array_equal(np.asarray(ec.allocatable)[:, r[name]], n[k]):
            raise RuntimeError(f"the program's cluster differs from the "
                               f"yardstick's node table in {k!r}")
    return ec, ep
