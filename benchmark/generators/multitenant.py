"""The multi-tenant accelerator deployment (BASELINE.json config 5), made by
the yardstick from the seed.

``generate`` gives a plain trace: the node table (cpu, mem, pods and the
extended resource ``tpu``, 0 on a node without the device plugin) and the pod
columns (arrival, gang, cpu, mem, tpu, priority, app, leader, tolerates),
numpy only. The node loop is a copy of the program's ``sim/synthetic.py``
``make_cluster(..., extended_resources={"google.com/tpu": (8, 0.25)})`` and
the pod loop a copy of the draws of ``make_workload(gang_fraction=0.05,
gang_size=4, extended_resource=("google.com/tpu", 8, 0.2),
with_tolerations=True)`` as ``config5_multitenant()`` calls it (the same draws
in the same order), so that no PR can move the traffic; the originals stay
for the program's own callers.

A pod's ``gang`` is its pod group (PAD = none): a gang is ``gangSize``
consecutive arrival slots, started at ``gangFraction`` of the slots outside
one. ``tpu`` is the pod's ``google.com/tpu`` request, 0 for the four fifths
that ask for none.

Every seed gets the same work: the cluster, the arrival times, the gang
layout (which arrival slots form a gang), the multiset of pods and the
accelerator pods are those of the configuration's ``baseSeed``; ``seed`` deals
the pods that ask for no accelerator (four fifths) onto their arrival slots in
another order. The accelerator pods keep their slots as the gangs do: the
extended resource runs out, and how many pods a batch places depends on the
order in which requests of 1, 2 and 8 meet the nodes that hold 8 (dealt too,
the placements of a batch moved by 2% between seeds at a constant batch time;
PERF.md §6, PR 33). Gangs are numbered by their first arrival slot, the
resources by the cluster's table and the one toleration by what it is, so
every seed hands the program the same tables.

``to_program`` hands the trace to the system under test as the objects its
ingest takes (``models.core`` ``Node`` / ``Pod``, ``models.encode.encode``),
the only place this file touches the program.
"""

from __future__ import annotations

import numpy as np

PAD = -1
MACHINE_SHAPES = [(16, 64), (32, 128), (64, 256), (96, 384)]  # cores, GiB
PODS_PER_NODE = 110.0
CPU_CHOICES = [0.25, 0.5, 1.0, 2.0, 4.0]
MEM_GIB_CHOICES = [0.5, 1.0, 2.0, 8.0]
PRIORITY_CHOICES = [0, 0, 0, 100, 1000]
ZONE_KEY = "topology.kubernetes.io/zone"
TOLERATION = ("dedicated", "batch")  # as ``make_workload`` writes it
DEALT = ("cpu", "mem", "priority", "app", "leader", "tolerates")


def node_table(nodes: int, seed: int, cl: dict) -> dict:
    """``make_cluster(nodes, seed, extended_resources={resource: (count,
    fraction)})``: a node's shape, then whether it holds the device plugin."""
    rng = np.random.default_rng(seed)
    acc = cl["accelerator"]
    cpu, mem = np.zeros(nodes, np.float32), np.zeros(nodes, np.float32)
    tpu = np.zeros(nodes, np.float32)
    for i in range(nodes):
        c, m = MACHINE_SHAPES[rng.integers(len(MACHINE_SHAPES))]
        cpu[i], mem[i] = float(c), float(m) * 2**30
        if rng.random() < acc["fraction"]:
            tpu[i] = float(acc["count"])
    return {
        "cpu": cpu, "mem": mem,
        "pods": np.full(nodes, PODS_PER_NODE, np.float32), "tpu": tpu,
        "zone": (np.arange(nodes) % cl["zones"]).astype(np.int32),
        "zones": min(cl["zones"], nodes),
        "hot": np.arange(nodes) % cl["hotEvery"] == 0,
    }


def pod_columns(pods: int, seed: int, wl: dict, count: int) -> dict:
    """``make_workload(pods, seed, gang_fraction, gang_size,
    extended_resource=(resource, count, fraction), with_tolerations=True)``:
    one pass, a pod's draws in its order."""
    rng = np.random.default_rng(seed + 1)
    cols = {
        "arrival": np.zeros(pods, np.float64),
        "gang": np.full(pods, PAD, np.int32),
        "cpu": np.zeros(pods, np.float32), "mem": np.zeros(pods, np.float32),
        "tpu": np.zeros(pods, np.float32),
        "priority": np.zeros(pods, np.int32), "app": np.zeros(pods, np.int32),
        "leader": np.zeros(pods, bool), "tolerates": np.zeros(pods, bool),
    }
    t, gang, left = 0.0, 0, 0
    for i in range(pods):
        t += float(rng.exponential(1.0 / wl["arrivalRate"]))
        cols["arrival"][i] = t
        cols["app"][i] = int(rng.integers(wl["numApps"]))
        cols["leader"][i] = not rng.random() < wl["workerFraction"]
        cols["cpu"][i] = float(rng.choice(CPU_CHOICES))
        cols["mem"][i] = float(rng.choice(MEM_GIB_CHOICES)) * 2**30
        cols["priority"][i] = int(rng.choice(PRIORITY_CHOICES))
        cols["tolerates"][i] = rng.random() < wl["tolerationFraction"]
        if rng.random() < wl["acceleratorFraction"]:
            cols["tpu"][i] = float(rng.choice([1, 2, count]))
        if left == 0 and rng.random() < wl["gangFraction"]:
            gang, left = gang + 1, wl["gangSize"]
        if left > 0:
            cols["gang"][i] = gang - 1
            left -= 1
    return cols


def generate(config: dict, nodes: int, tasks: int, seed: int) -> dict:
    wl, cl = config["workload"], config["cluster"]
    cols = pod_columns(tasks, wl["baseSeed"], wl, cl["accelerator"]["count"])
    free = np.nonzero(cols["tpu"] == 0)[0]  # the pods that are dealt
    deal = np.arange(tasks)
    deal[free] = np.random.default_rng(seed).permutation(free)
    for k in DEALT:
        cols[k] = cols[k][deal]
    return {"nodes": node_table(nodes, wl["baseSeed"], cl), "tasks": cols}


def program_objects(trace: dict, config: dict):
    """(Cluster, [Pod]) as ``make_cluster`` / ``make_workload`` build them."""
    from kubernetes_simulator_tpu.models import core as M

    n, t, cl = trace["nodes"], trace["tasks"], config["cluster"]
    resource = cl["accelerator"]["resource"]
    nodes = []
    for i in range(len(n["cpu"])):
        capacity = {"cpu": float(n["cpu"][i]), "memory": float(n["mem"][i]),
                    "pods": int(n["pods"][i])}
        labels = {
            ZONE_KEY: f"zone-{int(n['zone'][i])}",
            "topology.kubernetes.io/rack": f"rack-{i % cl['racks']}",
            "node.kubernetes.io/instance-type": f"type-{int(n['cpu'][i])}",
            "tier": "hot" if n["hot"][i] else "standard",
        }
        if n["tpu"][i] > 0:
            capacity[resource] = float(n["tpu"][i])
            labels["accelerator"] = resource.split("/")[-1]
        nodes.append(M.Node(name=f"node-{i}", capacity=capacity, labels=labels,
                            taints=[]))
    pods = []
    for i in range(len(t["arrival"])):
        pod = M.Pod(
            name=f"pod-{i}",
            labels={"app": f"app-{int(t['app'][i])}",
                    "role": "leader" if t["leader"][i] else "worker"},
            requests={"cpu": float(t["cpu"][i]), "memory": float(t["mem"][i])},
            priority=int(t["priority"][i]), arrival_time=float(t["arrival"][i]),
        )
        if t["tolerates"][i]:
            pod.tolerations.append(M.Toleration(
                key=TOLERATION[0], operator="Equal", value=TOLERATION[1]))
        if t["tpu"][i] > 0:
            pod.requests[resource] = float(t["tpu"][i])
        if t["gang"][i] != PAD:
            pod.pod_group = f"gang-{int(t['gang'][i])}"
        pods.append(pod)
    return M.Cluster(nodes=nodes), pods


def to_program(trace: dict, config: dict):
    """(EncodedCluster, EncodedPods) for the engines."""
    from kubernetes_simulator_tpu.models.encode import encode

    ec, ep = encode(*program_objects(trace, config))
    # The encoded cluster and the pod groups have to be the tables the
    # reference holds: the resources in the configuration's order, a gang
    # under the number the trace gives it.
    r, n = ec.vocab._r, trace["nodes"]
    if list(ec.vocab.resources) != list(config["resources"]):
        raise RuntimeError(f"the program numbers the resources "
                           f"{ec.vocab.resources}, the configuration states "
                           f"{config['resources']}")
    for k, name in zip(("cpu", "mem", "pods", "tpu"), config["resources"]):
        if not np.array_equal(np.asarray(ec.allocatable)[:, r[name]], n[k]):
            raise RuntimeError(f"the program's cluster differs from the "
                               f"yardstick's node table in {k!r}")
    if not np.array_equal(np.asarray(ep.group_id), trace["tasks"]["gang"]):
        raise RuntimeError("the program numbers the pod groups otherwise "
                           "than the trace")
    return ec, ep
