"""The gang-scheduled training deployment (a shared GPU cluster at the job
shape of Alibaba PAI's ``cluster-trace-gpu-v2020``), made by the yardstick
from the seed.

``generate`` gives a plain trace: the node table (cpu, mem, pods and the
extended resource ``gpu``, 0 on a node without the device plugin) and the pod
columns (arrival, gang, cpu, mem, gpu, priority, app, leader), numpy only. The
node loop is a copy of the program's ``sim/synthetic.py``
``make_cluster(..., extended_resources={"nvidia.com/gpu": (8, 0.45)})`` and
the pod loop a copy of the draws of ``make_job_workload(gang_sizes=...,
job_extended_resource=...)`` (the same draws in the same order:
``examples/config8_gpu_jobs_gangs.yaml`` spells the deployment for the CLI),
so that no PR can move the traffic; the originals stay for the program's own
callers.

A job is ``workers`` consecutive arrival slots, its worker count drawn from
the configuration's ``jobSizes`` until the pods are dealt out. A job of two or
more workers is a pod group (``gang``; PAD = none) with ``minMember`` = its
size: all its workers bind or none. A job asks for the extended resource with
probability ``smallJobFraction`` below ``wideFrom`` workers and
``wideJobFraction`` from there up, and all its workers ask the same count
(``gpu``; 0 = none).

Every seed gets baseSeed's cluster, arrival times, job layout and GPU jobs at
their slots, and every pod's cpu and memory up to the arrival slot
``workload.dealFrom``: by then the extended resource has run out in every
scenario (scenario 0's last GPU bind is the slot before), and which wide group
still fits, so what a batch places, hangs on where the pods before it went:
with cpu and memory dealt over the whole trace 7 of 32 seeds rolled three more
wide groups back in scenario 0 and placed 0.33% fewer pods over the batch
(PERF.md §6, PR 37; PR 33's lesson). ``seed`` deals what the scheduler reads
BEHIND that slot: the (cpu, memory) pairs of the pods there that ask for no
GPU, among those pods (LeastAllocated reads them, so every such pod and every
pod behind it may go to another node); and, among all the pods that ask for no
GPU, the labels nothing reads (priority, app, role). Gangs are numbered by
their first arrival slot, so every seed hands the program the same tables. A
smaller trace (a rehearsal) scales ``dealFrom`` with its pods.

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
DEALT = ("priority", "app", "leader")  # read by nothing
DEALT_LATE = ("cpu", "mem")  # read by the fit and the score


def node_table(nodes: int, seed: int, cl: dict) -> dict:
    """``make_cluster(nodes, seed, extended_resources={resource: (count,
    round(fraction * nodes))})``: every node's shape, then exactly that many
    nodes, drawn without replacement, hold the device plugin."""
    rng = np.random.default_rng(seed)
    acc = cl["accelerator"]
    cpu, mem = np.zeros(nodes, np.float32), np.zeros(nodes, np.float32)
    gpu = np.zeros(nodes, np.float32)
    for i in range(nodes):
        c, m = MACHINE_SHAPES[rng.integers(len(MACHINE_SHAPES))]
        cpu[i], mem[i] = float(c), float(m) * 2**30
    held = round(acc["fraction"] * nodes)
    gpu[rng.choice(nodes, size=held, replace=False)] = float(acc["count"])
    return {
        "cpu": cpu, "mem": mem,
        "pods": np.full(nodes, PODS_PER_NODE, np.float32), "gpu": gpu,
        "zone": (np.arange(nodes) % cl["zones"]).astype(np.int32),
        "zones": min(cl["zones"], nodes),
        "hot": np.arange(nodes) % cl["hotEvery"] == 0,
    }


def _shares(table: dict):
    keys = list(table)
    p = np.asarray([float(table[k]) for k in keys], np.float64)
    return keys, p / p.sum()


def pod_columns(pods: int, seed: int, wl: dict) -> dict:
    """``make_job_workload(pods, seed, gang_sizes=jobSizes,
    job_extended_resource=gpuJobs)``: job by job, a job's draws (size, whether
    it asks, the count) before its workers', a worker's draws in its order."""
    rng = np.random.default_rng(seed + 1)
    sizes, shares = _shares(wl["jobSizes"])
    sizes = [int(k) for k in sizes]
    ext = wl["gpuJobs"]
    counts, cshares = _shares(ext["counts"])
    counts = [float(k) for k in counts]
    cols = {
        "arrival": np.zeros(pods, np.float64),
        "gang": np.full(pods, PAD, np.int32),
        "cpu": np.zeros(pods, np.float32), "mem": np.zeros(pods, np.float32),
        "gpu": np.zeros(pods, np.float32),
        "priority": np.zeros(pods, np.int32), "app": np.zeros(pods, np.int32),
        "leader": np.zeros(pods, bool),
    }
    t, i, gangs = 0.0, 0, 0
    while i < pods:
        size = min(int(rng.choice(sizes, p=shares)), pods - i)
        frac = (ext["wideJobFraction"] if size >= int(ext["wideFrom"])
                else ext["smallJobFraction"])
        ask = 0.0
        if rng.random() < float(frac):
            ask = float(rng.choice(counts, p=cshares))
        for _ in range(size):
            t += float(rng.exponential(1.0 / wl["arrivalRate"]))
            cols["arrival"][i] = t
            cols["app"][i] = int(rng.integers(wl["numApps"]))
            cols["leader"][i] = not rng.random() < wl["workerFraction"]
            cols["cpu"][i] = float(rng.choice(CPU_CHOICES))
            cols["mem"][i] = float(rng.choice(MEM_GIB_CHOICES)) * 2**30
            cols["priority"][i] = int(rng.choice(PRIORITY_CHOICES))
            cols["gpu"][i] = ask
            if size > 1:
                cols["gang"][i] = gangs
            i += 1
        gangs += size > 1
    return cols


def generate(config: dict, nodes: int, tasks: int, seed: int) -> dict:
    wl, cl = config["workload"], config["cluster"]
    cols = pod_columns(tasks, wl["baseSeed"], wl)
    rng = np.random.default_rng(seed)
    free = np.nonzero(cols["gpu"] == 0)[0]  # the pods that are dealt
    late = free[free >= wl["dealFrom"] * tasks // wl["tasks"]]
    for among, keys in ((free, DEALT), (late, DEALT_LATE)):
        deal = np.arange(tasks)
        deal[among] = rng.permutation(among)
        for k in keys:
            cols[k] = cols[k][deal]
    return {"nodes": node_table(nodes, wl["baseSeed"], cl), "tasks": cols}


def program_objects(trace: dict, config: dict):
    """(Cluster, [Pod]) as ``make_cluster`` / ``make_job_workload`` build them."""
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
        if n["gpu"][i] > 0:
            capacity[resource] = float(n["gpu"][i])
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
        if t["gpu"][i] > 0:
            pod.requests[resource] = float(t["gpu"][i])
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
    for k, name in zip(("cpu", "mem", "pods", "gpu"), config["resources"]):
        if not np.array_equal(np.asarray(ec.allocatable)[:, r[name]], n[k]):
            raise RuntimeError(f"the program's cluster differs from the "
                               f"yardstick's node table in {k!r}")
    if not np.array_equal(np.asarray(ep.group_id), trace["tasks"]["gang"]):
        raise RuntimeError("the program numbers the pod groups otherwise "
                           "than the trace")
    return ec, ep
