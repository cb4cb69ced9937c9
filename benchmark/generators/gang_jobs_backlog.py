"""The shared GPU training cluster WITH its standing job queue, made by the
yardstick from the seed: ``generators/gang_jobs.py``'s cluster and job shapes
(imported, not copied: the node table, the job sizes, which jobs ask for GPUs
and how many, every member's cpu and memory) with what that deployment lacks.

* **A job is one arrival.** Every member carries its job's arrival time (the
  first member's) and its job's priority (the first member's draw): kube's
  QueueSort with the pod group's creation time as the tie keeps a job's
  members together in the queue.
* **Arrivals under a diurnal factor**, so that the queue builds towards the
  peak (it does not drain after it where the tail of long jobs holds its GPUs
  to the batch's end: the configuration's ``assumed`` says what scenario 0
  does): the jobs come as a Poisson stream whose rate is
  ``arrivalRate * (1 - diurnal * cos(2 pi t / span))`` pods a second over the
  batch's span (``tasks / arrivalRate`` seconds at the deployment's node
  count; on a smaller node table, a rehearsal, the rate is that table's share
  of the nodes): lowest at both ends, highest in the middle, the mean rate
  ``arrivalRate``. The base generator's own arrival draws (exponential gaps)
  are kept and warped, so the job layout is the accepted deployment's.
* **Durations per job**, one draw a job, log-normal with the configuration's
  ``duration.median`` and ``duration.mean`` seconds (PAI: tasks run minutes
  to days); float32, what the program reads.

Every seed gets baseSeed's cluster, job layout, arrival times, priorities,
durations and GPU asks, and every pod's cpu and memory up to the arrival slot
``workload.dealFrom``. ``seed`` deals, among the pods that ask for no GPU,
the (cpu, memory) pairs of the pods behind that slot and the labels nothing
reads (app, role). Priority is no such label here: the queue is sorted by it.

``to_program`` hands the trace to the system under test as
``generators/gang_jobs.py`` does, with each pod's duration.
"""

from __future__ import annotations

import numpy as np

from generators import gang_jobs as base

PAD = base.PAD
DEALT = ("app", "leader")  # read by nothing
DEALT_LATE = base.DEALT_LATE


def job_of(gang: np.ndarray) -> np.ndarray:
    """[P] each pod's job: its gang's first member, itself where it is in none."""
    pods = np.arange(len(gang))
    first = np.full(int(gang.max(initial=-1)) + 1, len(gang), np.int64)
    np.minimum.at(first, gang[gang != PAD], pods[gang != PAD])
    return np.where(gang != PAD, first[np.clip(gang, 0, None)], pods)


def warp(t: np.ndarray, span: float, diurnal: float) -> np.ndarray:
    """Arrival times of a stream whose rate is ``1 - diurnal * cos(2 pi t /
    span)`` times the mean, from the times ``t`` of a stream at the mean rate
    (the inverse of the rate's integral, on a fine grid)."""
    grid = np.linspace(0.0, span, 1 << 16)
    integral = grid - diurnal * span / (2 * np.pi) * np.sin(2 * np.pi * grid / span)
    return np.interp(np.minimum(t, span), integral, grid) + np.maximum(t - span, 0.0)


def generate(config: dict, nodes: int, tasks: int, seed: int) -> dict:
    wl, cl = config["workload"], config["cluster"]
    rate = wl["arrivalRate"] * nodes / cl["nodes"]
    cols = base.pod_columns(tasks, wl["baseSeed"], {**wl, "arrivalRate": rate})
    job = job_of(cols["gang"])
    cols["arrival"] = warp(cols["arrival"][job], tasks / rate, wl["diurnal"])
    cols["priority"] = cols["priority"][job]
    spec = wl["duration"]
    sigma = np.sqrt(2.0 * np.log(spec["mean"] / spec["median"]))
    draws = np.random.default_rng(wl["baseSeed"] + 2).lognormal(
        np.log(spec["median"]), sigma, size=tasks)
    cols["duration"] = draws[job].astype(np.float32)
    rng = np.random.default_rng(seed)
    free = np.nonzero(cols["gpu"] == 0)[0]  # the pods that are dealt
    late = free[free >= wl["dealFrom"] * tasks // wl["tasks"]]
    for among, keys in ((free, DEALT), (late, DEALT_LATE)):
        deal = np.arange(tasks)
        deal[among] = rng.permutation(among)
        for k in keys:
            cols[k] = cols[k][deal]
    return {"nodes": base.node_table(nodes, wl["baseSeed"], cl), "tasks": cols}


def to_program(trace: dict, config: dict):
    """(EncodedCluster, EncodedPods) for the engines."""
    from kubernetes_simulator_tpu.models.encode import encode

    cluster, pods = base.program_objects(trace, config)
    for pod, d in zip(pods, trace["tasks"]["duration"]):
        pod.duration = float(d)
    ec, ep = encode(cluster, pods)
    r, n = ec.vocab._r, trace["nodes"]
    if list(ec.vocab.resources) != list(config["resources"]):
        raise RuntimeError(f"the program numbers the resources "
                           f"{ec.vocab.resources}, the configuration states "
                           f"{config['resources']}")
    for k, name in zip(("cpu", "mem", "pods", "gpu"), config["resources"]):
        if not np.array_equal(np.asarray(ec.allocatable)[:, r[name]], n[k]):
            raise RuntimeError(f"the program's cluster differs from the "
                               f"yardstick's node table in {k!r}")
    for k, have in (("gang", ep.group_id), ("duration", ep.duration),
                    ("priority", ep.priority)):
        if not np.array_equal(np.asarray(have), trace["tasks"][k]):
            raise RuntimeError(f"the program's pods differ from the trace in {k!r}")
    return ec, ep
