"""Synthetic cluster/workload generators for the [BASELINE] eval configs.

Config 1: 100 nodes / 1k pods, NodeResourcesFit + LeastAllocated.
Config 2: 5k nodes / 50k pods, full default plugin set (affinity, taints,
topology-spread). Config 4's Borg-2019-like 10k×1M generator (gangs,
priorities, alloc sets) lives in :mod:`.borg`.

All generators are seeded and deterministic (SURVEY.md §4.3).
"""

from __future__ import annotations

from typing import List, Optional, Tuple

import numpy as np

from ..models.core import (
    Cluster,
    LabelSelector,
    MatchExpression,
    Node,
    NodeAffinitySpec,
    NodeSelectorTerm,
    Pod,
    PodAffinitySpec,
    PodAffinityTerm,
    PreferredSchedulingTerm,
    Taint,
    Toleration,
    TopologySpreadConstraint,
    WeightedPodAffinityTerm,
)

MACHINE_SHAPES = [  # (cpu cores, memory GiB) mimicking heterogeneous fleets
    (16, 64),
    (32, 128),
    (64, 256),
    (96, 384),
]


def make_cluster(
    num_nodes: int,
    seed: int = 0,
    num_zones: int = 8,
    taint_fraction: float = 0.0,
    extended_resources: Optional[dict] = None,
) -> Cluster:
    """Heterogeneous nodes across zones/racks; optional taints and extended
    resources: ``{"google.com/tpu": (8, 0.25)}`` gives each node 8 with
    probability 0.25; a whole number in the share's place
    (``{"nvidia.com/gpu": (8, 810)}``) gives exactly that many nodes 8,
    drawn without replacement once every node has its shape."""
    rng = np.random.default_rng(seed)
    nodes: List[dict] = []
    for i in range(num_nodes):
        cpu, mem = MACHINE_SHAPES[rng.integers(len(MACHINE_SHAPES))]
        labels = {
            "topology.kubernetes.io/zone": f"zone-{i % num_zones}",
            "topology.kubernetes.io/rack": f"rack-{i % (num_zones * 4)}",
            "node.kubernetes.io/instance-type": f"type-{cpu}",
            "tier": "hot" if i % 5 == 0 else "standard",
        }
        taints = []
        if taint_fraction and rng.random() < taint_fraction:
            taints.append(Taint("dedicated", "batch", "NoSchedule"))
        capacity = {"cpu": float(cpu), "memory": float(mem) * 2**30, "pods": 110}
        if extended_resources:
            for r, (count, frac) in extended_resources.items():
                if not isinstance(frac, int) and rng.random() < frac:
                    capacity[r] = float(count)
                    labels["accelerator"] = r.split("/")[-1]
        nodes.append(dict(name=f"node-{i}", capacity=capacity, labels=labels, taints=taints))
    for r, (count, held) in (extended_resources or {}).items():
        if isinstance(held, int):
            for i in rng.choice(num_nodes, size=held, replace=False):
                nodes[i]["capacity"][r] = float(count)
                nodes[i]["labels"]["accelerator"] = r.split("/")[-1]
    return Cluster(nodes=[Node(**kw) for kw in nodes])


def make_workload(
    num_pods: int,
    seed: int = 0,
    arrival_rate: float = 100.0,
    duration_mean: Optional[float] = None,
    with_affinity: bool = False,
    with_spread: bool = False,
    with_tolerations: bool = False,
    num_apps: int = 20,
    gang_fraction: float = 0.0,
    gang_size: int = 4,
    extended_resource: Optional[Tuple[str, int, float]] = None,
    gang_sizes: Optional[dict] = None,
    job_extended_resource: Optional[dict] = None,
    job_durations: Optional[dict] = None,
) -> Tuple[List[Pod], dict]:
    """Pods in arrival order with app labels; optional affinity/spread/
    toleration terms, gangs, extended-resource requests. ``gang_sizes``
    (a job-size mix, ``{workers: share}``) makes the trace job by job
    instead (:func:`make_job_workload`)."""
    if gang_sizes:
        return make_job_workload(
            num_pods, seed=seed, arrival_rate=arrival_rate, num_apps=num_apps,
            gang_sizes=gang_sizes, job_extended_resource=job_extended_resource,
            job_durations=job_durations,
        )
    rng = np.random.default_rng(seed + 1)
    pods: List[Pod] = []
    t = 0.0
    gang_id = 0
    gang_left = 0
    gang_name = None
    for i in range(num_pods):
        t += float(rng.exponential(1.0 / arrival_rate))
        app = f"app-{int(rng.integers(num_apps))}"
        labels = {"app": app, "role": "worker" if rng.random() < 0.8 else "leader"}
        requests = {
            "cpu": float(rng.choice([0.25, 0.5, 1.0, 2.0, 4.0])),
            "memory": float(rng.choice([0.5, 1.0, 2.0, 8.0])) * 2**30,
        }
        pod = Pod(
            name=f"pod-{i}",
            labels=labels,
            requests=requests,
            priority=int(rng.choice([0, 0, 0, 100, 1000])),
            arrival_time=t,
            duration=float(rng.exponential(duration_mean)) if duration_mean else None,
        )
        if with_tolerations and rng.random() < 0.3:
            pod.tolerations.append(Toleration(key="dedicated", operator="Equal", value="batch"))
        if with_affinity:
            r = rng.random()
            if r < 0.10:
                pod.pod_affinity = PodAffinitySpec(
                    required=(
                        PodAffinityTerm(
                            label_selector=LabelSelector.make({"app": app}),
                            topology_key="topology.kubernetes.io/zone",
                        ),
                    )
                )
            elif r < 0.18:
                pod.pod_anti_affinity = PodAffinitySpec(
                    required=(
                        PodAffinityTerm(
                            label_selector=LabelSelector.make({"app": app, "role": "leader"}),
                            topology_key="kubernetes.io/hostname",
                        ),
                    )
                )
            elif r < 0.35:
                pod.node_affinity = NodeAffinitySpec(
                    preferred=(
                        PreferredSchedulingTerm(
                            weight=int(rng.integers(1, 100)),
                            term=NodeSelectorTerm(
                                (MatchExpression.make("tier", "In", ["hot"]),)
                            ),
                        ),
                    )
                )
        if with_spread and rng.random() < 0.25:
            pod.topology_spread.append(
                TopologySpreadConstraint(
                    max_skew=int(rng.choice([1, 2, 5])),
                    topology_key="topology.kubernetes.io/zone",
                    when_unsatisfiable="DoNotSchedule" if rng.random() < 0.5 else "ScheduleAnyway",
                    label_selector=LabelSelector.make({"app": app}),
                )
            )
        if extended_resource is not None:
            rname, count, frac = extended_resource
            if rng.random() < frac:
                pod.requests[rname] = float(rng.choice([1, 2, count]))
        if gang_fraction and gang_left == 0 and rng.random() < gang_fraction:
            gang_name = f"gang-{gang_id}"
            gang_id += 1
            gang_left = gang_size
        if gang_left > 0:
            pod.pod_group = gang_name
            gang_left -= 1
        pods.append(pod)
    meta = {"num_gangs": gang_id, "makespan": t}
    return pods, meta


def make_job_workload(
    num_pods: int,
    seed: int = 0,
    arrival_rate: float = 100.0,
    num_apps: int = 20,
    gang_sizes: Optional[dict] = None,
    job_extended_resource: Optional[dict] = None,
    job_durations: Optional[dict] = None,
) -> Tuple[List[Pod], dict]:
    """A training cluster's trace, job by job: a job's worker count is drawn
    from ``gang_sizes`` (``{workers: share}``) until the pods are dealt out
    (the last job takes what is left); a job of two or more workers is a pod
    group with ``minMember`` = its size, its workers consecutive arrivals.
    ``job_extended_resource`` (``{"resource", "counts": {count: share},
    "wideFrom", "smallJobFraction", "wideJobFraction"}``): a job asks for the
    extended resource with probability ``smallJobFraction`` below
    ``wideFrom`` workers and ``wideJobFraction`` from there up, and all its
    workers ask the same count. cpu and memory requests, priority, app and
    role are :func:`make_workload`'s sets, a worker's draws in its order.
    A job's draws (size, whether it asks, the count) come before its
    workers'. ``job_durations`` (``{"median", "mean"}`` seconds, and
    optionally ``"diurnal"``): a job is then ONE arrival, as a standing job
    queue wants it (``WhatIfEngine(retry_groups=True)``): every member
    carries its job's arrival time and priority (the first member's) and
    its job's duration, log-normal, one draw a job (a stream of its own,
    ``seed + 2``); with ``diurnal`` the arrival rate is ``1 - diurnal *
    cos(2 pi t / span)`` times the mean over the trace's span."""
    rng = np.random.default_rng(seed + 1)
    sizes = [int(k) for k in gang_sizes]
    shares = np.asarray([float(gang_sizes[k]) for k in gang_sizes], np.float64)
    shares = shares / shares.sum()
    ext = job_extended_resource
    if ext:
        counts = [float(k) for k in ext["counts"]]
        cshares = np.asarray([float(ext["counts"][k]) for k in ext["counts"]])
        cshares = cshares / cshares.sum()
    pods: List[Pod] = []
    t = 0.0
    jobs = gangs = 0
    while len(pods) < num_pods:
        size = min(int(rng.choice(sizes, p=shares)), num_pods - len(pods))
        ask = 0.0
        if ext:
            frac = (
                ext["wideJobFraction"] if size >= int(ext["wideFrom"])
                else ext["smallJobFraction"]
            )
            if rng.random() < float(frac):
                ask = float(rng.choice(counts, p=cshares))
        for _ in range(size):
            i = len(pods)
            t += float(rng.exponential(1.0 / arrival_rate))
            app = f"app-{int(rng.integers(num_apps))}"
            role = "worker" if rng.random() < 0.8 else "leader"
            requests = {
                "cpu": float(rng.choice([0.25, 0.5, 1.0, 2.0, 4.0])),
                "memory": float(rng.choice([0.5, 1.0, 2.0, 8.0])) * 2**30,
            }
            if ask:
                requests[ext["resource"]] = ask
            pods.append(Pod(
                name=f"pod-{i}",
                labels={"app": app, "role": role},
                requests=requests,
                priority=int(rng.choice([0, 0, 0, 100, 1000])),
                arrival_time=t,
                pod_group=f"gang-{gangs}" if size > 1 else None,
            ))
        jobs += 1
        gangs += size > 1
    if job_durations:
        sigma = float(np.sqrt(2.0 * np.log(
            job_durations["mean"] / job_durations["median"])))
        draws = np.random.default_rng(seed + 2).lognormal(
            np.log(job_durations["median"]), sigma, size=num_pods)
        span, amp = num_pods / arrival_rate, float(job_durations.get("diurnal", 0))
        grid = np.linspace(0.0, span, 1 << 16)
        warped = grid - amp * span / (2 * np.pi) * np.sin(2 * np.pi * grid / span)
        head = None
        for i, pod in enumerate(pods):
            if head is None or pod.pod_group is None or pod.pod_group != head[0]:
                at = pod.arrival_time
                at = float(np.interp(min(at, span), warped, grid) + max(at - span, 0.0))
                head = (pod.pod_group, at, pod.priority,
                        float(np.float32(draws[i])))
            pod.arrival_time, pod.priority, pod.duration = head[1:]
        t = pods[-1].arrival_time
    return pods, {"num_jobs": jobs, "num_gangs": gangs, "makespan": t}


def make_chaos_timeline(
    num_nodes: int,
    seed: int = 0,
    horizon: float = 100.0,
    mtbf: float = 200.0,
    mttr: float = 20.0,
    node_fraction: float = 0.2,
    max_events: Optional[int] = None,
):
    """Seeded chaos campaign: per-node exponential failure/recovery pairs.

    Each node in a ``node_fraction`` sample draws failure gaps from
    ``Exp(mtbf)`` and outage lengths from ``Exp(mttr)``, emitting
    ``node_down``/``node_up`` pairs until ``horizon``. ``mttr=0`` means
    nodes stay down (pure-failure campaign, no ``node_up``). Events are
    returned sorted by time — ready for ``validate_node_events`` and any
    engine's ``node_events=`` argument. Deterministic per seed.
    """
    from .runtime import NodeEvent, validate_node_events

    if mtbf <= 0:
        raise ValueError(f"chaos mtbf must be > 0, got {mtbf}")
    if mttr < 0:
        raise ValueError(f"chaos mttr must be >= 0, got {mttr}")
    if not 0.0 < node_fraction <= 1.0:
        raise ValueError(
            f"chaos node_fraction must be in (0, 1], got {node_fraction}"
        )
    rng = np.random.default_rng(seed)
    n_pick = max(1, int(round(num_nodes * node_fraction)))
    targets = rng.choice(num_nodes, size=min(n_pick, num_nodes), replace=False)
    events: List = []
    for node in sorted(int(n) for n in targets):
        t = float(rng.exponential(mtbf))
        while t < horizon:
            events.append(NodeEvent(time=t, kind="node_down", node=node))
            if mttr <= 0:
                break  # stays down for the rest of the campaign
            up = t + max(float(rng.exponential(mttr)), 1e-9)
            if up >= horizon:
                break
            events.append(NodeEvent(time=up, kind="node_up", node=node))
            t = up + max(float(rng.exponential(mtbf)), 1e-9)
    events.sort(key=lambda e: (e.time, e.node))
    if max_events is not None and len(events) > max_events:
        # Truncate at a pair boundary: never strand a node_up whose
        # node_down was cut (validation would reject it).
        events = events[:max_events]
        down = set()
        kept = []
        for e in events:
            if e.kind == "node_up" and e.node not in down:
                continue
            if e.kind == "node_down":
                down.add(e.node)
            elif e.kind == "node_up":
                down.discard(e.node)
            kept.append(e)
        events = kept
    return validate_node_events(events, num_nodes)


def config1(num_nodes: int = 100, num_pods: int = 1000, seed: int = 0):
    """[BASELINE] config #1: default kube-scheduler shape, fit+LeastAllocated."""
    cluster = make_cluster(num_nodes, seed=seed)
    pods, _ = make_workload(num_pods, seed=seed)
    plugins = [{"name": "NodeResourcesFit", "args": {"strategy": "LeastAllocated"}}]
    return cluster, pods, plugins


def config2(num_nodes: int = 5000, num_pods: int = 50_000, seed: int = 0):
    """[BASELINE] config #2: full default plugin set at 5k/50k scale."""
    cluster = make_cluster(num_nodes, seed=seed, taint_fraction=0.1)
    pods, _ = make_workload(
        num_pods, seed=seed, with_affinity=True, with_spread=True, with_tolerations=True
    )
    return cluster, pods, None  # None → full default plugin set


def config5_multitenant(num_nodes: int = 1000, num_pods: int = 10_000, seed: int = 0):
    """[BASELINE] config #5 shape: extended resources + pod-group coscheduling."""
    cluster = make_cluster(
        num_nodes, seed=seed, extended_resources={"google.com/tpu": (8, 0.25)}
    )
    pods, meta = make_workload(
        num_pods,
        seed=seed,
        gang_fraction=0.05,
        gang_size=4,
        extended_resource=("google.com/tpu", 8, 0.2),
        with_tolerations=True,
    )
    return cluster, pods, None
