"""Simulation runtime — layers L4/L7 (SURVEY.md §1, §3.1).

Event-driven replay over a virtual clock: pod arrivals come from the trace,
bindings update the shared state used by subsequent pods, pod completions
free resources, node events perturb the cluster mid-replay (failure
injection, SURVEY.md §5). No apiserver/kubelet — the simulator IS the fake
backend (SURVEY.md §4.4).

This module is the **cpu** strategy (the [BASELINE]-mandated default path).
The `jax` strategy in :mod:`.jax_runtime` replays the same encoded trace as
a fused device program and must produce placements this engine agrees with
on parity workloads.
"""

from __future__ import annotations

import heapq
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import numpy as np

from ..framework.framework import FrameworkConfig, SchedulerFramework, ScheduleResult
from ..framework.queue import SchedulingQueue
from ..framework.registry import register_strategy
from ..models.encode import PAD, EncodedCluster, EncodedPods
from ..models.state import SchedState, bind, init_state, unbind
from ..utils.metrics import (
    fragmentation_gauges,
    round_fragmentation,
    series_gauges,
    utilization_means,
)
from .telemetry import ReplayTelemetry, TelemetryCollector, TelemetryConfig

# Event kinds, in tie-break order at equal timestamps: node events first,
# then completions (free resources), then arrivals, then permit timeouts.
EV_NODE = 0
EV_FINISH = 1
EV_ARRIVAL = 2
EV_PERMIT_TIMEOUT = 3

DEFAULT_PERMIT_TIMEOUT = 600.0  # virtual seconds a gang may hold reservations


@dataclass
class NodeEvent:
    """Cluster perturbation at a virtual timestamp (failure injection)."""

    time: float
    kind: str  # one of _EVENT_KINDS
    node: int
    scale: float = 1.0


# ``node_cordon``: the node takes no new bind from the event on and keeps
# what runs there (``kubectl cordon``). Under a ``DisruptionBudget`` it is
# the start of the node's drain; without one it only closes the node until
# a ``node_up``.
_EVENT_KINDS = ("node_down", "node_up", "capacity_scale", "node_cordon")


@dataclass
class DisruptionBudget:
    """What a maintenance drain may do to the applications of a trace: the
    PodDisruptionBudget rule at chunk-boundary granularity (Kubernetes docs,
    Disruptions / Safely Drain a Node; Borg's "tasks from a job that can be
    simultaneously down", Verma et al. 2015 §4). Data of a scenario
    (``sim.whatif.Scenario.budget``) or of one replay
    (``JaxReplayEngine.replay(budget=...)``); the rule itself is
    ``BoundaryOps.budget_events`` on the host and
    ``WhatIfEngine._evict_fn`` on the device.

    ``app_of`` ``[P]``: each task's application (a budget's selector,
    resolved; -1: under no budget, never refused). ``max_unavailable``
    ``[A]``: how many tasks of an application may be down at once by
    evictions, voluntary or forced, and not re-bound since (a dropped or
    stranded evicted task stays counted: its replacement is Pending). A
    cordoned node's tasks are evicted in walk order (the order of the
    ``node_cordon`` events, a node's tasks by id) while their application's
    count is below its limit; the rest are asked again at the next boundary.
    A cordoned node that holds nothing goes out and is back ``out_for``
    boundaries later; one that still holds tasks ``grace`` boundaries after
    its cordon (at the next boundary where ``grace`` is 0) goes out there
    and loses them whatever the budgets say. A ``node_down`` evicts past the
    budgets too and counts against them."""

    app_of: np.ndarray
    max_unavailable: np.ndarray
    grace: int = 0
    out_for: int = 1

    def __post_init__(self):
        self.app_of = np.asarray(self.app_of, np.int32)
        self.max_unavailable = np.asarray(self.max_unavailable, np.int32)
        if self.app_of.ndim != 1 or self.max_unavailable.ndim != 1:
            raise ValueError("budget: app_of [P] and max_unavailable [A]")
        if self.app_of.size and int(self.app_of.max()) >= len(self.max_unavailable):
            raise ValueError(
                f"budget: app_of names application {int(self.app_of.max())} "
                f"of {len(self.max_unavailable)}"
            )
        if (self.max_unavailable < 0).any():
            raise ValueError("budget: max_unavailable must be >= 0")
        if int(self.grace) < 0 or int(self.out_for) < 1:
            raise ValueError("budget: grace >= 0 and out_for >= 1 boundaries")


def validate_node_events(
    events: Optional[List[NodeEvent]], num_nodes: int
) -> List[NodeEvent]:
    """Up-front validation shared by every engine (CPU, device replay,
    what-if timelines): a malformed timeline raises an actionable
    ``ValueError`` instead of silently misbehaving mid-replay. Checks:
    known kind, node index in range, finite non-negative non-decreasing
    times, ``node_up`` only after a ``node_down`` on the same node (it
    clears a ``node_cordon`` of the node too; a ``node_down`` may follow a
    ``node_cordon``: a cordoned node fails like any other; a second
    ``node_cordon`` is no error: a drained node comes back by itself), and a
    non-negative ``capacity_scale`` factor. Returns the (unmodified)
    list for chaining."""
    events = events or []
    down: set = set()
    prev_t = -np.inf
    for i, ev in enumerate(events):
        where = f"node_events[{i}]"
        if ev.kind not in _EVENT_KINDS:
            raise ValueError(
                f"{where}: unknown kind {ev.kind!r} (expected one of "
                f"{', '.join(_EVENT_KINDS)})"
            )
        if not (0 <= int(ev.node) < num_nodes):
            raise ValueError(
                f"{where}: node {ev.node} out of range for a cluster of "
                f"{num_nodes} nodes"
            )
        t = float(ev.time)
        if not np.isfinite(t) or t < 0:
            raise ValueError(
                f"{where}: time {ev.time!r} must be a finite value >= 0"
            )
        if t < prev_t:
            raise ValueError(
                f"{where}: time {t} is before the previous event's "
                f"{prev_t} — timelines must be sorted by time (the "
                f"checkpoint event cursor and the boundary-granular "
                f"device application both assume it)"
            )
        prev_t = t
        if ev.kind == "node_down":
            down.add(int(ev.node))
        elif ev.kind == "node_up":
            if int(ev.node) not in down:
                raise ValueError(
                    f"{where}: node_up for node {ev.node} without a prior "
                    f"node_down — recovery of a node that never failed "
                    f"usually means a mis-built timeline"
                )
            down.discard(int(ev.node))
        elif ev.kind == "capacity_scale" and (
            not np.isfinite(float(ev.scale)) or float(ev.scale) < 0
        ):
            raise ValueError(
                f"{where}: capacity_scale factor {ev.scale!r} must be a "
                f"finite value >= 0"
            )
    return events


def events_hash(events: Optional[List[NodeEvent]]) -> np.ndarray:
    """Stable 32-byte digest of a timeline (uint8[32]) — stored in
    boundary-mode checkpoint blobs so a resume under a DIFFERENT event
    list is rejected instead of silently re-applying or skipping
    events."""
    import hashlib

    items = tuple(
        (float(e.time), str(e.kind), int(e.node), float(e.scale))
        for e in (events or [])
    )
    digest = hashlib.sha256(repr(items).encode()).digest()
    return np.frombuffer(digest, dtype=np.uint8).copy()


@dataclass
class ReplayResult:
    assignments: np.ndarray  # [P] i32 node per pod (PAD = never placed)
    placed: int
    unschedulable: int
    preemptions: int
    attempts: int
    wall_clock_s: float
    placements_per_sec: float
    virtual_makespan: float
    utilization: Dict[str, float]
    state: SchedState
    # Pods dropped on retry-buffer overflow (device retry/kube-preemption
    # paths; [K8S] keeps everything — a nonzero value means placements
    # were lost to buffer capacity, not infeasibility).
    retry_dropped: int = 0
    # Chaos disruption counters — node_down NoExecute evictions, kept
    # DISTINCT from scheduler-initiated `preemptions` so failure injection
    # is never conflated with PostFilter victim selection. `rescheduled`
    # counts evicted pods that later re-bound; `stranded` = evicted and
    # never re-placed by trace end; latency is mean virtual time from
    # eviction to re-bind (boundary-granular on the device path).
    evictions: int = 0
    evict_rescheduled: int = 0
    evict_stranded: int = 0
    evict_latency_mean: float = 0.0
    # Utilization economics (round 13): end-of-replay fragmentation /
    # stranded-capacity / packing gauges (utils.metrics
    # fragmentation_gauges) computed from the committed state against the
    # restored allocatable, with the still-pending pod set. Bit-identical
    # CPU engine ↔ device paths. None only on legacy callers that build
    # the result by hand.
    fragmentation: Optional[dict] = None
    # Under a retry buffer (greedy_replay): per pod the boundary whose retry
    # pass bound it, -1 for an arrival bind, -2 / -3 / -4 for a pod with no
    # node (sim.boundary.BoundaryOps.bind_boundary_codes).
    bind_boundary: Optional[np.ndarray] = None
    # ``greedy_replay(retry_groups=True)``: ``sim.waves.GROUP_COUNTERS``.
    group_counts: Optional[dict] = None
    # Telemetry (sim.telemetry.ReplayTelemetry) — None at granularity
    # "off". Latency histograms, rejection attribution, series, phase
    # timers; see the telemetry module docstring for cross-engine
    # parity semantics.
    telemetry: Optional["ReplayTelemetry"] = None

    def summary(self) -> dict:
        out = {
            "placed": self.placed,
            "unschedulable": self.unschedulable,
            "preemptions": self.preemptions,
            "attempts": self.attempts,
            "wall_clock_s": round(self.wall_clock_s, 4),
            "placements_per_sec": round(self.placements_per_sec, 1),
            "virtual_makespan": self.virtual_makespan,
            "utilization": {k: round(v, 4) for k, v in self.utilization.items()},
            "retry_dropped": self.retry_dropped,
            "evictions": self.evictions,
            "evict_rescheduled": self.evict_rescheduled,
            "evict_stranded": self.evict_stranded,
            "evict_latency_mean": round(self.evict_latency_mean, 4),
        }
        if self.fragmentation is not None:
            out["fragmentation"] = round_fragmentation(self.fragmentation)
        if self.telemetry is not None:
            out["telemetry"] = self.telemetry.summary()
        return out


class CpuReplayEngine:
    def __init__(
        self,
        ec: EncodedCluster,
        pods: EncodedPods,
        config: Optional[FrameworkConfig] = None,
        permit_timeout: float = DEFAULT_PERMIT_TIMEOUT,
        telemetry=None,
    ):
        self.ec = ec
        self.pods = pods
        self.fw = SchedulerFramework(ec, pods, config)
        self.permit_timeout = permit_timeout
        # Telemetry granularity (str | TelemetryConfig | None→"summary").
        # The event engine is the exact oracle: latencies are recorded at
        # the event clock, rejections at the failing attempt itself.
        self.telemetry_cfg = TelemetryConfig.resolve(telemetry)

    # -- helpers -----------------------------------------------------------

    def _affinity_dependent(self, p: int) -> bool:
        pods = self.pods
        return bool(
            pods.aff_req[p, 0] >= 0
            or pods.anti_req[p, 0] >= 0
            or pods.spread_g[p, 0] >= 0
        )

    # -- main loop ---------------------------------------------------------

    def replay(self, node_events: Optional[List[NodeEvent]] = None) -> ReplayResult:
        ec, pods = self.ec, self.pods
        validate_node_events(node_events, ec.num_nodes)
        st = init_state(ec, pods)
        q = SchedulingQueue()
        events: List[Tuple[float, int, int, int]] = []  # (time, kind, seq, payload)
        seq = 0

        def push_event(t: float, kind: int, payload: int) -> int:
            nonlocal seq
            s = seq
            heapq.heappush(events, (t, kind, s, payload))
            seq += 1
            return s

        to_schedule = np.nonzero(pods.bound_node == PAD)[0]
        for p in to_schedule:
            push_event(float(pods.arrival[p]), EV_ARRIVAL, int(p))
        node_events = node_events or []
        for i, ev in enumerate(node_events):
            push_event(ev.time, EV_NODE, i)
        # Per-pod seq of the CURRENT finish timer: an eviction + re-bind
        # re-arms the timer, and the stale event must not complete the pod
        # early (same staleness class as gang permit timeouts).
        finish_seq: Dict[int, int] = {}

        # Completions of pre-bound pods.
        for p in np.nonzero(pods.bound_node >= 0)[0]:
            if np.isfinite(pods.duration[p]):
                finish_seq[int(p)] = push_event(
                    float(pods.arrival[p] + pods.duration[p]), EV_FINISH, int(p)
                )

        # Gang bookkeeping ([K8S] coscheduling Permit; SURVEY.md §3.3).
        reserved: Dict[int, List[int]] = {}
        failed_groups: Dict[int, float] = {}  # group → virtual time of failure
        gang_timeout_seq: Dict[int, int] = {}
        failed_groups_ver: Dict[int, int] = {}  # group → progress_ver at failure

        placed = preemptions = attempts = 0
        # Chaos disruption accounting: eviction time per still-displaced
        # pod (a re-bind pops it; what remains at trace end is stranded).
        evictions = evict_rescheduled = 0
        evict_lat_sum = 0.0
        evict_time: Dict[int, float] = {}
        # Last successful placement per pod: a COMPLETED pod keeps its node
        # (it ran; it is not unschedulable), unlike st.bound which goes PAD
        # at EV_FINISH. Evictions clear it until re-placed.
        assignments = np.where(pods.bound_node >= 0, pods.bound_node, PAD).astype(
            np.int32
        )
        now = 0.0
        # Committed cluster progress (commits, completions, evictions, node
        # events) — NOT speculative gang reserves. Gates timed gang retries
        # so a gang that cannot complete doesn't spin the virtual clock.
        progress_ver = 0
        saved_alloc = ec.allocatable.copy()
        tel = (
            TelemetryCollector(self.telemetry_cfg)
            if self.telemetry_cfg.enabled
            else None
        )
        want_series = tel is not None and tel.cfg.want_series
        want_timeline = tel is not None and tel.cfg.want_timeline
        # First COMMITTED bind per pod — latency is arrival→first bind;
        # re-binds after eviction/preemption must not re-record.
        lat_seen: set = set()

        def record_bind(m: int, t: float) -> None:
            if tel is None:
                return
            tel.clear_episode(m)
            if want_timeline:
                tel.event("bind", t, int(m), int(st.bound[m]))
            if m not in lat_seen:
                lat_seen.add(m)
                lat = t - float(pods.arrival[m])
                if lat <= 0.0:
                    tel.bind_zero()
                else:
                    tel.bind_latency(m, lat)

        t0 = time.perf_counter()

        def rollback_group(g: int, park: bool):
            # ``park=False`` (permit timeout): members were placeable and the
            # gang just failed to assemble in time → backoff retry ([K8S]
            # coscheduling rejects waiting pods back through the backoff
            # queue) — but only if committed progress happened since the
            # last failure, else retrying cannot help and would spin the
            # virtual clock. ``park=True`` (a member failed): assembling
            # again needs a cluster event → everyone waits for one.
            retry = (not park) and failed_groups_ver.get(g) != progress_ver
            for m in reserved.pop(g, []):
                unbind(ec, pods, st, m)
                if retry:
                    q.requeue_backoff(m, int(pods.priority[m]), now)
                else:
                    q.mark_unschedulable(m, int(pods.priority[m]), now)
            gang_timeout_seq.pop(g, None)
            failed_groups[g] = now
            failed_groups_ver[g] = progress_ver

        def evict(p: int, requeue: bool = True):
            if tel is not None:
                # A displacement starts a fresh unschedulable episode: the
                # next fully-failed attempt re-enters the reasons counts.
                tel.clear_episode(int(p))
            unbind(ec, pods, st, int(p))
            assignments[int(p)] = PAD
            # An evicted reserved gang member returns to the queue
            # unreserved — drop it from the reservation so a later re-bind
            # cannot enter the members list twice.
            g = int(pods.group_id[p])
            if g != PAD and g in reserved and int(p) in reserved[g]:
                reserved[g].remove(int(p))
                if not reserved[g]:
                    reserved.pop(g)
                    gang_timeout_seq.pop(g, None)
            if requeue:
                q.push(int(p), int(pods.priority[p]))

        while events or len(q):
            _pt = time.perf_counter() if tel is not None else 0.0
            if events:
                # Advance to the next event OR the next backoff expiry,
                # whichever is first — a 1s backoff must not stretch to the
                # next event's timestamp.
                nb = q.next_backoff_time()
                t_next = events[0][0]
                now = max(now, min(t_next, nb) if nb is not None else t_next)
                progressed_cluster = False
                while events and events[0][0] <= now:
                    _, kind, ev_seq, payload = heapq.heappop(events)
                    if kind == EV_ARRIVAL:
                        q.push(payload, int(pods.priority[payload]))
                    elif kind == EV_FINISH:
                        if st.bound[payload] != PAD and finish_seq.get(payload) == ev_seq:
                            unbind(ec, pods, st, payload)
                            finish_seq.pop(payload, None)
                            progressed_cluster = True
                            progress_ver += 1
                    elif kind == EV_NODE:
                        ev = node_events[payload]
                        if ev.kind == "node_down":
                            ec.allocatable[ev.node] = 0.0
                            if want_timeline:
                                tel.event("node_down", now, -1, int(ev.node))
                            # NoExecute semantics: evict and requeue ([K8S]).
                            for m in np.nonzero(st.bound == ev.node)[0]:
                                if want_timeline:
                                    tel.event("evict", now, int(m), int(ev.node))
                                evict(int(m))
                                evictions += 1
                                evict_time[int(m)] = now
                        elif ev.kind == "node_up":
                            ec.allocatable[ev.node] = saved_alloc[ev.node]
                            if want_timeline:
                                tel.event("node_up", now, -1, int(ev.node))
                        elif ev.kind == "node_cordon":
                            # No new bind (every request fails the fit
                            # filter); what runs there keeps running.
                            ec.allocatable[ev.node] = 0.0
                        elif ev.kind == "capacity_scale":
                            ec.allocatable[ev.node] = saved_alloc[ev.node] * ev.scale
                        progressed_cluster = True
                        progress_ver += 1
                    elif kind == EV_PERMIT_TIMEOUT:
                        g = payload
                        # Seq must match: stale timeouts from a rolled-back
                        # reservation cycle must not cancel a fresh one.
                        if g in reserved and gang_timeout_seq.get(g) == ev_seq:
                            rollback_group(g, park=False)
                if progressed_cluster:
                    q.flush_unschedulable(now)
            q.flush_backoff(now)
            if tel is not None:
                tel.phases.add("host_events", time.perf_counter() - _pt)
                if want_series:
                    tel.sample(
                        now,
                        active=len(q),
                        unschedulable=q.num_unschedulable,
                        backoff=q.num_backoff,
                        # Utilization economics (round 13): sampled after
                        # the instant's events, before scheduling — the
                        # device boundary samples the same committed
                        # state via the shared helper (bit-parity).
                        **series_gauges(st.used, ec.allocatable, ec.vocab._r),
                    )
                _pt = time.perf_counter()

            made_bind = False
            while True:
                p = q.pop()
                if p is None:
                    break
                g = int(pods.group_id[p])
                if g != PAD and g in failed_groups and failed_groups[g] == now:
                    # Group already failed at this instant; retry later.
                    # No ``now``: this was not a real scheduling attempt, so
                    # it must not inflate the pod's exponential backoff.
                    q.mark_unschedulable(p, int(pods.priority[p]))
                    continue
                attempts += 1
                res = self.fw.schedule_one(
                    st, p, allow_preemption=g == PAD, want_reasons=want_series
                )
                if res.node == PAD:
                    if want_series and res.reasons is not None:
                        tel.rejection(int(p), res.reasons)
                    if g != PAD and g in reserved:
                        rollback_group(g, park=True)
                    q.mark_unschedulable(p, int(pods.priority[p]), now)
                    continue
                for v in res.victims:
                    if want_timeline:
                        tel.event("preempt", now, int(v), int(st.bound[v]))
                    evict(v)
                    preemptions += 1
                    progress_ver += 1
                bind(ec, pods, st, p, res.node)
                if g != PAD:
                    members = reserved.setdefault(g, [])
                    if not members:
                        gang_timeout_seq[g] = push_event(
                            now + self.permit_timeout, EV_PERMIT_TIMEOUT, g
                        )
                    members.append(p)
                    if len(members) >= int(pods.pg_min_member[g]):
                        # Permit: whole gang reserved → commit.
                        for m in reserved.pop(g):
                            placed += 1
                            made_bind = True
                            progress_ver += 1
                            assignments[m] = st.bound[m]
                            record_bind(m, now)
                            if m in evict_time:
                                evict_rescheduled += 1
                                evict_lat_sum += now - evict_time.pop(m)
                            if np.isfinite(pods.duration[m]):
                                finish_seq[m] = push_event(
                                    now + float(pods.duration[m]), EV_FINISH, m
                                )
                        gang_timeout_seq.pop(g, None)
                        failed_groups.pop(g, None)
                        failed_groups_ver.pop(g, None)
                else:
                    placed += 1
                    made_bind = True
                    progress_ver += 1
                    assignments[p] = res.node
                    record_bind(p, now)
                    if p in evict_time:
                        evict_rescheduled += 1
                        evict_lat_sum += now - evict_time.pop(p)
                    if np.isfinite(pods.duration[p]):
                        finish_seq[p] = push_event(
                            now + float(pods.duration[p]), EV_FINISH, p
                        )
                if made_bind and q.num_unschedulable:
                    # Binding is a cluster event for affinity/spread waiters.
                    q.flush_unschedulable(now)
            if tel is not None:
                tel.phases.add("host_schedule", time.perf_counter() - _pt)
            # Idle until the next event (or backoff expiry).
            nb = q.next_backoff_time()
            if not events and len(q) == 0 and nb is not None:
                now = max(now, nb)
                q.flush_backoff(now)
                if len(q) == 0:
                    break

        # Any still-reserved gang at trace end never completed → roll back.
        for g in list(reserved):
            rollback_group(g, park=True)

        wall = time.perf_counter() - t0
        ec.allocatable[:] = saved_alloc
        util = utilization_means(st.used, ec.allocatable, ec.vocab._r)
        unsched = int((assignments[to_schedule] == PAD).sum())
        pending = to_schedule[assignments[to_schedule] == PAD]
        frag = fragmentation_gauges(
            ec.allocatable, st.used, pods.requests[pending], ec.vocab._r
        )
        return ReplayResult(
            assignments=assignments,
            placed=placed,
            unschedulable=unsched,
            preemptions=preemptions,
            attempts=attempts,
            wall_clock_s=wall,
            placements_per_sec=placed / wall if wall > 0 else 0.0,
            virtual_makespan=now,
            utilization=util,
            state=st,
            evictions=evictions,
            evict_rescheduled=evict_rescheduled,
            evict_stranded=len(evict_time),
            evict_latency_mean=(
                evict_lat_sum / evict_rescheduled if evict_rescheduled else 0.0
            ),
            fragmentation=frag,
            telemetry=tel.result() if tel is not None else None,
        )


@register_strategy("cpu")
def _make_cpu(ec: EncodedCluster, pods: EncodedPods, config: Optional[FrameworkConfig] = None, **kw):
    return CpuReplayEngine(ec, pods, config, **kw)
