"""Greedy wave replay, numpy host edition.

Implements EXACTLY the algorithm the JAX engine compiles — arrival-order
waves, sequential slots with speculative binds, wave-boundary gang
commit/rollback — but on the host, reusing the tested CPU plugin path.
This is the parity anchor for the device scan (SURVEY.md §4.2): for any
workload, `greedy_replay` and the `jax` strategy must produce identical
placements.

``preemption="tier"`` (or ``True``) adds the greedy engines' TIER
preemption (the fast in-scan approximation — NOT kube's minimal-victims
PostFilter): when a pod is unschedulable, a node may be chosen where
evicting ALL lower-priority non-gang pods makes it fit (resource fit +
taint/node-affinity + the count-based masks at their CURRENT, pre-eviction
values); candidates rank by (fewest victims, lowest max victim tier,
lowest index). Evicted pods become unplaced and are NOT re-queued, and
their affinity/spread count contributions are NOT rewound ("phantom
counts") — aggregate state can't attribute counts to individual victims.
At most one preemption fires per wave; gang pods neither preempt nor get
evicted.

``preemption="kube"`` (round 5) is the kube-EXACT minimal-victims
PostFilter, run at chunk boundaries through the retry buffer
(:mod:`.boundary`): a failed non-gang pod retries at each boundary and,
still failing, preempts per upstream defaultpreemption — fewest victims,
lowest max victim priority, victims chosen lowest-priority-first, ONLY
the victims needed for this pod's fit, with a FULL count rewind (no
phantom counts). Victims re-enter the retry buffer exactly as the CPU
event engine requeues them. Requires ``completions_chunk_waves`` (the
boundary grid) and ``retry_buffer > 0``. In-wave attempts never preempt —
fidelity is chunk-granular (exact vs CpuReplayEngine at W=1/C=1 on
queue-trivial traces; measured divergence at production chunk sizes).
"""

from __future__ import annotations

import time
from typing import List, Optional

import numpy as np

from ..framework.framework import FrameworkConfig, SchedulerFramework
from ..models.encode import PAD, EncodedCluster, EncodedPods
from ..models.state import bind, unbind
from ..utils.metrics import fragmentation_gauges, utilization_means
from .runtime import ReplayResult
from .waves import (
    WaveBatch, pack_waves, refuse_split_jobs, refuse_wide_gangs, wide_gang_table,
)


def priority_tiers(ep: EncodedPods):
    """(tiers [T] ascending distinct priorities, pod_tier [P] i32)."""
    tiers, inv = np.unique(ep.priority, return_inverse=True)
    return tiers.astype(np.int64), inv.astype(np.int32)


def _try_tier_preempt(fw, ec, ep, st, p, pod_tier):
    """The anchor's preemption decision. Returns (node, victims) or None.
    Mirrors ops.tpu3's device arithmetic exactly (see module docstring)."""
    tp = int(pod_tier[p])
    if ep.group_id[p] != PAD or tp == 0:
        return None
    bound = st.bound
    lower = np.nonzero(
        (bound >= 0) & (pod_tier < tp) & (ep.group_id == PAD)
    )[0]
    if lower.size == 0:
        return None
    N = ec.num_nodes
    victims_n = np.zeros(N, np.int64)
    np.add.at(victims_n, bound[lower], 1)
    lower_used = np.zeros((N, ec.num_resources), np.float32)
    np.add.at(lower_used, bound[lower], ep.requests[lower])
    # Fit after evict-all-lower (same eps form as ops.cpu.fit_mask).
    pre_fit = np.all(
        st.used - lower_used + ep.requests[p][None, :] <= ec.allocatable + 1e-6,
        axis=1,
    )
    # All non-fit filters at their current (pre-eviction) values.
    masks = np.ones(N, bool)
    for pl in fw.plugins:
        if pl.name == "NodeResourcesFit":
            continue
        m = pl.filter(fw.ctx, st, p)
        if m is not None:
            masks &= m
    cand = pre_fit & masks & (victims_n > 0)
    if not cand.any():
        return None
    maxtier_n = np.full(N, -1, np.int64)
    np.maximum.at(maxtier_n, bound[lower], pod_tier[lower].astype(np.int64))
    score = victims_n * 1024 + maxtier_n
    score = np.where(cand, score, np.iinfo(np.int64).max)
    n = int(np.argmin(score))  # lowest index on ties
    victims = lower[bound[lower] == n]
    return n, victims


def normalize_preemption(preemption) -> Optional[str]:
    """False/None → None; True → "tier"; "tier"/"kube" pass through."""
    if preemption in (False, None):
        return None
    if preemption is True:
        return "tier"
    if preemption in ("tier", "kube"):
        return preemption
    raise ValueError(
        f"preemption must be False/True/'tier'/'kube', got {preemption!r}"
    )


def greedy_replay(
    ec: EncodedCluster,
    ep: EncodedPods,
    config: Optional[FrameworkConfig] = None,
    waves: Optional[WaveBatch] = None,
    wave_width: int = 8,
    preemption=False,
    completions_chunk_waves: Optional[int] = None,
    retry_buffer: int = 0,
    retry_groups: bool = False,
) -> ReplayResult:
    """``completions_chunk_waves``: mirror the device engines' chunk-granular
    completions — before each chunk of that many waves, pods whose
    ``arrival + duration`` is at or before the chunk's start time release
    their resources and count contributions (they stay in ``assignments``:
    a completed pod ran to completion, it is not unschedulable).

    ``retry_buffer`` (round 4, [K8S] activeQ flush-on-event analogue):
    non-gang pods that miss placement enter a retry buffer (capacity
    ``retry_buffer``; overflow drops the newest — they stay permanently
    unscheduled as before). At each chunk boundary, AFTER releases apply,
    one bounded retry pass re-attempts every buffered pod in kube's
    QueueSort order (priority descending, then the order of arrival);
    placed pods leave the buffer and start at the boundary's time — they
    release at the first boundary whose start time reaches ``t_b +
    duration`` (computed in f32, exactly as the device does; at least
    ``b+1``), however many are outstanding. The result's
    ``bind_boundary`` says, pod by pod, which boundary's pass bound it
    (``BoundaryOps.bind_boundary_codes``). Requires
    ``completions_chunk_waves``. Mirrors WhatIfEngine(retry_buffer=...)'s
    device semantics exactly.

    ``retry_groups`` (the scheduler profile's; with ``retry_buffer``): a
    queue entry belongs to a JOB (a pod group; a pod in none is a job of
    one). A job that is rolled back joins the queue WHOLE at the boundary
    after its closing wave, behind what is queued at its priority, in
    arrival order, or is dropped whole where the buffer lacks room for all
    its members; every pass tries each queued job again as at its arrival
    (``BoundaryOps._retry_jobs``), a job bound at its arrival or by a pass
    is released whole, and a group wider than the wave runs with
    completions and the buffer. ``ReplayResult.group_counts`` holds
    ``sim.waves.GROUP_COUNTERS``. Mirrors
    ``WhatIfEngine(retry_groups=True)``'s device semantics exactly."""
    from .boundary import BoundaryOps

    from dataclasses import replace as dc_replace

    mode = normalize_preemption(preemption)
    # kube PostFilter runs ONLY through the boundary pass; in-wave
    # attempts pass allow_preemption=False below. Copy, don't write
    # through the caller's config object.
    config = dc_replace(
        config or FrameworkConfig(), enable_preemption=mode == "kube"
    )
    if retry_buffer and not completions_chunk_waves:
        raise ValueError("retry_buffer requires completions_chunk_waves")
    if retry_groups and not (retry_buffer and mode is None):
        raise ValueError("retry_groups requires retry_buffer and no preemption")
    if retry_groups:
        refuse_split_jobs(ep)
    if retry_buffer and mode == "tier":
        raise ValueError("retry_buffer is not supported with tier preemption")
    if mode == "kube" and not completions_chunk_waves:
        raise ValueError(
            "preemption='kube' requires completions_chunk_waves (the "
            "boundary grid the PostFilter pass runs on)"
        )
    fw = SchedulerFramework(ec, ep, config)
    if waves is None:
        waves = pack_waves(ep, wave_width)
    ops = BoundaryOps(
        ec, ep, fw, waves, wave_width, completions_chunk_waves or 1,
        retry_buffer=retry_buffer, kube=mode == "kube",
        retry_groups=retry_groups,
    )
    st = ops.st
    _, pod_tier = priority_tiers(ep)
    # Pre-bound pods appear in assignments (matching the device engines)
    # but never count toward placed_total (they were not scheduled here).
    assignments = ops.assignments
    preemptions = 0  # tier evictions (kube evictions live in ops)
    # A pod group WIDER than the wave (sim.waves.wide_gang_table): its
    # members bind tentatively, wave after wave, and the verdict falls at
    # the end of the wave that holds its last member — the device step's
    # carried transaction (ops.tpu3.GangTxn), on the host.
    wide = wide_gang_table(ep, waves.wave_width)
    if wide is not None:
        refuse_wide_gangs(
            waves.wave_width, int(wide[:, 1].max()),
            retry_groups=retry_groups,
            completions=bool(completions_chunk_waves),
            retry_buffer=bool(retry_buffer),
            kube_preemption=mode == "kube", tier_preemption=mode == "tier",
        )
    txn: List[int] = []  # the open wide group's members bound so far
    txn_all: List[int] = []  # ... and every member tried so far
    txn_failed = False
    t0 = time.perf_counter()
    for wi, wave in enumerate(waves.idx):
        if completions_chunk_waves and wi % completions_chunk_waves == 0:
            b = wi // completions_chunk_waves
            first = int(wave[0]) if wave.shape[0] else -1
            t_chunk = float(ep.arrival[first]) if first >= 0 else np.inf
            ops.boundary(b, t_chunk)
        slot_choice: List[int] = []
        slot_pods: List[int] = []
        evicted_in_wave: set = set()
        preempted_this_wave = False
        for p in wave:
            if p < 0:
                continue
            p = int(p)
            res = fw.schedule_one(st, p, allow_preemption=False)
            node = res.node
            if node == PAD and mode == "tier" and not preempted_this_wave:
                hit = _try_tier_preempt(fw, ec, ep, st, p, pod_tier)
                if hit is not None:
                    node, victims = hit
                    preempted_this_wave = True
                    preemptions += len(victims)
                    for v in victims:
                        v = int(v)
                        vn = int(st.bound[v])
                        # Resources-only unbind: counts stay (phantom).
                        st.used[vn] -= ep.requests[v]
                        st.bound[v] = PAD
                        if assignments[v] >= 0:
                            assignments[v] = PAD
                            if ep.bound_node[v] == PAD:  # scheduled here
                                ops.placed_total -= 1
                        elif v in slot_pods:
                            evicted_in_wave.add(v)
            if node != PAD:
                bind(ec, ep, st, p, node)
            slot_pods.append(p)
            slot_choice.append(node)
        # Gang commit: a group fails if ANY member slot went unplaced.
        failed_groups = {
            int(ep.group_id[p])
            for p, c in zip(slot_pods, slot_choice)
            if c == PAD and ep.group_id[p] != PAD
            and (wide is None or wide[p, 0] < 0)
        }
        closes = False
        offered = set()
        for p, c in zip(slot_pods, slot_choice):
            if wide is not None and wide[p, 0] >= 0:
                # Tentative: bound (above) and handed back as placed until
                # the group's verdict.
                txn_all.append(p)
                if c != PAD:
                    txn.append(p)
                    assignments[p] = c
                    ops.placed_total += 1
                else:
                    txn_failed = True
                closes |= wide[p, 0] == wide[p, 1] - 1
                continue
            if p in evicted_in_wave:
                continue  # evicted mid-wave: never committed
            g = int(ep.group_id[p])
            if retry_groups and (g in failed_groups or c == PAD):
                # the job (a pod in no group is a job of one) joins the
                # queue whole at the next boundary
                key = g if g != PAD else -1 - p
                if key not in offered:
                    ops.offer_job([p] if g == PAD else [
                        q for q in slot_pods if ep.group_id[q] == g
                    ])
                    offered.add(key)
            elif retry_groups and (g == PAD or ops.job[p, 1] == 0):
                ops.group_counts["jobs_bound_arrival"] += 1
            if c != PAD and g in failed_groups:
                unbind(ec, ep, st, p)
            elif c != PAD:
                assignments[p] = c
                ops.placed_total += 1
                if completions_chunk_waves:
                    ops.bind_chunk[p] = wi // completions_chunk_waves
            else:
                # Failed non-gang pod enters the retry buffer (slot
                # order within the wave; overflow drops the newest).
                ops.offer_failure(p)
        if closes:
            # The rollback falls at the closing wave's end: the pods behind
            # the group in this wave were scheduled on its tentative binds.
            if txn_failed:
                for p in txn:
                    unbind(ec, ep, st, p)
                    assignments[p] = PAD
                ops.placed_total -= len(txn)
                if retry_groups:
                    ops.offer_job(txn_all)
            elif completions_chunk_waves:
                # bound whole: released whole, as of its closing chunk
                ops.bind_chunk[txn] = wi // completions_chunk_waves
                ops.group_counts["jobs_bound_arrival"] += retry_groups
            txn, txn_all, txn_failed = [], [], False
    if mode == "kube":
        # Trailing boundary: pods that failed in the LAST chunk still get
        # their PostFilter attempt (the CPU engine preempts at the failure
        # instant; without this a late high-priority pod would never
        # preempt). t = inf ⇒ no static releases, no pend scheduling.
        ops.boundary(
            -(-waves.idx.shape[0] // (completions_chunk_waves or 1)), np.inf
        )
    if retry_groups:
        ops.join_failed()  # the last chunk's: queued untried
    wall = time.perf_counter() - t0
    placed_total = ops.placed_total
    preemptions += ops.preemptions
    to_schedule = int((ep.bound_node == PAD).sum())
    util = utilization_means(st.used, ec.allocatable, ec.vocab._r)
    pending = (ep.bound_node == PAD) & (assignments == PAD)
    frag = fragmentation_gauges(
        ec.allocatable, st.used, ep.requests[pending], ec.vocab._r
    )
    return ReplayResult(
        assignments=assignments,
        placed=placed_total,
        unschedulable=to_schedule - placed_total,
        preemptions=preemptions,
        attempts=to_schedule,
        wall_clock_s=wall,
        placements_per_sec=placed_total / wall if wall > 0 else 0.0,
        virtual_makespan=float(ep.arrival.max()) if ep.num_pods else 0.0,
        utilization=util,
        state=st,
        retry_dropped=ops.retry_dropped,
        fragmentation=frag,
        bind_boundary=ops.bind_boundary_codes() if retry_buffer else None,
        group_counts=dict(ops.group_counts) if retry_groups else None,
    )
