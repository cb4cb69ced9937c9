"""Wave packing — the rectangular schedule the device scan walks.

Pods (in arrival order) are packed into fixed-width "waves" of W slots such
that no pod-group (gang) of at most W members spans waves. The JAX engine
scans waves; within a wave, slots are processed sequentially (pod k sees pod
k-1's speculative bindings — SURVEY.md §7 hard part #1), and gang
commit/rollback happens at the wave boundary as one masked update (hard
part #3).

A gang WIDER than the wave starts on a wave's first slot and fills
ceil(size / W) consecutive waves; the rest of its last wave is open to the
pods that follow, as a wave-local gang's is. The step carries such a gang's
transaction across its waves and rolls it back where it closes
(:func:`wide_gang_table`, ``ops.tpu3.GangTxn``).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional

import numpy as np

from ..models.encode import PAD, EncodedPods


@dataclass
class WaveBatch:
    idx: np.ndarray  # [num_waves, W] i32 pod ids (PAD = empty slot)
    wave_width: int

    @property
    def num_waves(self) -> int:
        return self.idx.shape[0]


def pack_waves(
    ep: EncodedPods, wave_width: int = 8, order: Optional[np.ndarray] = None,
    page_pods: Optional[int] = None,
) -> WaveBatch:
    """Pack schedulable pods into waves. ``order`` defaults to arrival order
    of unbound pods (stable; deterministic). Uses the native C++ packer
    (kubernetes_simulator_tpu.native) when available — ~40× faster at 1M
    pods; this Python path is the semantic reference and fallback.

    ``page_pods`` (round 14 paged mode): number of pod SLOTS per streamed
    page. Validated here against the largest gang — a gang split across
    pages could see its later members arrive after the page carrying its
    earlier ones was evicted, so the guard mirrors the wave-width check
    (and runs on BOTH the native and reference paths)."""
    if order is None:
        unbound = np.nonzero(ep.bound_node == PAD)[0]
        order = unbound[np.argsort(ep.arrival[unbound], kind="stable")]
    if page_pods is not None:
        gids = ep.group_id[np.asarray(order)]
        gids = gids[gids != PAD]
        max_gang = int(np.bincount(gids).max()) if gids.size else 1
        if page_pods < max_gang:
            raise ValueError(
                f"paged mode: page of {page_pods} pod slots is smaller than "
                f"the largest gang ({max_gang} pods) — a gang must fit in "
                f"one page; raise chunk_waves/wave_width so that "
                f"chunk_waves * wave_width >= {max_gang}, or disable paging"
            )
    from ..native import pack_waves_native

    idx_native = pack_waves_native(np.asarray(order), ep.group_id, wave_width)
    if idx_native is not None:
        return WaveBatch(idx=idx_native, wave_width=wave_width)
    members: Dict[int, List[int]] = {}
    for p in order:
        g = int(ep.group_id[p])
        if g != PAD:
            members.setdefault(g, []).append(int(p))
    waves: List[List[int]] = []
    current: List[int] = []
    consumed = set()

    def flush():
        nonlocal current
        if current:
            waves.append(current)
            current = []

    for p in order:
        p = int(p)
        if p in consumed:
            continue
        g = int(ep.group_id[p])
        batch = [p] if g == PAD else members[g]
        if len(current) + len(batch) > wave_width:
            flush()
        # A gang wider than the wave: whole waves of it, from a wave's
        # first slot; what is left over stays open like any other wave.
        while len(batch) > wave_width:
            waves.append(batch[:wave_width])
            consumed.update(batch[:wave_width])
            batch = batch[wave_width:]
        current.extend(batch)
        consumed.update(batch)
    flush()

    idx = np.full((max(len(waves), 1), wave_width), PAD, dtype=np.int32)
    for i, w in enumerate(waves):
        idx[i, : len(w)] = w
    return WaveBatch(idx=idx, wave_width=wave_width)


def widest_gang(ep: EncodedPods) -> int:
    """Members of the largest gang among the unbound pods (0 without one)."""
    gid = ep.group_id[ep.bound_node == PAD]
    gid = gid[gid != PAD]
    return int(np.bincount(gid).max()) if gid.size else 0


# What a gang wider than the wave does not run with: the ONE
# list (the step's builder, both device engines, the host twin and the CLI's
# ``validate`` all refuse through :func:`refuse_wide_gangs`). The transaction
# gives back resource usage and lives in the arrivals-only scan's state: a
# release, a retry or boundary pass, an eviction, a fork or a checkpoint
# would have to know of an open one, and count planes would have to be
# rolled back with it.
WIDE_GANG_UNSUPPORTED = {
    "completions": "completions (finite pod durations; pass completions=False)",
    "retry_buffer": "a retry buffer",
    "kube_preemption": "kube preemption",
    "tier_preemption": "tier preemption",
    "fork_checkpoint": "a fork checkpoint",
    "checkpoint": "checkpoint or resume",
    "count_planes": "carried affinity / spread count planes",
    "no_transaction": "a step built without the wave width "
                      "(V3Static.build(..., wave_width=))",
}


# ... but for these two, together, under the scheduler profile's
# ``retry_groups``: a rolled-back group joins the queue WHOLE, every pass tries
# it again as one transaction of its own, and a job's members are released
# together (``sim.whatif`` ``per_scenario_retry``; ``sim.boundary``).
WIDE_GANG_WITH_RETRY_GROUPS = ("completions", "retry_buffer")

# What ``retry_groups`` counts per scenario as the batch runs (``RetryQueue.gn``
# on the device, ``BoundaryOps.group_counts`` on the host;
# ``summary()["retry"]["groups"]``). How long the jobs of each size waited is
# what the two answer arrays imply (:func:`job_waits`): no counter of the run.
GROUP_COUNTERS = (
    "jobs_bound_arrival",  # jobs bound in their arrival waves
    "jobs_bound_pass",     # ... by a retry pass
    "pass_attempts",       # a queued job tried by a pass
    "pass_rollbacks",      # ... and rolled back
    "pass_rollbacks_after_bind",  # ... a job WIDER than the wave after a member had bound
    "dropped_jobs",        # jobs that found the buffer short of room for all their members
)


def job_table(ep: EncodedPods, idx: np.ndarray, chunk_waves: int) -> np.ndarray:
    """``[P, 3]`` i32 per pod ``(size, pos, closing chunk)`` of its JOB (a pod
    group; a pod in none is a job of one): the member count, the pod's place
    among the members in the order the packer lays them out, and the chunk
    that holds the job's last member (``1 << 30`` for a pod in no wave).
    Static per (trace, wave width, chunk): what ``retry_groups`` reads of a
    queued task beside :func:`wide_gang_table`'s rows."""
    P = ep.num_pods
    flat = idx.reshape(-1)
    at = np.nonzero(flat >= 0)[0]
    pods = flat[at]
    tab = np.zeros((P, 3), np.int64)
    tab[:, 0], tab[:, 2] = 1, 1 << 30
    tab[pods, 2] = at // (chunk_waves * idx.shape[1])
    gid = ep.group_id[pods]
    g = gid >= 0
    if g.any():
        order = np.argsort(gid[g], kind="stable")  # job by job, wave order inside
        mem, gm = pods[g][order], gid[g][order]
        sizes = np.bincount(gm)
        starts = np.cumsum(sizes) - sizes
        tab[mem, 0] = sizes[gm]
        tab[mem, 1] = np.arange(mem.size) - starts[gm]
        last = np.zeros(sizes.shape[0], np.int64)
        np.maximum.at(last, gm, tab[mem, 2])
        tab[mem, 2] = last[gm]
    return tab.astype(np.int32)


def job_waits(bind_boundary: np.ndarray, job: np.ndarray) -> dict:
    """How long the jobs a retry pass bound had waited, by JOB SIZE, from the
    answers alone: ``bind_boundary`` ``[..., P]`` (``>= 0``: the boundary
    whose pass bound the pod's job) and :func:`job_table`'s ``job``. A job
    waits from the boundary after its closing chunk, so ``b - closing chunk``
    boundaries; its first member speaks for it. ``-> {"size" [K] (the sizes
    the trace holds, ascending), "bound_pass", "wait_sum", "wait_max"
    [..., K]}``."""
    heads = np.nonzero(job[:, 1] == 0)[0]
    size = job[heads, 0]
    bb = np.asarray(bind_boundary)[..., heads].astype(np.int64)
    got = bb >= 0
    wait = np.where(got, bb - job[heads, 2], 0)
    sizes = np.unique(size)
    per = lambda f: np.stack([f(size == k) for k in sizes], axis=-1)
    return {
        "size": sizes.astype(np.int64),
        "bound_pass": per(lambda m: got[..., m].sum(-1)),
        "wait_sum": per(lambda m: wait[..., m].sum(-1)),
        "wait_max": per(lambda m: wait[..., m].max(-1, initial=0)),
    }


def refuse_split_jobs(ep: EncodedPods) -> None:
    """``retry_groups`` keeps a job together in the queue and releases it
    whole because its members carry ONE arrival time, priority and duration:
    raise where a pod group's do not."""
    g = ep.group_id
    m = np.nonzero((g != PAD) & (ep.bound_node == PAD))[0]
    if not m.size:
        return
    first = np.full(int(g[m].max()) + 1, ep.num_pods, np.int64)
    np.minimum.at(first, g[m], m)
    head = first[g[m]]
    for what, col in (("arrival time", ep.arrival), ("priority", ep.priority),
                      ("duration", ep.duration)):
        col = np.asarray(col)
        if not np.array_equal(col[m], col[head], equal_nan=col.dtype.kind == "f"):
            raise ValueError(
                f"retry_groups: the members of a pod group must share one "
                f"{what} (a job is one arrival)"
            )


def refuse_wide_gangs(wave_width: int, widest: int, retry_groups: bool = False,
                      **on) -> None:
    """Raise where a gang of ``widest`` members is wider than the wave and
    any of ``on`` (keys of :data:`WIDE_GANG_UNSUPPORTED`) holds; under
    ``retry_groups`` the two of :data:`WIDE_GANG_WITH_RETRY_GROUPS` run."""
    blockers = [
        WIDE_GANG_UNSUPPORTED[k] for k, v in on.items()
        if v and not (retry_groups and k in WIDE_GANG_WITH_RETRY_GROUPS)
    ]
    if widest > wave_width and blockers:
        raise ValueError(
            f"a gang of {widest} exceeds the wave width ({wave_width}): a "
            f"gang wider than the wave is not supported with "
            f"{', '.join(blockers)}; raise the wave width to {widest} or use "
            f"the CPU event engine"
        )


def wide_gang_table(
    ep: EncodedPods, wave_width: int
) -> Optional[np.ndarray]:
    """``[P, 3]`` i32 per pod ``(pos, size, ordinal)`` of the gangs WIDER than
    the wave among the unbound pods, or None where there is none: ``pos`` the
    pod's place among its gang's members in arrival order (the order the
    packer lays them out in: member ``pos`` sits in slot ``pos % W`` of the
    gang's wave ``pos // W``), ``size`` the gang's member count, ``ordinal``
    the gang's number among the wide ones, by group id. A pod in no wide
    gang reads ``(-1, 0, 0)``. Static per (trace, wave width): the device
    step reads a wave's rows to know whether a wide gang continues or closes
    there, the same in every scenario."""
    unbound = np.nonzero(ep.bound_node == PAD)[0]
    gid = ep.group_id[unbound]
    in_gang = gid != PAD
    if not in_gang.any():
        return None
    sizes = np.bincount(gid[in_gang])
    wide_g = np.nonzero(sizes > wave_width)[0]
    if wide_g.size == 0:
        return None
    ordinal = np.full(sizes.shape[0], -1, np.int32)
    ordinal[wide_g] = np.arange(wide_g.size, dtype=np.int32)
    order = unbound[np.argsort(ep.arrival[unbound], kind="stable")]
    g_o = ep.group_id[order]
    wide_o = (g_o != PAD) & (ordinal[np.clip(g_o, 0, None)] >= 0)
    members = order[wide_o]  # arrival order
    by_gang = np.argsort(g_o[wide_o], kind="stable")
    members = members[by_gang]  # gang by gang, arrival order inside
    g_m = ep.group_id[members]
    starts = np.concatenate([[0], np.cumsum(sizes[wide_g])[:-1]])
    table = np.zeros((ep.num_pods, 3), np.int32)
    table[:, 0] = -1
    table[members, 0] = np.arange(members.size) - np.repeat(starts, sizes[wide_g])
    table[members, 1] = sizes[g_m]
    table[members, 2] = ordinal[g_m]
    return table
