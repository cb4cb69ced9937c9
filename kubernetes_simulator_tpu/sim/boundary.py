"""Shared chunk-boundary semantics for the greedy anchor and the device
replay engine (SURVEY.md §2 L3/L4; VERDICT r4 next #1/#3).

A "boundary" is the host synchronization point between device chunks —
the same point where chunk-granular completions already apply. Three
passes run there, in order:

1. **Pending releases** — boundary-placed pods (retried/preempted binds)
   whose scheduled release boundary has arrived free their contributions.
2. **Static releases** — arrival-placed pods whose ``arrival + duration``
   is at or before the boundary's start time, bound in chunks ≤ b−2 (the
   one-chunk slack shared with the device pipeline).
3. **Bounded retry / preemption pass** — the [K8S] activeQ analogue:
   failed non-gang pods retry placement in kube's QueueSort order
   (priority descending, then the order they entered the queue, which is
   arrival order; one stable sort of the queue a boundary); under
   ``kube=True`` a pod
   that still fails runs the EXACT kube PostFilter
   (``SchedulerFramework._post_filter_preempt``: fewest victims, lowest
   max victim priority, only the victims needed for THIS pod's fit,
   lowest-priority-first eviction order) — victims are unbound with a
   full count rewind (no phantom counts) and re-enter the queue, exactly
   as the CPU event engine requeues them.

The class owns the host bookkeeping (a live :class:`SchedState` mirror,
assignments, counters). ``greedy_replay`` drives it slot-by-slot;
``JaxReplayEngine`` folds whole device chunks into it and applies the
returned (release, bind, evict) lists to the device carry as rank-1
plane deltas through the existing release machinery — the kube
preemption algorithm itself never enters the compiled program. That is
the TPU-first shape of this feature: preemption is a rare, branchy,
data-dependent search (victim prefixes over per-node sorted pod lists)
that would poison the fused wave scan, but it only ever needs to run for
the handful of pods that failed placement — so it runs on host at the
sync points the engine already pays for, with the device program
unchanged and the decision arithmetic bit-identical to the CPU engine's
by construction (it IS the CPU engine's PostFilter).

Fidelity is chunk-granular: a pod preempts at the first boundary after
its failed chunk, not at its failure instant. At ``wave_width=1,
chunk_waves=1`` the boundary follows every pod and placements match
``CpuReplayEngine(enable_preemption=True)`` exactly on queue-trivial
traces (tests/test_kube_preempt.py); at production chunk sizes the
divergence is a measured, pinned number — the same contract as
completions (tests/test_divergence_pin.py).
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import numpy as np

from ..framework.framework import SchedulerFramework
from ..models.encode import PAD, EncodedCluster, EncodedPods
from ..models.state import bind, init_state, release_delta, unbind
from .waves import GROUP_COUNTERS, WaveBatch, job_table

# (pods, nodes) int arrays collected for device delta application.
PairArrays = Tuple[np.ndarray, np.ndarray]

_NEVER = 1 << 30  # bind_chunk sentinel: never statically released

# What a budgeted drain's log says of an eviction (``BoundaryOps.evict_kind``,
# the fifth column of ``WhatIfResult.eviction_log``), and what it counts
# (``summary()["retry"]`` of a what-if batch; ``BoundaryOps.budget_counts``).
EVICT_KINDS = {"voluntary": 0, "deadline": 1, "failure": 2}
BUDGET_COUNTERS = (
    "evict_voluntary",        # evictions a budget allowed
    "evict_forced_deadline",  # ... made at a drain's deadline, past the budgets
    "evict_forced_failure",   # ... made by a node_down, past the budgets
    "evict_deferred",         # candidate turns refused (asked again next boundary)
    "budget_spent_max",       # the most tasks down at once after a boundary's evictions
    "nodes_drained",          # cordoned nodes that went out holding nothing
    "nodes_forced",           # ... that lost a task at their deadline
)


def _empty_pairs() -> PairArrays:
    return np.zeros(0, np.int64), np.zeros(0, np.int64)


def apply_planes(ec, ep, st, sign: float, pods, nodes) -> None:
    """Add (``sign`` > 0) or subtract the aggregate contribution of
    ``pods`` bound at ``nodes`` to a host mirror's planes."""
    du, dmc, daa, dpw = release_delta(ec, ep, pods, nodes)
    if sign > 0:
        st.used += du
        st.match_count += dmc
        st.anti_active += daa
        st.pref_wsum += dpw
    else:
        st.used -= du
        st.match_count -= dmc
        st.anti_active -= daa
        st.pref_wsum -= dpw


def charge_first_rejects(fw: SchedulerFramework, st, pods, tel) -> None:
    """First-reject attribution (telemetry ``series``), the one rule of
    the boundary mirror's chunk fold and of the plain replay's: each
    failed valid slot in ``pods`` is charged, node by node, to the first
    plugin of the CPU framework's filter chain that rejects the node on
    ``st``. A slot whose mask is not empty charges nothing: the pod itself
    was feasible and a gang revert took its bind — the CPU engine records
    no attempt for those either."""
    for p in pods:
        rc: Dict[str, int] = {}
        if not fw.feasible_mask(st, int(p), reject_counts=rc).any():
            tel.rejection(int(p), rc)


def fold_answers(fw: SchedulerFramework, st, rows, choices, tel) -> None:
    """Fold one chunk's final answers into the plain replay's mirror
    ``st`` in slot order, charging each failed valid slot on the state its
    turn found (``charge_first_rejects``). Exact at any wave width but for
    a slot that stands behind a rolled-back gang member of its own wave:
    that member's tentative bind is in no answer."""
    v = rows >= 0
    ids, nd = rows[v], np.asarray(choices).reshape(rows.shape)[v]
    lo = 0
    for k in (*np.nonzero(nd < 0)[0].tolist(), ids.size):
        if k > lo:
            apply_planes(fw.ec, fw.pods, st, 1.0, ids[lo:k], nd[lo:k])
            st.bound[ids[lo:k]] = nd[lo:k]
        if k < ids.size:
            charge_first_rejects(fw, st, ids[k : k + 1], tel)
        lo = k + 1


class BoundaryOps:
    """Host bookkeeping + boundary passes shared by the greedy anchor and
    the device engine. All semantics here are THE semantics — the two
    callers must only disagree in how placements inside a chunk are
    produced (slot loop vs compiled wave scan), which the existing
    greedy↔device parity suites pin."""

    def __init__(
        self,
        ec: EncodedCluster,
        ep: EncodedPods,
        fw: SchedulerFramework,
        waves: WaveBatch,
        wave_width: int,
        chunk_waves: int,
        retry_buffer: int = 0,
        kube: bool = False,
        lazy: bool = False,
        telemetry=None,
        retry_groups: bool = False,
    ):
        if kube and not retry_buffer:
            raise ValueError(
                "preemption='kube' requires retry_buffer > 0 (failed pods "
                "reach the PostFilter through the boundary retry pass)"
            )
        self.ec, self.ep, self.fw = ec, ep, fw
        self.kube = kube
        # Lazy mode (device engines only): plane folds are appended to an
        # op log instead of applied; the log flushes — in eager order —
        # only when the retry pass actually needs to READ the planes
        # (``schedule_one``). The greedy anchor reads planes every slot and
        # must stay eager. Bookkeeping (bound/assignments/bind_chunk/
        # queues/counters) is ALWAYS eager, so checkpoint blobs are
        # bit-identical across modes.
        self.lazy = lazy
        self.wave_width = wave_width
        self.chunk_waves = chunk_waves
        # Telemetry (sim.telemetry.TelemetryCollector | None). The mirror
        # records boundary-granular signals: retry-bind latency
        # (t_boundary − arrival, first binds only), first-reject
        # attribution for failed slots/retries, retry/pend depth series,
        # and timeline events. Telemetry state is deliberately NOT part of
        # to_blob()/restore() — checkpoint blobs stay bit-identical with
        # telemetry on or off.
        self.tel = telemetry
        self._ever_bound: Optional[np.ndarray] = (
            (ep.bound_node >= 0).copy() if telemetry is not None else None
        )
        self._last_finite_t = 0.0
        self._plane_log: List[tuple] = []  # (key, sign, pods, nodes)
        self.plane_folds = 0  # applied plane deltas (test/bench probe)
        if retry_buffer:
            # Wave-multiple rounding shared with the device retry pass
            # (sim.whatif) — the caps must agree or placed counts diverge
            # once a buffer fills past the raw capacity.
            retry_buffer = -(-retry_buffer // wave_width) * wave_width
        self.retry_buffer = retry_buffer
        P = ep.num_pods
        self.st = init_state(ec, ep)
        self.assignments = np.where(
            ep.bound_node >= 0, ep.bound_node, PAD
        ).astype(np.int32)
        self.released = np.zeros(P, bool)
        self.rel_time = ep.arrival + np.where(
            np.isfinite(ep.duration), ep.duration, np.inf
        )
        # Chunk index each pod was bound in (pre-bound = -2): boundary b
        # releases only pods bound in chunks <= b-2 (one-chunk slack).
        self.bind_chunk = np.full(P, _NEVER, np.int64)
        self.bind_chunk[ep.bound_node >= 0] = -2
        self.retry_q: List[int] = []
        # Every retried bind with a finite release boundary, however many
        # are outstanding (no cap: a bind that lost its release would hold
        # its node to the end of the trace).
        self.pend: List[list] = []  # [relb, pod, node]
        # Per pod: -1 bound in its arrival wave (or pre-bound), b >= 0
        # bound by the retry pass of boundary b; ``bind_boundary_codes``
        # adds the codes of the pods with no node.
        self.bind_boundary = np.full(P, -1, np.int32)
        self._dropped = np.zeros(P, bool)
        # ``retry_groups``: a queue entry belongs to a JOB (a pod group; a
        # pod in none is a job of one). ``job`` [P, 3] (size, place in the
        # job, closing chunk), the chunk's rolled-back jobs waiting to join
        # (first flat wave position, members), the counters the device
        # keeps in ``RetryQueue.gn``.
        self.retry_groups = bool(retry_groups)
        self.job = job_table(ep, waves.idx, chunk_waves) if retry_groups else None
        self._chunk_failed: List[Tuple[int, List[int]]] = []
        self.group_counts = dict.fromkeys(GROUP_COUNTERS, 0)
        self.placed_total = 0
        self.preemptions = 0
        # [K8S] keeps every pending pod; the bounded analogue sheds load —
        # loudly (VERDICT r4 weak #2: drops must be a reported number).
        self.retry_dropped = 0
        # Chaos disruption: node_down NoExecute evictions (evict_node),
        # DISTINCT from scheduler-initiated `preemptions`. `_evict_time`
        # maps each still-displaced pod to its eviction boundary time — a
        # retry-pass re-bind pops it (rescheduled, latency accumulated);
        # whatever remains at trace end is stranded.
        self.evictions = 0
        self.evict_rescheduled = 0
        self._evict_lat_sum = 0.0
        self._evict_time: Dict[int, float] = {}
        # Every eviction, in the order made: (boundary, pod, the node it
        # held, the boundary whose pass had bound it; -1 its arrival wave
        # or pre-bound). With ``assignments`` and ``bind_boundary_codes``
        # a pod's history is whole; the what-if device path hands back the
        # same rows (``WhatIfResult.eviction_log``).
        self.evict_log: List[Tuple[int, int, int, int]] = []
        self.budget = None  # ``set_budget``
        self._evicted_gang = np.zeros(P, bool)
        # Boundary start times: f64 for the static release schedule, f32
        # finite prefix for the retry pend schedule (matching the device's
        # staged f32 table bit-for-bit).
        firsts = waves.idx[0::chunk_waves, 0]
        tb_all = np.where(
            firsts >= 0, ep.arrival[np.clip(firsts, 0, None)], np.inf
        )
        nfin = int(np.isfinite(tb_all).sum())
        self.tb32: Optional[np.ndarray] = None
        if retry_buffer:
            self.tb32 = tb_all[:nfin].astype(np.float32)
        # Static release schedule: each pod's earliest eligible boundary is
        # known up front (rel_time <= tb[b]  <=>  b >= searchsorted(tb,
        # rel_time, 'left'), floored by the one-chunk slack bind_chunk+2).
        # Bucketing candidates per boundary replaces the per-boundary
        # full-[P] mask scan; boundary() re-checks the dynamic parts
        # (still bound, not released, not retry-placed).
        chunk_of = np.full(P, _NEVER, np.int64)
        flat = waves.idx.reshape(-1)
        fv = flat >= 0
        if fv.any():
            chunk_of[flat[fv]] = np.nonzero(fv)[0] // (
                chunk_waves * waves.idx.shape[1]
            )
        if retry_groups:
            # a job's members are released together: each counts as bound
            # in the chunk that holds the job's last member
            self._pos = np.full(P, flat.size, np.int64)
            self._pos[flat[fv]] = np.nonzero(fv)[0]
            chunk_of[flat[fv]] = self.job[flat[fv], 2]
        chunk_of[ep.bound_node >= 0] = -2
        elig = np.searchsorted(tb_all[:nfin], self.rel_time, side="left")
        b_rel = np.maximum(elig, chunk_of + 2)
        ok = b_rel < nfin  # inf rel_time / absent pods fall out naturally
        cand = np.nonzero(ok)[0].astype(np.int64)
        order = np.argsort(b_rel[cand], kind="stable")  # pod-asc within b
        cand = cand[order]
        counts = np.bincount(b_rel[cand], minlength=max(nfin, 1))
        self._rel_bucket_off = np.concatenate(
            ([0], np.cumsum(counts))
        ).astype(np.int64)
        self._rel_bucket_pods = cand
        self._n_rel_buckets = nfin

    # -- checkpoint / resume (round 5) --------------------------------------

    def to_blob(self) -> dict:
        """The mirror's resume state as small named arrays (the count
        planes ride the main checkpoint — only the per-pod bookkeeping
        and the queues live here). ``mode`` records the writer's
        (kube, retry_buffer, chunk_waves, wave_width) so a resume on a
        differently-configured engine — including a different chunk grid,
        which silently shifts every boundary time — is rejected instead
        of diverging."""
        return {
            "mode": np.asarray(
                [
                    int(self.kube),
                    self.retry_buffer,
                    self.chunk_waves,
                    self.wave_width,
                ],
                np.int64,
            ),
            "bound": self.st.bound.copy(),
            "assignments": self.assignments.copy(),
            "released": self.released.copy(),
            "bind_chunk": self.bind_chunk.copy(),
            "retry_q": np.asarray(self.retry_q, np.int64),
            "pend": (
                np.asarray(self.pend, np.int64).reshape(-1, 3)
                if self.pend
                else np.zeros((0, 3), np.int64)
            ),
            "counters": np.asarray(
                [self.placed_total, self.preemptions, self.retry_dropped],
                np.int64,
            ),
            "chaos": np.asarray(
                [self.evictions, self.evict_rescheduled], np.int64
            ),
            "evict_lat": np.asarray([self._evict_lat_sum], np.float64),
            "evict_times": (
                np.asarray(
                    [[p, t] for p, t in sorted(self._evict_time.items())],
                    np.float64,
                ).reshape(-1, 2)
            ),
        }

    def restore(self, blob: dict, used, mc, aa, pw) -> None:
        """Rebuild the mirror from a checkpoint: the count planes come
        from the main checkpoint arrays (domain space — the mirror's own
        layout), the rest from :meth:`to_blob`."""
        mode = blob.get("mode")
        if mode is not None and (
            bool(mode[0]) != self.kube or int(mode[1]) != self.retry_buffer
        ):
            want = ("kube" if mode[0] else "retry-only", int(mode[1]))
            raise ValueError(
                f"checkpoint was written by a {want[0]} boundary replay "
                f"with retry_buffer={want[1]}; resume with the same "
                f"configuration (this engine: "
                f"{'kube' if self.kube else 'retry-only'}, "
                f"retry_buffer={self.retry_buffer})"
            )
        if mode is not None and len(mode) >= 4:
            # Chunk-grid guard: boundary indices (bind_chunk, retry_q pend
            # relb) are meaningless on a different grid. Blobs from before
            # this field have len(mode) == 2 and skip the check.
            if (
                int(mode[2]) != self.chunk_waves
                or int(mode[3]) != self.wave_width
            ):
                raise ValueError(
                    f"checkpoint was written on a chunk grid of "
                    f"chunk_waves={int(mode[2])}, wave_width="
                    f"{int(mode[3])}; this engine uses chunk_waves="
                    f"{self.chunk_waves}, wave_width={self.wave_width}. "
                    f"Boundary bookkeeping (bind chunks, pending release "
                    f"boundaries) does not transfer across grids — resume "
                    f"with the original wave_width/completions_chunk_waves "
                    f"or restart the replay from scratch."
                )
        self._plane_log.clear()  # planes below are authoritative
        self.st.used[:] = used
        self.st.match_count[:] = mc
        self.st.anti_active[:] = aa
        self.st.pref_wsum[:] = pw
        self.st.bound[:] = blob["bound"]
        self.assignments[:] = blob["assignments"]
        self.released[:] = blob["released"].astype(bool)
        self.bind_chunk[:] = blob["bind_chunk"]
        self.retry_q = [int(p) for p in blob["retry_q"]]
        self.pend = [list(map(int, row)) for row in blob["pend"]]
        c = blob["counters"]
        self.placed_total = int(c[0])
        self.preemptions = int(c[1])
        self.retry_dropped = int(c[2])
        # Chaos keys absent = a pre-chaos blob (zero disruption so far).
        ch = blob.get("chaos")
        self.evictions = int(ch[0]) if ch is not None else 0
        self.evict_rescheduled = int(ch[1]) if ch is not None else 0
        el = blob.get("evict_lat")
        self._evict_lat_sum = float(el[0]) if el is not None else 0.0
        et = blob.get("evict_times")
        self._evict_time = (
            {int(p): float(t) for p, t in et} if et is not None else {}
        )

    # -- plane folds (eager or logged) --------------------------------------

    def _apply_planes(self, sign: float, pods: np.ndarray, nodes: np.ndarray):
        apply_planes(self.ec, self.ep, self.st, sign, pods, nodes)
        self.plane_folds += 1

    def _plane_op(self, key: tuple, sign: float, pods, nodes) -> None:
        pods = np.asarray(pods, np.int64)
        nodes = np.asarray(nodes, np.int64)
        if not pods.size:
            return
        if self.lazy:
            self._plane_log.append((key, sign, pods, nodes))
        else:
            self._apply_planes(sign, pods, nodes)

    def flush_planes(self) -> None:
        """Apply every logged plane delta in eager order: boundary ``b``'s
        releases (key ``(b, 0)``) before chunk ``b``'s binds (key
        ``(b, 1)``). The per-delta sums are associative-exact (bucketed
        k8s magnitudes — the same invariant fold_chunk already leans on),
        so the mirror planes land bit-identical to the eager path."""
        if not self._plane_log:
            return
        for _key, sign, pods, nodes in sorted(
            self._plane_log, key=lambda e: e[0]
        ):
            self._apply_planes(sign, pods, nodes)
        self._plane_log.clear()

    # -- chunk-side hooks ---------------------------------------------------

    def offer_failure(self, p: int) -> None:
        """A non-gang pod that missed placement enters the buffer, behind
        the pods already there (overflow drops the newest — counted)."""
        if (not self.retry_buffer or self.ep.group_id[p] != PAD
                or self.retry_groups):  # there a JOB joins: ``offer_job``
            return
        if len(self.retry_q) < self.retry_buffer:
            self.retry_q.append(int(p))
        else:
            self.retry_dropped += 1
            self._dropped[p] = True

    def offer_job(self, members: List[int]) -> None:
        """``retry_groups``: a job that was rolled back (or a pod in no
        group that fitted nowhere) waits to join the queue WHOLE at the
        boundary after its closing wave (:meth:`join_failed`)."""
        self._chunk_failed.append((int(self._pos[members[0]]), list(members)))

    def join_failed(self) -> None:
        """The jobs offered since the last boundary join the queue in
        arrival order, each while the buffer has room for ALL its members;
        one that finds less is dropped whole (counted) and those behind it
        that fit still join."""
        for _, members in sorted(self._chunk_failed):
            if len(self.retry_q) + len(members) <= self.retry_buffer:
                self.retry_q.extend(members)
            else:
                self.retry_dropped += len(members)
                self._dropped[members] = True
                self.group_counts["dropped_jobs"] += 1
        self._chunk_failed = []

    def bind_boundary_codes(self) -> np.ndarray:
        """[P] i32 beside ``assignments``: -1 bound in its arrival wave (or
        pre-bound), ``b >= 0`` bound by the retry pass of boundary ``b``;
        for a pod with no node -2 still queued at the end, -3 dropped at a
        full buffer (at its arrival or at its eviction), -4 refused at
        arrival and never queued (a gang member, or any pod without a
        retry buffer), -5 a gang member evicted with its node and stranded
        (``evict_node``). The what-if engine's ``bind_boundary`` hand-back,
        on the host."""
        out = self.bind_boundary.copy()
        none = self.assignments == PAD
        # under ``retry_groups`` every job that fails is queued or dropped
        out[none] = -3 if self.retry_groups else -4
        out[none & self._evicted_gang] = -5
        out[none & self._dropped] = -3
        queued = np.asarray(self.retry_q, np.int64)
        out[queued[none[queued]]] = -2
        return out

    def fold_chunk(self, ci: int, rows: np.ndarray, choices: np.ndarray) -> None:
        """Fold one device chunk's placements into the host mirror (batch
        form of the per-slot binds the greedy anchor performs inline; the
        aggregate f32 sums are the same multiset in the same wave order).
        Failures enter the retry buffer in wave order."""
        ch = np.asarray(choices).reshape(rows.shape)
        v = rows >= 0
        ids = rows[v]
        nd = ch[v]
        placed = nd >= 0
        pid = ids[placed]
        pnd = nd[placed]
        tel = self.tel
        if tel is not None and tel.cfg.want_series and (~placed).any():
            # First-reject attribution for the chunk's failed slots,
            # computed against the pre-chunk mirror state (exact at
            # W=1/C=1 where a chunk IS one slot; chunk-granular
            # otherwise).
            self.flush_planes()  # attribution reads the count planes
            charge_first_rejects(self.fw, self.st, ids[~placed], tel)
        if pid.size:
            self._plane_op((ci, 1), 1.0, pid, pnd)
            self.st.bound[pid] = pnd
            self.assignments[pid] = pnd
            self.bind_chunk[pid] = ci
            self.placed_total += int(pid.size)
            if tel is not None:
                # Wave-placed pods bind in their arrival wave: latency 0.
                tel.bind_zero(int((~self._ever_bound[pid]).sum()))
                self._ever_bound[pid] = True
                if tel.cfg.want_timeline:
                    for p, n in zip(pid.tolist(), pnd.tolist()):
                        tel.event("bind", float(self.ep.arrival[p]), p, n)
        for p in ids[~placed]:
            self.offer_failure(int(p))

    def counters(self) -> tuple:
        """Per-scenario result counters in one tuple — (preemptions,
        retry_dropped, evictions, evict_rescheduled, evict_stranded,
        evict_latency_mean). The exact fields the what-if engine stacks
        per scenario at result assembly; keeping the list HERE means the
        round-11 end-of-replay DCN gather and the single-process oracle
        can never drift on which counters a boundary mirror reports."""
        return (
            self.preemptions, self.retry_dropped, self.evictions,
            self.evict_rescheduled, self.evict_stranded,
            self.evict_latency_mean,
        )

    # -- chaos eviction (node_down NoExecute) -------------------------------

    @property
    def evict_stranded(self) -> int:
        """Evicted pods not re-placed (yet) — final value read at trace end."""
        return len(self._evict_time)

    @property
    def evict_latency_mean(self) -> float:
        """Mean virtual time from eviction to re-bind (boundary-granular)."""
        return (
            self._evict_lat_sum / self.evict_rescheduled
            if self.evict_rescheduled
            else 0.0
        )

    def evict_node(self, node: int, b: int, t_chunk: float) -> PairArrays:
        """NoExecute eviction of every pod the mirror holds bound on
        ``node`` at boundary ``b`` — the device twin of the CPU event
        engine's ``node_down`` handling. Victims are unbound with a FULL
        count rewind, their scheduled releases are cancelled, and non-gang
        victims re-enter the retry buffer exactly like preemption victims
        (overflow counted in ``retry_dropped``). Gang victims cannot
        re-assemble through the boundary retry pass (Permit is in-wave on
        the device), so they stay displaced and surface as stranded.
        Returns the (pods, nodes) pair for the device carry delta; the
        caller must have the mirror current through chunk ``b-1``
        (``fold_chunk``/``_fold_pending``) before calling."""
        return self._evict(np.nonzero(self.st.bound == node)[0], node, b, t_chunk)

    def _evict(self, victims, node: int, b: int, t_chunk: float,
               kind: Optional[int] = None) -> PairArrays:
        """``evict_node``'s body over ``victims``, pods bound on ``node``
        (ascending). ``kind`` (budgeted drains only): what the log says of
        the rows beside them, ``EVICT_KINDS``; each victim with an
        application is counted down in ``unavail``."""
        ec, ep, st = self.ec, self.ep, self.st
        victims = np.asarray(victims, np.int64)
        if not victims.size:
            return _empty_pairs()
        # unbind reads/writes the live count planes — logged deltas must
        # land first (chaos is rare; quiet runs never pay this flush).
        self.flush_planes()
        for v in victims:
            v = int(v)
            if self.tel is not None:
                # Eviction starts a fresh unschedulable episode.
                self.tel.clear_episode(v)
                if self.tel.cfg.want_timeline:
                    self.tel.event("evict", float(t_chunk), v, int(node))
            unbind(ec, ep, st, v)
            self.evictions += 1
            self._evict_time[v] = float(t_chunk)
            self.evict_log.append(
                (int(b), v, int(node), int(self.bind_boundary[v]))
            )
            if kind is not None:
                self.evict_kind.append(int(kind))
                if self.budget.app_of[v] >= 0:
                    self.unavail[self.budget.app_of[v]] += 1
            # Same bookkeeping as a preemption victim: a displaced pod's
            # pending release no longer frees anything, and a later
            # re-placement starts at THAT boundary — the arrival-based
            # static release must never fire.
            self.pend[:] = [e for e in self.pend if e[1] != v]
            self.bind_chunk[v] = _NEVER
            if self.assignments[v] >= 0:
                self.assignments[v] = PAD
                if ep.bound_node[v] == PAD:
                    self.placed_total -= 1
            if self.retry_buffer and ep.group_id[v] == PAD:
                if len(self.retry_q) < self.retry_buffer:
                    self.retry_q.append(v)
                else:
                    self.retry_dropped += 1
                    self._dropped[v] = True
            elif ep.group_id[v] != PAD:
                self._evicted_gang[v] = True
        return victims, np.full(victims.size, int(node), np.int64)

    # -- a drain under disruption budgets ------------------------------------

    def set_budget(self, budget) -> None:
        """Arm the budgeted drain (``sim.runtime.DisruptionBudget``): from
        here on the caller hands every boundary's due events to
        ``budget_events`` instead of calling ``evict_node``. A node is in
        service, cordoned (``cordon_at >= 0``: the boundary of its cordon;
        ``walk_place``: its place among the cordoned, the order of the
        ``node_cordon`` events) or out (``until >= 0``: the boundary it is
        back at, ``_NEVER`` for a failed node, which waits for its
        ``node_up``); ``closed`` is what takes no bind."""
        N = self.ec.num_nodes
        self.budget = budget
        self.cordon_at = np.full(N, -1, np.int64)
        self.walk_place = np.zeros(N, np.int64)
        self._walked = 0
        self.until = np.full(N, -1, np.int64)
        # the fourth answer: the boundary a cordoned node went out (empty,
        # at its deadline or by a failure), -1 never
        self.node_out_at = np.full(N, -1, np.int32)
        self.unavail = np.zeros(len(budget.max_unavailable), np.int64)
        self.evict_kind: List[int] = []  # beside evict_log: EVICT_KINDS
        self.budget_counts = dict.fromkeys(BUDGET_COUNTERS, 0)

    @property
    def closed(self) -> np.ndarray:
        """[N] bool: the nodes that take no bind now, cordoned or out."""
        return (self.cordon_at >= 0) | (self.until >= 0)

    def cordon_node(self, node: int, b: int) -> None:
        """``node_cordon`` at boundary ``b``: of a node that is out or
        cordoned already, nothing (its maintenance counts as done, or is
        under way)."""
        if self.until[node] < 0 and self.cordon_at[node] < 0:
            self.cordon_at[node] = b
            self.walk_place[node] = self._walked
            self._walked += 1

    def budget_events(self, b: int, t_chunk: float, due) -> List[PairArrays]:
        """The events of boundary ``b`` under the budget, BEFORE its
        releases and its retry pass (``due``: the timeline's events that
        fall here, in its order), as the what-if eviction program makes
        them:

        1. back: a node whose outage ends at ``b`` (``until == b``, or the
           last ``node_down`` / ``node_up`` due for it is a ``node_up``) is
           in service, empty, no longer cordoned;
        2. forced: every ``node_down`` due (timeline order), then every
           cordoned node at its deadline (walk order), loses every live
           bind, whatever the budgets say; the evictions count against
           them. A failed node waits for its ``node_up`` (one due here
           already: it stays in service); a node at its deadline is out for
           ``out_for`` boundaries;
        3. cordon: the ``node_cordon`` s due, except of a node that failed
           here;
        4. voluntary: the live binds on the cordoned nodes, in walk order
           and a node's by id, each evicted iff its application has fewer
           than ``max_unavailable`` down at its turn;
        5. a cordoned node that holds nothing now goes out for ``out_for``.

        Returns the (pods, nodes) pairs evicted, for the device delta."""
        bud, cnt = self.budget, self.budget_counts
        downs: List[int] = []
        cordons: List[int] = []
        last: Dict[int, str] = {}
        for ev in due:
            n = int(ev.node)
            if ev.kind == "node_down" and n not in downs:
                downs.append(n)
            if ev.kind == "node_cordon" and n not in cordons:
                cordons.append(n)
            if ev.kind in ("node_down", "node_up"):
                last[n] = ev.kind
        ups = [n for n, k in last.items() if k == "node_up"]
        pairs: List[PairArrays] = []
        on = lambda n: np.nonzero(self.st.bound == n)[0]
        # 1.
        back = np.concatenate([np.nonzero(self.until == b)[0],
                               np.asarray(ups, np.int64)]).astype(np.int64)
        self.until[back] = -1
        self.cordon_at[back] = -1
        # 2.
        for n in downs:
            got = self._evict(on(n), n, b, t_chunk, kind=EVICT_KINDS["failure"])
            pairs.append(got)
            cnt["evict_forced_failure"] += len(got[0])
            if self.cordon_at[n] >= 0:
                self.node_out_at[n] = b
                self.cordon_at[n] = -1
            if n not in ups:
                self.until[n] = _NEVER
        cord = self._cordoned()
        for n in cord[(self.cordon_at[cord] < b)
                      & (self.cordon_at[cord] + bud.grace <= b)].tolist():
            got = self._evict(on(n), n, b, t_chunk, kind=EVICT_KINDS["deadline"])
            pairs.append(got)
            cnt["evict_forced_deadline"] += len(got[0])
            cnt["nodes_forced" if len(got[0]) else "nodes_drained"] += 1
            self._goes_out(n, b)
        # 3.
        for n in cordons:
            if n not in downs:
                self.cordon_node(n, b)
        # 4. and 5.
        for n in self._cordoned().tolist():
            asked = on(n)
            take = np.zeros(len(asked), bool)
            spent = self.unavail.copy()
            for i, v in enumerate(asked.tolist()):
                a = int(bud.app_of[v])
                take[i] = a < 0 or spent[a] < bud.max_unavailable[a]
                if take[i] and a >= 0:
                    spent[a] += 1
            pairs.append(self._evict(asked[take], n, b, t_chunk,
                                     kind=EVICT_KINDS["voluntary"]))
            cnt["evict_voluntary"] += int(take.sum())
            cnt["evict_deferred"] += int((~take).sum())
            if take.all():
                cnt["nodes_drained"] += 1
                self._goes_out(n, b)
        cnt["budget_spent_max"] = max(cnt["budget_spent_max"],
                                      int(self.unavail.sum()))
        return [p for p in pairs if p[0].size]

    def _cordoned(self) -> np.ndarray:
        """The cordoned nodes, in walk order."""
        cord = np.nonzero(self.cordon_at >= 0)[0]
        return cord[np.argsort(self.walk_place[cord], kind="stable")]

    def _goes_out(self, node: int, b: int) -> None:
        self.cordon_at[node] = -1
        self.until[node] = b + int(self.budget.out_for)
        self.node_out_at[node] = b

    # -- the boundary -------------------------------------------------------

    def boundary(
        self, b: int, t_chunk: float
    ) -> Tuple[PairArrays, PairArrays, PairArrays]:
        """Run boundary ``b`` (start time ``t_chunk``). Returns
        ``(releases, binds, evictions)`` as (pods, nodes) int array pairs
        — the device engine turns them into carry-plane deltas; the
        greedy anchor ignores them (its state IS self.st).

        Split since round 10 into ``boundary_releases`` (passes 1–2) +
        ``boundary_retry`` (pass 3): the release passes only read state
        from chunks ≤ b−2 (the one-chunk slack pins the static mask to
        ``bind_chunk < b−1`` and pend entries were scheduled ≥ one
        boundary ahead), so the double-buffered runtime stages them
        BEFORE folding chunk b−1 — overlapping host release bookkeeping
        with device compute — while the retry pass, which reads the
        folded planes through schedule_one, stays after the fold.
        Composing the two here is byte-for-byte the old single pass."""
        rel = self.boundary_releases(b, t_chunk)
        binds, evicts = self.boundary_retry(b, t_chunk)
        return rel, binds, evicts

    def boundary_releases(self, b: int, t_chunk: float) -> PairArrays:
        """Passes 1–2 of boundary ``b``: pend + static-bucket releases.
        Safe to run before chunk b−1's fold (see ``boundary``)."""
        st = self.st
        if np.isfinite(t_chunk):
            # Retry binds at the trailing (t=inf) boundary record latency
            # clamped to the last finite boundary time — the same
            # boundary-granular envelope the chaos reschedule latency uses.
            self._last_finite_t = float(t_chunk)
        # 1. Pending releases of boundary-placed pods (relb encodes the
        # time comparison already — no finite-t gate).
        rel_pods: List[int] = []
        still = []
        for entry in self.pend:
            if entry[0] <= b:
                rel_pods.append(int(entry[1]))
            else:
                still.append(entry)
        self.pend[:] = still
        # 2. Static releases (pods that started at arrival): candidates
        # come from the per-boundary bucket; the dynamic residue — still
        # bound, not already released, not retry-placed (those release
        # through pend only) — is re-checked here. One batched rewind
        # replaces the per-pod unbind loop; the sums are associative-exact
        # (see flush_planes), so the planes match the sequential path.
        if b < self._n_rel_buckets and np.isfinite(t_chunk):
            cand = self._rel_bucket_pods[
                self._rel_bucket_off[b] : self._rel_bucket_off[b + 1]
            ]
            if cand.size:
                m = (
                    (st.bound[cand] >= 0)
                    & ~self.released[cand]
                    & (self.bind_chunk[cand] < b - 1)
                )
                if m.any():
                    rel_pods.extend(cand[m].tolist())
        if rel_pods:
            rel_p = np.asarray(rel_pods, np.int64)
            rel_n = st.bound[rel_p].astype(np.int64)
            self._plane_op((b, 0), -1.0, rel_p, rel_n)
            st.bound[rel_p] = PAD
            self.released[rel_p] = True
            return (rel_p, rel_n)
        return _empty_pairs()

    def _retry_jobs(self, b: int, t_chunk: float, binds_l: list) -> None:
        """Boundary ``b``'s pass under ``retry_groups``: the queue in kube's
        QueueSort order with the job's creation as the tie (priority
        descending, the job's arrival, the member's place in the job), every
        queued JOB tried as at its arrival: members in order, each on the
        state the binds before it give, the tentative binds of its own job
        included, the members after a failed one still tried; the verdict
        at its last member. Bound: its binds are committed and recorded
        and it leaves the queue. Rolled back: what its members took is
        given back before the next job's first member, nothing is
        recorded, all members keep their place."""
        ec, ep, st, job = self.ec, self.ep, self.st, self.job
        if not self.retry_q:
            return
        self.flush_planes()
        # a job's members stand in wave order from its first: the place in
        # the waves IS (the job's arrival, the member's place in the job)
        self.retry_q.sort(key=lambda p: (-int(ep.priority[p]), int(self._pos[p])))
        q, still, n, i = self.retry_q, [], self.group_counts, 0
        while i < len(q):
            size = int(job[q[i], 0])
            members, i = q[i : i + size], i + size
            n["pass_attempts"] += 1
            bound = []
            for p in members:
                node = self.fw.schedule_one(st, p, allow_preemption=False).node
                if node != PAD:
                    bind(ec, ep, st, p, node)
                    bound.append((p, int(node)))
            if len(bound) < size:
                for p, _ in bound:
                    unbind(ec, ep, st, p)
                n["pass_rollbacks"] += 1
                n["pass_rollbacks_after_bind"] += bool(
                    bound and size > self.wave_width)
                still.extend(members)
                continue
            n["jobs_bound_pass"] += 1
            for p, node in bound:
                binds_l.append((p, node))
                self.assignments[p] = node
                self.bind_boundary[p] = b
                self.placed_total += 1
                self._schedule_release(p, node, b, t_chunk)
        self.retry_q = still

    def _schedule_release(self, p: int, node: int, b: int, t_chunk: float) -> None:
        """A bind made by boundary ``b``'s pass STARTS now, not at its
        arrival: it is released at the first boundary whose time reaches
        ``t_b + duration`` (f32 boundary search), at least ``b + 1``."""
        dur = np.float32(self.ep.duration[p])
        if np.isfinite(dur):
            rb = int(np.searchsorted(
                self.tb32, np.float32(t_chunk) + dur, side="left"))
            if rb < len(self.tb32):
                self.pend.append([max(rb, b + 1), p, int(node)])

    def boundary_retry(
        self, b: int, t_chunk: float
    ) -> Tuple[PairArrays, PairArrays]:
        """Pass 3 of boundary ``b``: the bounded retry (+ kube
        preemption) walk and the telemetry occupancy sample. Reads the
        folded count planes — must run AFTER chunk b−1's fold."""
        ec, ep, st = self.ec, self.ep, self.st
        tel = self.tel
        binds_l: List[Tuple[int, int]] = []
        evicts_l: List[Tuple[int, int]] = []
        if self.retry_groups:
            self.join_failed()
            self._retry_jobs(b, t_chunk, binds_l)
        # 3. Bounded retry (+ kube preemption) pass in QueueSort order:
        # priority descending, then the order of entering the queue (a
        # stable sort: pods of one priority keep FIFO order). Victims
        # re-enter the walked queue and are attempted later in the SAME
        # pass — mirroring the CPU event engine, which requeues victims
        # into the activeQ at the preemption instant.
        if self.retry_buffer and self.retry_q and not self.retry_groups:
            # The pass reads the count planes through schedule_one — any
            # logged deltas must land first (rare path; quiet runs never
            # get here and never pay a fold).
            self.flush_planes()
            self.retry_q.sort(key=lambda p: -int(ep.priority[p]))
            q = self.retry_q
            still_q: List[int] = []
            i = 0
            want_reasons = tel is not None and tel.cfg.want_series
            while i < len(q):
                p = q[i]
                i += 1
                res = self.fw.schedule_one(
                    st, p, allow_preemption=self.kube, want_reasons=want_reasons
                )
                if res.node == PAD:
                    if want_reasons and res.reasons is not None:
                        # Grows rejection_attempts every boundary; charges
                        # `reasons` only if the pod's in-scan failure was
                        # not already attributed (episode semantics).
                        tel.rejection(int(p), res.reasons)
                    still_q.append(p)
                    continue
                for v in res.victims:
                    v = int(v)
                    if tel is not None:
                        tel.clear_episode(v)
                        if tel.cfg.want_timeline:
                            tel.event(
                                "preempt", self._last_finite_t, v, int(st.bound[v])
                            )
                    evicts_l.append((v, int(st.bound[v])))
                    unbind(ec, ep, st, v)  # FULL count rewind — no phantoms
                    self.preemptions += 1
                    # A victim with a scheduled pending release no longer
                    # holds what that release would free — cancel it; and
                    # if re-placed later it starts at THAT boundary, so its
                    # arrival-based static release must never fire.
                    self.pend[:] = [e for e in self.pend if e[1] != v]
                    self.bind_chunk[v] = 1 << 30
                    if self.assignments[v] >= 0:
                        self.assignments[v] = PAD
                        if ep.bound_node[v] == PAD:
                            self.placed_total -= 1
                    if (len(q) - i) + len(still_q) < self.retry_buffer:
                        q.append(v)
                    else:
                        self.retry_dropped += 1
                bind(ec, ep, st, p, res.node)
                binds_l.append((p, int(res.node)))
                self.assignments[p] = res.node
                self.bind_boundary[p] = b
                if tel is not None:
                    tel.clear_episode(p)
                    t_bind = (
                        float(t_chunk)
                        if np.isfinite(t_chunk)
                        else self._last_finite_t
                    )
                    if not self._ever_bound[p]:
                        # First bind through the retry pass: latency is
                        # boundary-granular virtual wait since arrival.
                        self._ever_bound[p] = True
                        lat = t_bind - float(ep.arrival[p])
                        if lat <= 0.0:
                            tel.bind_zero()
                        else:
                            tel.bind_latency(p, lat)
                    if tel.cfg.want_timeline:
                        tel.event("bind", t_bind, int(p), int(res.node))
                if ep.bound_node[p] == PAD:
                    self.placed_total += 1
                if p in self._evict_time:
                    # A chaos-evicted pod re-bound: boundary-granular
                    # reschedule latency (the trailing boundary's inf
                    # start time contributes 0 — the re-bind still counts).
                    t_ev = self._evict_time.pop(p)
                    self.evict_rescheduled += 1
                    if self.budget is not None and self.budget.app_of[p] >= 0:
                        # its application has one task fewer down: the next
                        # boundary's evictions see it
                        self.unavail[self.budget.app_of[p]] -= 1
                    if np.isfinite(t_chunk):
                        self._evict_lat_sum += float(t_chunk) - t_ev
                self._schedule_release(p, res.node, b, t_chunk)
            self.retry_q = still_q
        if tel is not None and tel.cfg.want_series and np.isfinite(t_chunk):
            # Post-boundary occupancy in virtual time (the device twin of
            # the CPU engine's per-event queue-depth samples). Utilization
            # gauges need the mirror's committed planes — flush the lazy
            # plane log first (cheap/idempotent when empty; the caller
            # already forced a pre-boundary fold under want_series).
            self.flush_planes()
            from ..utils.metrics import series_gauges

            tel.sample(
                float(t_chunk),
                retry_depth=len(self.retry_q),
                pend_depth=len(self.pend),
                **series_gauges(
                    self.st.used, self.ec.allocatable, self.ec.vocab._r
                ),
            )

        def _pairs(lst: List[Tuple[int, int]]) -> PairArrays:
            if not lst:
                return _empty_pairs()
            a = np.asarray(lst, np.int64)
            return a[:, 0], a[:, 1]

        return _pairs(binds_l), _pairs(evicts_l)
