"""Simulation telemetry — layer L7 (SURVEY.md §5).

Cross-engine observability signals collected DURING replay and reduced to
compact summaries on ``ReplayResult``/``WhatIfResult``:

* **Per-pod scheduling latency** — arrival → *first* bind in virtual time.
  The CPU event engine records exact event-clock latencies; the device path
  is chunk-granular (wave-placed pods bind in their arrival wave ⇒ latency
  0, boundary-retry binds record ``t_boundary − arrival``). Both engines
  reduce through :func:`latency_summary`, so at W=1/C=1 on
  boundary-cadence-aligned traces the histograms bit-match.

* **Filter-rejection attribution** — kube-style "0/N nodes available"
  breakdown: for each fully-failed scheduling attempt, every node is
  charged to the FIRST plugin (in Filter order) that rejected it. Two
  counters are kept:

  - ``reasons`` — per *unschedulable episode*: counted once when a pod
    first goes unschedulable (and again only after an eviction starts a
    new episode). Invariant to retry cadence, so it bit-matches across
    engines wherever placements do.
  - ``rejection_attempts`` — accumulated across every failed attempt.
    Engine-cadence-dependent (the CPU queue uses exponential backoff, the
    device path retries at chunk boundaries); bit-matches only on traces
    whose retry instants coincide.

* **Virtual-time series** (``series`` granularity) — queue/retry-buffer
  depth sampled at event instants (CPU) or chunk boundaries (device).

* **Wall-clock phase breakdown** — perf-counter timers over dispatch /
  device step / boundary fold / host mirror, attached at every
  granularity except ``off``.

* **Timeline events** (``timeline`` granularity) — bind / preempt / evict
  / node_down / node_up instants in virtual time, exportable as a Chrome
  trace (Perfetto-loadable) via :func:`write_chrome_trace`.

Granularity knob (``telemetry:`` YAML section, ``TelemetryConfig``):

    off      — collect nothing, ``ReplayResult.telemetry`` is None.
    summary  — latency histogram + phase timers. Never changes a device
               program: the plain scan stays byte-identical (bench-safe).
    series   — + rejection attribution + virtual-time series. On the
               plain device path this swaps in an instrumented chunk
               program carrying in-scan per-plugin reject counters.
    timeline — + timeline events + Chrome-trace export.

Checkpoint note: telemetry state is deliberately EXCLUDED from boundary
checkpoint blobs — blobs stay bit-identical with telemetry on or off.
"""

from __future__ import annotations

import json
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

# Fixed exponential bucket edges (virtual seconds), kube-histogram style.
# The overflow bucket is implicit (label "+Inf").
LATENCY_BUCKETS: Tuple[float, ...] = (
    0.0, 0.5, 1.0, 2.0, 4.0, 8.0, 16.0, 32.0, 64.0, 128.0, 256.0, 512.0,
)

_LEVELS = ("off", "summary", "series", "timeline")


@dataclass(frozen=True)
class TelemetryConfig:
    granularity: str = "summary"

    def __post_init__(self):
        if self.granularity not in _LEVELS:
            raise ValueError(
                f"telemetry granularity {self.granularity!r} must be one of "
                f"{', '.join(_LEVELS)}"
            )

    @classmethod
    def resolve(cls, v) -> "TelemetryConfig":
        """None → default (summary); str → validated; config → itself."""
        if v is None:
            return cls()
        if isinstance(v, cls):
            return v
        return cls(granularity=str(v))

    @property
    def enabled(self) -> bool:
        return self.granularity != "off"

    @property
    def want_series(self) -> bool:
        return _LEVELS.index(self.granularity) >= 2

    @property
    def want_timeline(self) -> bool:
        return _LEVELS.index(self.granularity) >= 3


def latency_summary(
    zero_count: int, values: Sequence[float]
) -> Optional[dict]:
    """Reduce first-bind latencies (``zero_count`` exact zeros + explicit
    ``values``) to count/mean/p50/p90/p99 plus fixed-bucket cumulative
    counts. Shared by BOTH engines — quantiles use ``np.percentile``
    with ``method='lower'`` (an exact data value), so engines that record
    the same latency multiset produce bit-identical summaries."""
    vals = np.asarray(list(values), dtype=np.float64)
    n = int(zero_count) + vals.size
    if n == 0:
        return None
    arr = np.concatenate([np.zeros(int(zero_count), dtype=np.float64), vals])
    arr.sort()
    buckets: Dict[str, int] = {}
    # Cumulative "le" buckets (kube-style); searchsorted on the sorted array.
    idx = np.searchsorted(arr, np.asarray(LATENCY_BUCKETS), side="right")
    for edge, c in zip(LATENCY_BUCKETS, idx):
        buckets[f"le_{edge:g}"] = int(c)
    buckets["le_inf"] = n
    p50, p90, p99 = (
        float(np.percentile(arr, q, method="lower")) for q in (50, 90, 99)
    )
    return {
        "count": n,
        "mean": float(arr.mean()),
        "max": float(arr[-1]),
        "p50": p50,
        "p90": p90,
        "p99": p99,
        "buckets": buckets,
    }


# Canonical phase-timer names instrumented by the replay engines. The
# flight stream's and the benchmark's consumers key on these strings when
# attributing wall-clock, so they are API: renaming one is a breaking
# change pinned by tests/test_telemetry.py.
PHASE_NAMES = (
    "stage", "dispatch", "device_wait", "boundary_fold", "host_mirror",
    "gather",
    # what-if only: the device-release path's placements to the host
    "handback",
)

#: Every host span the program writes by a fixed name (``profiling.Span``,
#: armed by ``KSIM_PROFILE_DIR``): the phases, each at every site that
#: ticks its timer, a what-if block's checkpoint publication, and under a
#: mesh the puts on the devices (inside ``stage``) and the placements'
#: gather and fetch (inside ``handback``), which carry ``bytes=`` as the
#: event's stats; ``host_events``: what the host does for a boundary's
#: timeline events on the what-if device path (the eviction program's
#: arguments; its call is ``boundary_fold``'s); ``handback_wait`` and
#: ``handback_fetch``, inside ``handback`` of a batch with a
#: ``retry_buffer``: the hand-back program from its dispatch to its outputs
#: being ready, then one span an answer brought to the host (``answer=``
#: its name, ``bytes=`` its size); ``retry_pass_waves``, a batch under
#: ``retry_groups``: no time, a counter: ``waves=`` the wave steps its passes
#: EXECUTED, ``passes=`` how many. Trace readers import these and hold no
#: list of their own.
HOST_SPAN_NAMES = PHASE_NAMES + (
    "checkpoint", "mesh_put", "mesh_fetch", "host_events",
    "handback_wait", "handback_fetch", "retry_pass_waves",
)
#: ``chunk:<i>``: the dispatch of chunk ``i``, inside ``dispatch``.
CHUNK_SPAN = "chunk"
#: ``replay:<n>`` / ``whatif_run:<n>``: one root around everything the
#: engine's ``n``-th ``replay()`` / ``run()`` does (0-based). Every other
#: span of the call lies inside it on the calling thread.
ROOT_SPANS = ("replay", "whatif_run")


class PhaseTimers:
    """Accumulating wall-clock phase breakdown. ``tick(phase)`` returns a
    context manager; overhead is two ``perf_counter`` calls per use, so it
    is safe at chunk cadence (never per pod)."""

    def __init__(self):
        self.acc: Dict[str, float] = {}

    class _Tick:
        __slots__ = ("timers", "phase", "t0")

        def __init__(self, timers: "PhaseTimers", phase: str):
            self.timers = timers
            self.phase = phase

        def __enter__(self):
            self.t0 = time.perf_counter()
            return self

        def __exit__(self, *exc):
            self.timers.add(self.phase, time.perf_counter() - self.t0)
            return False

    def tick(self, phase: str) -> "_Tick":
        return PhaseTimers._Tick(self, phase)

    def add(self, phase: str, dt: float) -> None:
        self.acc[phase] = self.acc.get(phase, 0.0) + dt

    def summary(self) -> Dict[str, float]:
        return {k: round(v, 6) for k, v in sorted(self.acc.items())}


@dataclass
class ReplayTelemetry:
    """Telemetry attached to ``ReplayResult.telemetry`` (None at ``off``).

    Leaves are plain picklable data (dicts/lists/ints/floats) end to
    end, NEVER device arrays — round 11 ships per-scenario instances
    through the host-side DCN gather (parallel.dcn.gather) at what-if
    result assembly, and the single-process oracle must see identical
    objects after the pickle round-trip (pinned in tests/test_dcn.py)."""

    granularity: str
    # Latency histogram (see latency_summary); None when nothing bound.
    latency: Optional[dict] = None
    # Per-episode first-reject counts by plugin name ("unschedulable
    # reasons" — each sums to num_nodes per episode).
    reasons: Optional[Dict[str, int]] = None
    # Per-attempt first-reject counts (cadence-dependent; >= reasons).
    rejection_attempts: Optional[Dict[str, int]] = None
    # Virtual-time series: {"t": [...], "<depth name>": [...], ...}.
    series: Optional[Dict[str, List[float]]] = None
    # Wall-clock phase accumulators (seconds).
    phases: Dict[str, float] = field(default_factory=dict)
    # Raw first-bind latencies for pods that did NOT bind in their arrival
    # instant/wave (pod → virtual seconds) + count of exact-zero binds.
    # Kept for the timeline exporter and tests; not in summary().
    bind_latency: Dict[int, float] = field(default_factory=dict)
    zero_latency_binds: int = 0
    # Timeline events: (kind, t, pod, node) with pod/node = -1 when n/a.
    events: List[Tuple[str, float, int, int]] = field(default_factory=list)
    # Chunk width the device replay ran: the granularity guard may shrink
    # the configured one (sim.granularity). None where no chunk loop ran.
    chunk_waves: Optional[int] = None
    # Form of the in-wave usage corrections the chunk program was built
    # with (ops.tpu3.inwave_corrections): "plane", "terms" or
    # "resolved_terms".
    inwave_corrections: Optional[str] = None
    # Whether a slot of the chunk program finds the spread's zone
    # feasibility and its node in one node-wide reduce or in two
    # (ops.tpu3.select_form): "zone_packed" or "two_pass".
    select_form: Optional[str] = None
    # What-if batches only: the count planes of the compiled problem
    # (ops.tpu3.count_planes: rows at domain scale and at host scale, the
    # domain width, spread rows, term rows, and under ``host_commit`` the
    # host rows by the form of their wave-end commit); scenarios evaluated; on the
    # device-release path the pow2 widths its release program ran with and
    # the largest number of rank rounds one block of a release list needed
    # (1: no two releases of a block ever hit one node; ops.release_planes);
    # where placements come back from the device in one copy, its bytes (0
    # when they were not asked for). Under a device mesh, ``mesh``: devices,
    # scenarios a device, bytes put on the devices and fetched from them in
    # the batch and the host seconds of those calls (nested in the ``stage``
    # and ``handback`` phases, so not phases themselves: the phases tile
    # the call), and the cross-device instructions counted once in the
    # compiled programs (``chunk`` and ``handback``: 0 expected; ``gather``:
    # the one all-gather that brings the placements to one device).
    count_planes: Optional[Dict[str, object]] = None
    # What-if batches only: the form in which a slot reads the row of its
    # toleration / node-affinity class (ops.tpu3.class_row_reads), "slice"
    # or "select": ``arrival`` for the chunk's arrival waves, ``retry`` for
    # the retry pass (only with ``retry_buffer`` on the device path), and
    # the rows of the two class planes (``tol_classes``, ``na_classes``; 0:
    # the program holds no such plane). Static per compiled program.
    class_row_reads: Optional[Dict[str, object]] = None
    scenarios: Optional[int] = None
    release_buckets: Optional[List[int]] = None
    release_rounds: Optional[int] = None
    handback_bytes: Optional[int] = None
    mesh: Optional[Dict[str, object]] = None
    # Only where the trace has a pod group wider than the wave (the v3
    # step's carried transaction, ops.tpu3.GangTxn): ``wide_groups``,
    # ``max_group``, ``max_waves_spanned`` and ``rollback_form`` are static
    # per compiled program; ``wide_rolled_back`` (groups) and
    # ``pods_rolled_back`` (binds given back) are counted on the device and
    # fetched at gather, summed over a what-if batch's scenarios.
    gangs: Optional[Dict[str, object]] = None
    # Only on the what-if device retry path (``retry_buffer``): the buffer,
    # the passes made, and per scenario mean / max of ``retry_placed``,
    # ``retry_dropped``, the queue's depth at the boundaries (``depth_max``,
    # ``depth_at_end``), ``release_leaked`` (re-tried binds whose release
    # fell inside the trace and never ran: 0 by construction) and, where the
    # placements were handed back, ``handback_merged`` (the re-tried binds
    # the hand-back program wrote on the device: ``retry_placed``), with
    # scenario 0's own numbers under ``scenario0``.
    retry: Optional[Dict[str, object]] = None

    def summary(self) -> dict:
        out: dict = {"granularity": self.granularity, "phases": self.phases}
        for key in ("chunk_waves", "inwave_corrections", "select_form",
                    "count_planes", "class_row_reads", "scenarios",
                    "release_buckets", "release_rounds", "handback_bytes",
                    "mesh", "gangs", "retry"):
            if getattr(self, key) is not None:
                out[key] = getattr(self, key)
        if self.latency is not None:
            out["latency"] = self.latency
        if self.reasons is not None:
            out["reasons"] = dict(self.reasons)
            out["rejection_attempts"] = dict(self.rejection_attempts or {})
        if self.series is not None:
            out["series_samples"] = len(self.series.get("t", ()))
        if self.events:
            out["timeline_events"] = len(self.events)
        return out

    def query_view(self) -> dict:
        """JSON-ready per-scenario view for serving-plane query-result
        rows (round 22, sim.service): :meth:`summary` plus the raw
        virtual-time series. Phase timers are dropped — the wall clocks
        of a shared batch replay belong to the batch, not to any one
        tenant's query. Series values are virtual-time-deterministic,
        so a batched query's view bit-matches its sequential oracle's
        (the round-15 batch-composition-independence bar)."""
        out = self.summary()
        out.pop("phases", None)
        if self.series is not None:
            out["series"] = {
                k: [float(v) for v in vs] for k, vs in self.series.items()
            }
        return out

    @classmethod
    def merge(
        cls,
        parts: Sequence[Optional["ReplayTelemetry"]],
        process_ids: Optional[Sequence[int]] = None,
    ) -> Optional["ReplayTelemetry"]:
        """Merge telemetries over disjoint pod/scenario populations into
        one fleet view (round 12). The merge is EXACT, order-normalized
        and associative where the semantics allow:

        * latency — recomputed by :func:`latency_summary` over the union
          of raw first-bind latencies (the summary sorts before every
          reduction), so a 2-process merge bit-matches the single-process
          oracle over the same multiset;
        * ``reasons`` / ``rejection_attempts`` — key-wise integer sums
          (None only when absent from every part);
        * ``series`` / ``events`` — concatenated in part order (parts
          arrive in process order off the DCN gather, which is global
          scenario order);
        * ``phases`` — wall clocks of different hosts never sum
          meaningfully, so with ``process_ids`` given (one per part,
          aligned) part *i*'s timers land under ``p<pid>/<phase>`` and
          stay distinct; without, parts are same-process and timers are
          key-wise summed. Keys already containing ``/`` are assumed
          scoped and pass through (re-merging a merge never
          double-prefixes).

        Raw ``bind_latency`` values are re-keyed by running index: merged
        parts span scenarios, so original pod ids collide and are not
        preserved. ``None`` parts (telemetry off) are skipped; returns
        None when nothing remains.

        Elastic recovery (round 15) keeps this merge byte-stable: a
        survivor that claims a dead process's block republishes that
        block's telemetry under the DEAD pid's gather slot, so parts
        still arrive one per scenario block in global scenario order and
        the result-bearing fields (latency/reasons/series/events)
        bit-match the no-failure fleet. Only the ``p<pid>/<phase>``
        timers are attributed to the block's pid while having been
        *measured* on the claimant's host — wall clocks are
        host-relative either way and are never compared across parts."""
        if process_ids is not None and len(process_ids) != len(parts):
            raise ValueError(
                f"process_ids ({len(process_ids)}) must align 1:1 with "
                f"parts ({len(parts)})"
            )
        keep = [(i, p) for i, p in enumerate(parts) if p is not None]
        if not keep:
            return None
        gran = keep[0][1].granularity
        for _, p in keep:
            if p.granularity != gran:
                raise ValueError(
                    "cannot merge telemetries of different granularity: "
                    f"{p.granularity!r} vs {gran!r}"
                )
        zero = sum(int(p.zero_latency_binds) for _, p in keep)
        vals: List[float] = []
        for _, p in keep:
            vals.extend(float(v) for v in p.bind_latency.values())

        def _sum_counters(attr: str) -> Optional[Dict[str, int]]:
            present = [
                getattr(p, attr) for _, p in keep
                if getattr(p, attr) is not None
            ]
            if not present:
                return None
            out: Dict[str, int] = {}
            for d in present:
                for k, v in d.items():
                    out[k] = out.get(k, 0) + int(v)
            return out

        series: Optional[Dict[str, List[float]]] = None
        if any(p.series is not None for _, p in keep):
            series = {}
            for _, p in keep:
                for k, v in (p.series or {}).items():
                    series.setdefault(k, []).extend(v)
        phases: Dict[str, float] = {}
        for i, p in keep:
            prefix = (
                "" if process_ids is None else f"p{process_ids[i]}/"
            )
            for k, v in p.phases.items():
                key = k if "/" in k else f"{prefix}{k}"
                phases[key] = round(phases.get(key, 0.0) + float(v), 6)
        tel = cls(
            granularity=gran,
            latency=latency_summary(zero, vals),
            phases=phases,
            bind_latency={i: v for i, v in enumerate(vals)},
            zero_latency_binds=zero,
            events=[e for _, p in keep for e in p.events],
        )
        tel.reasons = _sum_counters("reasons")
        tel.rejection_attempts = _sum_counters("rejection_attempts")
        tel.series = series
        # Engine-level counters: parts are disjoint scenario blocks of one
        # batch (or none carries them).
        for key in ("chunk_waves", "inwave_corrections", "select_form",
                    "count_planes", "class_row_reads"):
            values = [getattr(p, key) for _, p in keep]
            if all(v == values[0] for v in values):
                setattr(tel, key, values[0])
        for key in ("scenarios", "handback_bytes"):
            have = [getattr(p, key) for _, p in keep
                    if getattr(p, key) is not None]
            if have:
                setattr(tel, key, sum(have))
        gangs = [p.gangs for _, p in keep if p.gangs is not None]
        if gangs:
            # one layout, one program; the verdicts add up over the blocks
            tel.gangs = dict(gangs[0])
            for key in ("wide_rolled_back", "pods_rolled_back"):
                tel.gangs[key] = sum(g[key] for g in gangs)
        retries = [p.retry for _, p in keep if p.retry is not None]
        if retries:
            # disjoint scenario blocks: the largest of the maxes, the means
            # averaged block by block; scenario 0 lies in the first block
            tel.retry = dict(retries[0])
            for key, v in retries[0].items():
                if isinstance(v, dict) and "max" in v:
                    tel.retry[key] = {
                        "mean": float(np.mean([r[key]["mean"] for r in retries])),
                        "max": max(r[key]["max"] for r in retries),
                    }
        buckets = [p.release_buckets for _, p in keep
                   if p.release_buckets is not None]
        if buckets:
            tel.release_buckets = sorted(set().union(*buckets))
        rounds = [p.release_rounds for _, p in keep
                  if p.release_rounds is not None]
        if rounds:
            tel.release_rounds = max(rounds)
        meshes = [p.mesh for _, p in keep if p.mesh is not None]
        if meshes:
            # Each process's local mesh: devices, bytes, seconds and
            # collectives add up; the scenarios a device are the largest.
            tel.mesh = {
                k: (max(m[k] for m in meshes) if k == "scenarios_per_device"
                    else sum(m[k] for m in meshes))
                for k in meshes[0] if k != "collectives"
            }
            tel.mesh["collectives"] = {
                name: sum(m["collectives"].get(name, 0) for m in meshes)
                for m0 in meshes for name in m0["collectives"]
            }
        return tel


class TelemetryCollector:
    """Mutable per-replay accumulator. Engines call the record hooks (all
    cheap, most gated behind granularity properties); :meth:`result`
    freezes into a :class:`ReplayTelemetry`.

    Episode semantics for rejection attribution: a pod is *attributed*
    after its first fully-failed attempt is charged to ``reasons``;
    further failed attempts only grow ``rejection_attempts`` until a bind
    or an eviction (``clear_episode``) re-arms it."""

    def __init__(
        self, config: Optional[TelemetryConfig] = None,
        chunk_waves: Optional[int] = None,
        inwave_corrections: Optional[str] = None,
        select_form: Optional[str] = None,
    ):
        self.cfg = TelemetryConfig.resolve(config)
        self.chunk_waves = chunk_waves
        self.inwave_corrections = inwave_corrections
        self.select_form = select_form
        self.phases = PhaseTimers()
        self._lat: Dict[int, float] = {}
        self._zero = 0
        self._reasons: Dict[str, int] = {}
        self._attempts: Dict[str, int] = {}
        self._attributed: set = set()
        self._series: Dict[str, List[float]] = {}
        self._events: List[Tuple[str, float, int, int]] = []

    # -- latency ----------------------------------------------------------

    def bind_zero(self, n: int = 1) -> None:
        """n pods bound at their arrival instant/wave (latency exactly 0)."""
        self._zero += int(n)

    def bind_latency(self, pod: int, lat: float) -> None:
        """First bind of ``pod`` at ``lat`` virtual seconds after arrival.
        Caller guarantees first-bind (re-binds after eviction/preemption
        must not re-record)."""
        self._lat[int(pod)] = float(lat)

    # -- rejection attribution -------------------------------------------

    def rejection(self, pod: int, counts: Dict[str, int]) -> None:
        """One fully-failed scheduling attempt for ``pod`` with first-reject
        ``counts`` by plugin name."""
        for k, v in counts.items():
            self._attempts[k] = self._attempts.get(k, 0) + int(v)
        if pod not in self._attributed:
            self._attributed.add(pod)
            for k, v in counts.items():
                self._reasons[k] = self._reasons.get(k, 0) + int(v)

    def clear_episode(self, pod: int) -> None:
        """A bind or an eviction ends the pod's unschedulable episode."""
        self._attributed.discard(int(pod))

    def is_attributed(self, pod: int) -> bool:
        return int(pod) in self._attributed

    def mark_attributed(self, pod: int) -> None:
        """Pod already charged to ``reasons`` elsewhere (e.g. the in-scan
        failure that routed it into the retry buffer)."""
        self._attributed.add(int(pod))

    # -- series / timeline ------------------------------------------------

    def sample(self, t: float, **depths: float) -> None:
        self._series.setdefault("t", []).append(float(t))
        for k, v in depths.items():
            self._series.setdefault(k, []).append(float(v))

    def event(self, kind: str, t: float, pod: int = -1, node: int = -1) -> None:
        self._events.append((kind, float(t), int(pod), int(node)))

    # -- finalize ---------------------------------------------------------

    def result(self) -> Optional[ReplayTelemetry]:
        if not self.cfg.enabled:
            return None
        tel = ReplayTelemetry(
            granularity=self.cfg.granularity,
            latency=latency_summary(self._zero, list(self._lat.values())),
            phases=self.phases.summary(),
            bind_latency=dict(self._lat),
            zero_latency_binds=self._zero,
            chunk_waves=self.chunk_waves,
            inwave_corrections=self.inwave_corrections,
            select_form=self.select_form,
        )
        if self.cfg.want_series:
            # Zero entries are dropped so engine comparisons see the same
            # dict regardless of which plugins happened to run (the CPU
            # Filter chain short-circuits; the device one does not).
            tel.reasons = {k: v for k, v in self._reasons.items() if v}
            tel.rejection_attempts = {
                k: v for k, v in self._attempts.items() if v
            }
            tel.series = {k: list(v) for k, v in self._series.items()}
        if self.cfg.want_timeline:
            tel.events = list(self._events)
        return tel


def first_reject_counts_host(
    plugins, ctx, st, p: int, num_nodes: int
) -> Tuple[np.ndarray, Dict[str, int]]:
    """Host-side first-reject attribution: run the Filter chain charging
    each node to the first plugin that rejects it. Returns (final mask,
    counts). Counting mirrors ``SchedulerFramework.feasible_mask``'s
    short-circuit exactly — once the running mask is empty every later
    plugin rejects 0 additional nodes, so stopping early is lossless."""
    mask = np.ones(num_nodes, dtype=bool)
    counts: Dict[str, int] = {}
    for pl in plugins:
        counts[pl.name] = 0
        m = pl.filter(ctx, st, p)
        if m is not None:
            counts[pl.name] = int((mask & ~m).sum())
            mask &= m
    return mask, counts


# -- Chrome-trace (Perfetto) export --------------------------------------


def _trace_events(
    res,
    arrival: Optional[np.ndarray] = None,
    duration: Optional[np.ndarray] = None,
    process_id: Optional[int] = None,
    requests: Optional[np.ndarray] = None,
    rindex: Optional[Dict[str, int]] = None,
) -> List[dict]:
    """Trace events for ONE result. With ``process_id`` None (the
    single-process export) pids are 0 ("cluster") / 1 ("chaos") exactly
    as before round 12; with ``process_id`` p, the pair becomes one track
    GROUP per process — pids 2p / 2p+1 named "cluster (p<p>)" /
    "chaos (p<p>)" — so merged fleet traces render side by side in one
    Perfetto timeline."""
    tel = getattr(res, "telemetry", None)
    assignments = np.asarray(res.assignments)
    makespan = float(getattr(res, "virtual_makespan", 0.0))
    p_ = process_id
    pid_cluster = 0 if p_ is None else 2 * int(p_)
    pid_chaos = pid_cluster + 1
    suffix = "" if p_ is None else f" (p{int(p_)})"
    ev: List[dict] = [
        {"name": "process_name", "ph": "M", "pid": pid_cluster,
         "args": {"name": f"cluster{suffix}"}},
        {"name": "process_name", "ph": "M", "pid": pid_chaos,
         "args": {"name": f"chaos{suffix}"}},
    ]
    used_nodes = sorted({int(n) for n in assignments if n >= 0})
    for n in used_nodes:
        ev.append({"name": "thread_name", "ph": "M", "pid": pid_cluster,
                   "tid": n, "args": {"name": f"node{n}"}})
    lat = tel.bind_latency if tel is not None else {}
    spans: List[tuple] = []  # (pod, node, start, end) — spans + counters
    if arrival is not None:
        placed = np.nonzero(assignments >= 0)[0]
        for p in placed.tolist():
            start = float(arrival[p]) + float(lat.get(p, 0.0))
            end = makespan
            if duration is not None and np.isfinite(duration[p]):
                end = min(end, start + float(duration[p]))
            spans.append((p, int(assignments[p]), start, end))
            ev.append({
                "name": f"pod{p}", "ph": "X", "pid": pid_cluster,
                "tid": int(assignments[p]),
                "ts": start * 1e6, "dur": max(end - start, 0.0) * 1e6,
            })
    if requests is not None and rindex is not None and spans:
        # Per-node utilization counter tracks (round 13): the pod spans
        # above double as change-points of a running cpu/mem usage sum,
        # emitted as Chrome "C" counter events — Perfetto renders one
        # stacked-area track per node next to its span row.
        req = np.asarray(requests, dtype=np.float64)
        cols = [
            (rn, ri) for rn, ri in sorted(rindex.items(), key=lambda kv: kv[1])
            if rn in ("cpu", "memory")
        ]
        deltas: Dict[int, Dict[float, np.ndarray]] = {}
        for p, n, start, end in spans:
            d = deltas.setdefault(n, {})
            r = req[p, [ri for _, ri in cols]]
            d[start] = d.get(start, 0.0) + r
            d[end] = d.get(end, 0.0) - r
        for n in sorted(deltas):
            run = np.zeros(len(cols), dtype=np.float64)
            for t in sorted(deltas[n]):
                run = run + deltas[n][t]
                ev.append({
                    "name": f"node{n} usage", "ph": "C", "pid": pid_cluster,
                    "tid": n, "ts": t * 1e6,
                    "args": {
                        rn: round(float(run[k]), 6)
                        for k, (rn, _) in enumerate(cols)
                    },
                })
    down_at: Dict[int, float] = {}
    for kind, t, pod, node in (tel.events if tel is not None else ()):
        if kind == "node_down":
            down_at[node] = t
        elif kind == "node_up":
            t0 = down_at.pop(node, t)
            ev.append({"name": f"node{node} down", "ph": "X",
                       "pid": pid_chaos, "tid": node, "ts": t0 * 1e6,
                       "dur": max(t - t0, 0.0) * 1e6})
        else:
            ev.append({
                "name": kind, "ph": "i", "s": "t", "pid": pid_cluster,
                "tid": node if node >= 0 else 0, "ts": t * 1e6,
                "args": ({"pod": pod} if pod >= 0 else {}),
            })
    for node, t0 in sorted(down_at.items()):
        # Unrecovered failure: span runs to the makespan.
        ev.append({"name": f"node{node} down", "ph": "X", "pid": pid_chaos,
                   "tid": node, "ts": t0 * 1e6,
                   "dur": max(makespan - t0, 0.0) * 1e6})
    return ev


def write_chrome_trace(
    path: str,
    res,
    arrival: Optional[np.ndarray] = None,
    duration: Optional[np.ndarray] = None,
    process_id: Optional[int] = None,
    requests: Optional[np.ndarray] = None,
    rindex: Optional[Dict[str, int]] = None,
) -> int:
    """Export the SIMULATED cluster timeline as a Chrome trace JSON
    (load in Perfetto / chrome://tracing). Virtual seconds map to trace
    microseconds. Rows (tids) are nodes under the "cluster" process;
    chaos node_down→node_up windows render as spans under "chaos".

    Pod spans are drawn from each pod's FIRST bind (arrival + recorded
    latency) to its completion (or the makespan); disruptions (preempt /
    evict / boundary re-binds) appear as instant events on the node row.
    ``process_id`` scopes the track group for multi-process exports (see
    :func:`_trace_events`); the default keeps the round-7 pid 0/1 layout.
    ``requests`` ([P, R] pod requests) + ``rindex`` (resource → column)
    additionally emit per-node cpu/mem usage counter tracks (round 13).
    Returns the number of trace events written."""
    ev = _trace_events(
        res, arrival, duration, process_id, requests=requests, rindex=rindex
    )
    with open(path, "w") as f:
        json.dump({"traceEvents": ev, "displayTimeUnit": "ms"}, f)
    return len(ev)


def write_chrome_trace_merged(
    path: str,
    parts: Sequence[tuple],
    rindex: Optional[Dict[str, int]] = None,
) -> int:
    """Merge per-process timelines into ONE Chrome trace (round 12): each
    element of ``parts`` is ``(res, arrival, duration)`` — or, round 13,
    ``(res, arrival, duration, requests)`` to add that process's per-node
    usage counter tracks (``rindex`` maps resource → request column; the
    fleet shares one vocabulary) — in process order, and process *i*'s
    events land in its own track group ("cluster (pi)" / "chaos (pi)"),
    so a 2-process DCN replay renders as a single Perfetto timeline.
    Returns the number of trace events written."""
    ev: List[dict] = []
    for i, part in enumerate(parts):
        res, arrival, duration = part[0], part[1], part[2]
        requests = part[3] if len(part) > 3 else None
        ev.extend(_trace_events(
            res, arrival, duration, process_id=i,
            requests=requests, rindex=rindex,
        ))
    with open(path, "w") as f:
        json.dump({"traceEvents": ev, "displayTimeUnit": "ms"}, f)
    return len(ev)
