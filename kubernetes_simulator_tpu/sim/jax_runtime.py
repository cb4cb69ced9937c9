"""The `jax` scheduling strategy — the whole replay as one compiled TPU
program (SURVEY.md §3.1 "device boundary", §3.5).

The host feeds chunks of wave-packed pods; a jitted ``lax.scan`` walks the
waves, evaluating every enabled plugin's Filter mask and Score over all
nodes at once, selecting with a deterministic argmax, and updating the
carried state with scatter-adds. Gang commit/rollback is a masked update at
each wave boundary. Selected through the strategy registry ([BASELINE]: the
CPU plugin path stays the default; `jax` is opt-in).

Semantics = :mod:`.greedy` exactly (the parity anchor): arrival-order
greedy waves with chunk-granular completions ON BY DEFAULT (pods with
finite duration release resources and count contributions at chunk
boundaries, one-chunk slack — see ``JaxReplayEngine.replay``).
Preemption is opt-in: ``preemption="kube"`` runs the EXACT kube
minimal-victims PostFilter in the chunk-boundary pass (round 5,
:mod:`.boundary`); ``"tier"``/``True`` keeps the in-scan tier
approximation. Exact-timestamp event ordering and queue
re-ordering/backoff remain CPU-event-engine-only; batched what-if over
scenarios builds on
this module via ``vmap``/``shard_map`` (:mod:`.whatif`, :mod:`..parallel`).
"""

from __future__ import annotations

import os
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..framework.framework import FrameworkConfig
from ..framework.registry import register_strategy
from ..models.core import Effect
from ..models.encode import PAD, EncodedCluster, EncodedPods
from ..models.state import SchedState, init_state
from ..ops import tpu as T
from ..plugins.builtin import DEFAULT_WEIGHTS
from ..utils.metrics import (
    fragmentation_gauges,
    series_gauges,
    utilization_means,
)
from ..utils.profiling import (
    make_span,
    profiling_active,
    register_program,
    shape_structs,
    stage,
)
from .runtime import ReplayResult, events_hash, validate_node_events
from .telemetry import TelemetryCollector, TelemetryConfig
from .waves import WaveBatch, pack_waves, refuse_wide_gangs, widest_gang


def _file_bytes(path: str) -> int:
    """Blob size for flight-recorder checkpoint rows (0 when unreadable —
    observability never takes the replay down)."""
    import os

    try:
        return os.path.getsize(path)
    except OSError:
        return 0

DEFAULT_PLUGINS = (
    "NodeResourcesFit",
    "TaintToleration",
    "NodeAffinity",
    "InterPodAffinity",
    "PodTopologySpread",
)


@dataclass(frozen=True)
class StepSpec:
    """Static (trace-time) description of the fused Filter+Score step."""

    fit: bool = True
    taints: bool = True
    node_affinity: bool = True
    interpod: bool = True
    spread: bool = True
    fit_strategy: str = "LeastAllocated"
    weights: Tuple[Tuple[str, float], ...] = ()
    resource_weights: Tuple[float, ...] = ()  # [R]
    shape_x: Tuple[float, ...] = (0.0, 100.0)
    shape_y: Tuple[float, ...] = (0.0, 100.0)
    # Static trace properties: gate work the trace can never trigger.
    has_symmetric_pref: bool = True  # any preferred (anti-)affinity terms
    has_gangs: bool = True  # any pod-group membership (gang rollback)
    # Any PreferNoSchedule taint can exist (cluster or injected): when
    # False the taint score row is a constant 100 on every node (raw ≡ 0 →
    # reverse max-normalize), which never changes the argmax — dropped.
    taint_score: bool = True
    # [G] upstream PodTopologySpread topologyNormalizingWeight table:
    # log(size + 2) per match-group's topology ([K8S] scoring.go).
    sp_w_g: Tuple[float, ...] = ()
    # Static guarantee that every possible spread raw ≤ 83886, making the
    # f32 form of the normalize division exactly equal to integer division
    # (see ops.tpu.spread_norm_from_extrema).
    sp_norm_f32: bool = False

    @classmethod
    def from_config(
        cls,
        ec: EncodedCluster,
        config: Optional[FrameworkConfig],
        pods: Optional[EncodedPods] = None,
    ) -> "StepSpec":
        entries = (config.plugins if config and config.plugins is not None else None)
        if entries is None:
            entries = [{"name": n} for n in DEFAULT_PLUGINS]
        names = {e["name"] for e in entries}
        weights = dict(DEFAULT_WEIGHTS)
        if config and config.weights:
            weights.update(config.weights)
        fit_strategy = "LeastAllocated"
        res = {"cpu": 1.0, "memory": 1.0}
        shape = [{"utilization": 0, "score": 0}, {"utilization": 100, "score": 10}]
        for e in entries:
            if e["name"] == "NodeResourcesFit":
                args = e.get("args", {})
                fit_strategy = args.get("strategy", fit_strategy)
                res = args.get("resources", res)
                shape = args.get("shape", shape)
        rw = np.zeros(ec.num_resources, dtype=np.float32)
        for rname, w in res.items():
            ri = ec.vocab._r.get(rname)
            if ri is not None:
                rw[ri] = w
        # Static trace gates: a plugin whose terms never occur in the trace
        # contributes exactly 0 to every mask and normalized score (its raw
        # is all-zero → normalize yields 0), so disabling it is exact.
        na_on = "NodeAffinity" in names
        ip_on = "InterPodAffinity" in names
        sp_on = "PodTopologySpread" in names
        if pods is not None:
            na_on = na_on and bool(
                pods.na_has_req.any() or (pods.na_pref >= 0).any()
            )
            ip_on = ip_on and bool(
                (pods.aff_req >= 0).any()
                or (pods.anti_req >= 0).any()
                or (pods.pref_aff >= 0).any()
            )
            sp_on = sp_on and bool((pods.spread_g >= 0).any())
        return cls(
            fit="NodeResourcesFit" in names,
            taints="TaintToleration" in names,
            taint_score=bool((ec.taint_effect == int(Effect.PREFER_NO_SCHEDULE)).any()),
            node_affinity=na_on,
            interpod=ip_on,
            spread=sp_on,
            fit_strategy=fit_strategy,
            weights=tuple(sorted(weights.items())),
            resource_weights=tuple(float(x) for x in rw),
            shape_x=tuple(float(pt["utilization"]) for pt in shape),
            shape_y=tuple(float(pt["score"]) * 10.0 for pt in shape),
            has_symmetric_pref=(
                bool((pods.pref_aff >= 0).any()) if pods is not None else True
            ),
            has_gangs=(bool((pods.group_id >= 0).any()) if pods is not None else True),
            sp_w_g=(sp_w := _spread_w_table(ec)),
            sp_norm_f32=_spread_norm_f32_ok(sp_w, pods) if sp_on else False,
        )


def _spread_norm_f32_ok(sp_w, pods: Optional[EncodedPods]) -> bool:
    """True when NO trace state can push a spread raw score past 83886 —
    the bound under which the f32 normalize division is exactly the
    integer division (ops.tpu.spread_norm_from_extrema). The 80,000 below
    is also what keeps ``ops.tpu.floor_div_f32`` inside its own bound there:
    numerator 100·(hi+lo−raw) ≤ 16,000,000 plus denominator hi ≤ 80,000 is
    under 2²⁴ = 16,777,216; raise it only with that sum in hand. Conservative:
    per-group counts are bounded by the total pods matching the group
    (plus a wave-correction margin), summed over the pod's constraint
    width at the largest weight/skew."""
    if pods is None:
        return False
    SPw = pods.spread_g.shape[1]
    if SPw == 0:
        return True
    pmg_tot = pods.pod_matches_group.sum(axis=0).astype(np.float64)
    w = np.asarray(sp_w, np.float64)
    L = min(len(pmg_tot), len(w))
    gm = float((pmg_tot[:L] * w[:L]).max()) if L else 0.0
    skew_max = float(pods.spread_skew.max()) if pods.spread_skew.size else 0.0
    bound = SPw * (gm + 64.0 * w.max(initial=0.0) + max(skew_max - 1.0, 0.0))
    return bound <= 80_000.0


def _spread_w_table(ec: EncodedCluster) -> Tuple[float, ...]:
    """[G] upstream topologyNormalizingWeight (log(size + 2)) per
    match-group, matching ops.cpu.spread_weight value-for-value: f64 log
    cast once to f32."""
    G = max(ec.num_groups, 1)
    gt = (
        ec.group_topo[:G]
        if ec.group_topo.shape[0] >= G
        else np.full(G, PAD, np.int32)
    )
    nd_g = np.where(gt >= 0, ec.num_domains[np.clip(gt, 0, None)], 0)
    w = np.log(nd_g.astype(np.float64) + 2.0).astype(np.float32)
    return tuple(float(x) for x in w)


def replicated_resident_bytes(
    ec: EncodedCluster, pods: EncodedPods, pods_resident: bool = True
) -> int:
    """Per-device HBM estimate of the REPLICATED single-scenario
    residency: the DevCluster tensors, the DevState planes, and (when
    ``pods_resident`` — the v3 unpaged layout) the whole-trace
    SlotSource/ExtraSource rows. The ``KSIM_MAX_REPLICATED_BYTES`` gate
    in JaxReplayEngine refuses replicated runs past this estimate with a
    pointer at paged — the Borg-scale shapes (10k nodes × 1M pods) are
    exactly the ones that OOM one chip silently otherwise."""
    dc_fields = (
        ec.allocatable, ec.node_label_key, ec.node_label_kv,
        ec.node_label_num, ec.taint_key, ec.taint_kv, ec.taint_effect,
        ec.node_domain, ec.num_domains, ec.expr_key, ec.expr_op,
        ec.expr_vals, ec.expr_num, ec.group_topo,
    )
    total = sum(int(np.asarray(a).nbytes) for a in dc_fields)
    N, R = ec.num_nodes, ec.num_resources
    G = max(ec.num_groups, 1)
    total += 4 * (N * R + 3 * G * N + G)  # DevState planes (f32)
    if pods_resident:
        pod_fields = (
            pods.requests, pods.tol_key, pods.tol_kv, pods.tol_effect,
            pods.na_req, pods.na_has_req, pods.na_pref, pods.na_pref_w,
            pods.aff_req, pods.anti_req, pods.pref_aff, pods.pref_aff_w,
            pods.spread_g, pods.spread_skew, pods.spread_dns,
            pods.pod_matches_group, pods.group_id,
        )
        total += sum(int(np.asarray(a).nbytes) for a in pod_fields)
    return total


def _pager_thread_enabled() -> bool:
    """Round-19 A/B gate for the threaded pager. Read at pager
    construction (every ``replay()`` builds a fresh pager), so tests and
    the ``overlap:`` config section flip it per run: set
    ``KSIM_PAGER_THREAD=0`` to fetch pages on the chunk-loop thread as
    rounds 14–18 did."""
    return os.environ.get("KSIM_PAGER_THREAD", "1") not in ("", "0")


class _PodPager:
    """Rolling two-deep host→device page prefetcher (round 14 paged pod
    waves): ``get(ci)`` returns chunk ci's staged page (staging it now if
    the prefetch missed — first chunk, resume jumps); ``prefetch(ci)`` is
    called right after dispatching a chunk, so the next page's H2D copies
    are issued while the device is still scanning — the paged twin of the
    double-buffered boundary staging.

    Round 19 (``threaded=True``, the default via ``KSIM_PAGER_THREAD``):
    ``prefetch`` hands the encode/pack + ``device_put`` to ONE background
    worker (a bounded single-slot hand-off — the queue depth stays 2
    counting the in-flight chunk's own page), so a prefetch only costs
    loop wall when the fetch genuinely outruns chunk compute. Pages are
    pure functions of the chunk index, so the staged values are
    bit-identical wherever the fetch runs. Attribution:

    * ``stalls`` / ``stall_s`` — EXPOSED wall: synchronous misses plus
      (threaded) blocking waits on a still-in-flight prefetch. Miss
      COUNTS are deterministic (first chunk, resume jumps); wait counts
      ride ``waits`` because whether a wait occurs is a race outcome.
    * ``prefetch_wall_s`` — the prefetch fetches' own wall: HIDDEN when
      threaded, loop-exposed when not. Overlap efficiency is
      ``prefetch_wall_s / (prefetch_wall_s + stall_s)`` under threading.
    * ``invalidations`` — staged pages discarded because ``get`` asked
      for a different chunk (a resume jump): the stale page is dropped
      and the requested fetch re-issued instead of silently serving a
      plain miss (round-19 fix — previously indistinguishable from a
      cold stall in the flight ``page`` rows)."""

    def __init__(self, fetch, threaded: bool = False):
        self._fetch = fetch
        self._next = None  # (ci, page) or (ci, Future) when threaded
        self.stalls = 0
        self.stall_s = 0.0
        self.last_stall_s = 0.0
        self.prefetches = 0
        self.waits = 0
        self.wait_s = 0.0
        self.prefetch_wall_s = 0.0
        self.invalidations = 0
        self.threaded = bool(threaded)
        self._pool = None
        if self.threaded:
            from concurrent.futures import ThreadPoolExecutor

            self._pool = ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="ksim-pager"
            )

    @property
    def depth(self) -> int:
        """Pages currently staged ahead (0 or 1 — the prefetcher is
        two-deep counting the in-flight chunk's own page)."""
        return 0 if self._next is None else 1

    def _timed_fetch(self, ci: int):
        # Runs on the worker thread when threaded — its wall is the
        # HIDDEN side of the overlap ledger.
        t0 = time.perf_counter()
        page = self._fetch(ci)
        self.prefetch_wall_s += time.perf_counter() - t0
        return page

    def _resolve(self, staged):
        """Staged entry → page, charging any blocking wait as exposed
        stall wall (the fetch outran chunk compute)."""
        from concurrent.futures import Future

        if not isinstance(staged, Future):
            return staged
        if staged.done():
            return staged.result()
        t0 = time.perf_counter()
        page = staged.result()
        dt = time.perf_counter() - t0
        self.waits += 1
        self.wait_s += dt
        self.stall_s += dt
        self.last_stall_s = dt
        return page

    def get(self, ci: int):
        staged, self._next = self._next, None
        if staged is not None and staged[0] != ci:
            # Resume jump: the staged page is for another chunk. Drop it
            # (draining the worker so the single slot is free again) and
            # re-issue the fetch for the chunk actually requested.
            self.invalidations += 1
            try:
                self._resolve_quietly(staged[1])
            except Exception:
                pass
            staged = None
        if staged is not None:
            return self._resolve(staged[1])
        t0 = time.perf_counter()
        page = self._fetch(ci)
        self.last_stall_s = time.perf_counter() - t0
        self.stall_s += self.last_stall_s
        self.stalls += 1
        return page

    def _resolve_quietly(self, staged) -> None:
        from concurrent.futures import Future

        if isinstance(staged, Future) and not staged.cancel():
            staged.result()

    def prefetch(self, ci: int) -> None:
        self.prefetches += 1
        if self._pool is not None:
            self._next = (ci, self._pool.submit(self._timed_fetch, ci))
        else:
            self._next = (ci, self._timed_fetch(ci))

    def close(self) -> None:
        if self._pool is not None:
            self._pool.shutdown(wait=False)
            self._pool = None


def make_chunk_fn3_src(static3, shared3, rep_slots, wave_width: int, spec: StepSpec):
    """The v3 chunk program with the slot gathers INSIDE the jit:
    (dc, state, SlotSource, ExtraSource, idx [C, W]) → (state, choices).
    One dispatch per chunk and only the index array as per-chunk input,
    instead of separate gather dispatches."""
    from ..ops import tpu3 as V3

    def chunk_fn(dc: T.DevCluster, state, src, xsrc, idx):
        with stage("ksim.gather"):
            slots = T.gather_slots_device(src, idx)
            extra = V3.gather_extra_device(xsrc, idx)
        with stage("ksim.derive"):
            d = T.Derived.build(dc)
            cmasks = V3.class_masks(dc, d, static3, spec, rep_slots)
        step = V3.make_wave_step3(
            dc, d, shared3, static3, wave_width, spec, cmasks
        )
        # The scan's own per-wave slicing of the gathered slots and the
        # stacking of the choices; the step's stages inside it win (the
        # innermost scope names an instruction).
        with stage("ksim.gather"):
            state, choices = jax.lax.scan(step, state, (slots, extra))
        return state, choices

    return jax.jit(chunk_fn, donate_argnums=(1,))


def wave_start_times(pods: EncodedPods, idx: np.ndarray) -> np.ndarray:
    """Arrival time of each wave's first valid pod (inf for padding) —
    the boundary clock shared by both engines, BoundaryOps and the
    granularity guard."""
    first = idx[:, 0]
    safe = np.clip(first, 0, None)
    return np.where(first >= 0, pods.arrival[safe], np.inf)


def bind_chunk_of(pods: EncodedPods, idx: np.ndarray, C: int) -> np.ndarray:
    """[P] chunk index each pod's wave belongs to (pre-bound = −2,
    unscheduled = huge) — the bind-chunk side of the one-chunk-slack
    release rule, shared by the single-replay engine and the batch
    what-if eager folds (the rule must stay identical for anchor
    parity)."""
    W = idx.shape[1]
    flat = idx.reshape(-1)
    v = flat >= 0
    out = np.full(pods.num_pods, 1 << 30, np.int64)
    out[flat[v]] = np.nonzero(v)[0] // (C * W)
    out[pods.bound_node >= 0] = -2
    return out


def preemption_walk(assignments: np.ndarray, idx: np.ndarray, finals: np.ndarray,
                    ev_node: np.ndarray, ev_tier: np.ndarray,
                    pod_tier: np.ndarray, nongang: np.ndarray,
                    released: Optional[np.ndarray] = None) -> None:
    """Reconstruct assignments under tier evictions, in place: walk waves
    in order, unassigning prior-wave lower-tier non-gang victims at each
    eviction event, then applying the wave's choices (in-wave victims are
    already PAD in the device output). ``released``: completed pods keep
    their assignment but can no longer be evicted (their resources are
    gone — the device tier planes already dropped them). Shared by the
    replay engine and the what-if collect/completions paths.

    Vectorized (round 5): eviction events are rare, so the walk is bulk
    segment folds between event waves plus one [P] mask per event — the
    S-stacked eager folds of the batch preemption × completions path
    would otherwise pay a Python iteration per (scenario, wave)."""

    def fold(lo: int, hi: int) -> None:
        r = idx[lo:hi].reshape(-1)
        ch = finals[lo:hi].reshape(-1)
        ok = r >= 0
        assignments[r[ok]] = ch[ok]

    ev_waves = np.nonzero(np.asarray(ev_node) >= 0)[0]
    start = 0
    for w in ev_waves:
        w = int(w)
        fold(start, w)  # waves before the event commit first
        vict = (
            (assignments == int(ev_node[w]))
            & (pod_tier < int(ev_tier[w]))
            & nongang
        )
        if released is not None:
            vict &= ~released
        assignments[vict] = PAD
        start = w
    fold(start, idx.shape[0])


def rebuild_fork_state(pods: EncodedPods, idx: np.ndarray, C: int, outs,
                       wave_times: np.ndarray, upto_chunk: int,
                       reconstruct_released: bool = True,
                       slack: int = 1):
    """Replay saved per-chunk choices for chunks 0..upto_chunk-1 and apply
    the completions an uninterrupted completions-on run would have released
    at each boundary. Returns (host_assign [P], released [P]).

    A release is due at boundary b when the pod was placed in a chunk
    ≤ b−2 (pre-bound pods count as chunk −2, eligible at every boundary)
    and its arrival+duration is at or before the boundary's start time —
    the one-chunk slack that lets the live engines overlap host release
    computation with the in-flight chunk. Shared by JaxReplayEngine.replay
    resume and the what-if fork path (which previously started released
    all-False and re-subtracted every pre-fork release — advisor round-2)."""
    host_assign = np.where(pods.bound_node >= 0, pods.bound_node, PAD).astype(
        np.int32
    )
    chunk_of = np.where(pods.bound_node >= 0, -2, 1 << 30).astype(np.int64)
    rel_time = pods.arrival + np.where(
        np.isfinite(pods.duration), pods.duration, np.inf
    )
    for cj in range(upto_chunk):
        rows = idx[cj * C : (cj + 1) * C]
        ch = np.asarray(outs[cj]).reshape(rows.shape)
        v = rows >= 0
        host_assign[rows[v]] = ch[v]
        chunk_of[rows[v]] = cj
    released = np.zeros(pods.num_pods, bool)
    if reconstruct_released:
        # O(upto_chunk × P) — callers holding a persisted mask skip this.
        for b in range(upto_chunk):
            tb = wave_times[b * C]
            if np.isfinite(tb):
                released |= (
                    (host_assign != PAD)
                    & (chunk_of < b - slack)
                    & np.isfinite(rel_time)
                    & (rel_time <= tb)
                )
    return host_assign, released


def snapshot_carriers(tree) -> list:
    """Host-layout leaf list of a chunk-loop carrier tree (round 15 DCN
    recovery checkpoints). Flattening drops the container structure on
    purpose: the restoring process rebuilds an IDENTICAL fresh carrier
    tree (same engine ctor args, deterministic dict order) and matches
    leaves positionally, so NamedTuple/dataclass containers never need to
    round-trip through the gather payload walker."""
    import jax

    return [
        np.asarray(jax.device_get(leaf))
        for leaf in jax.tree_util.tree_leaves(tree)
    ]


def checkpoint_payload(cursor: int, sig, carriers, outs) -> dict:
    """The one checkpoint-blob payload schema every chunk-loop resume
    path seeds from — claimant recovery (round 15), work-queue steals
    and speculation (round 18), and the durable-journal whole-fleet
    restart (round 20): the loop cursor, the engine signature the
    restorer must match, the host-layout carrier leaves, and the
    per-chunk outputs accumulated so far (host-resident, so the payload
    is device-free and survives pickling into the KV store and the
    filesystem journal alike)."""
    import jax

    return {
        "cursor": int(cursor),
        "sig": list(sig),
        "leaves": snapshot_carriers(carriers),
        "outs": jax.device_get(outs),
    }


def restore_carriers(tree, host_leaves):
    """Inverse of :func:`snapshot_carriers` against a freshly-built
    carrier ``tree`` of identical structure: each host leaf is cast to
    the fresh leaf's dtype and ``device_put`` with the fresh leaf's
    sharding, so the restored tree is layout-identical to one the chunk
    loop produced locally. Raises ValueError on any structural mismatch —
    callers treat that as \"checkpoint unusable\" and re-execute the
    block from chunk 0 (still byte-identical, just slower)."""
    import jax

    flat, treedef = jax.tree_util.tree_flatten(tree)
    if len(flat) != len(host_leaves):
        raise ValueError(
            f"checkpoint carries {len(host_leaves)} leaves but the fresh "
            f"carriers have {len(flat)} — engine modes differ"
        )
    out = []
    for k, (fresh, host) in enumerate(zip(flat, host_leaves)):
        host = np.asarray(host)
        shape = tuple(getattr(fresh, "shape", np.shape(fresh)))
        if shape != tuple(host.shape):
            raise ValueError(
                f"checkpoint leaf {k}: shape {tuple(host.shape)} != fresh "
                f"{shape}"
            )
        dtype = getattr(fresh, "dtype", None)
        if dtype is not None and host.dtype != np.dtype(dtype):
            host = host.astype(dtype)
        if isinstance(fresh, jax.Array):
            host = jax.device_put(host, fresh.sharding)
        out.append(host)
    return jax.tree_util.tree_unflatten(treedef, out)


def compiled_cache_size(fn) -> int:
    """Number of compiled executables a jitted callable holds. The
    serving plane pins this at 1 per pool engine — a warm query must
    never recompile."""
    return int(fn._cache_size())


def rep_slots_for(static3, pods: EncodedPods):
    """(tol_reps, na_reps) PodSlot batches of class representatives. Empty
    gathers when the class path is off — keeps unused (possibly huge)
    constants out of the jitted closures."""
    none = np.zeros(0, np.int32)
    return (
        T.gather_slots(pods, static3.tol_rep if static3.use_tol_classes else none),
        T.gather_slots(pods, static3.na_rep if static3.use_na_classes else none),
    )


class JaxReplayEngine:
    def __init__(
        self,
        ec: EncodedCluster,
        pods: EncodedPods,
        config: Optional[FrameworkConfig] = None,
        wave_width: int = 8,
        chunk_waves: int = 2048,
        dmax_coarse: int = 128,
        preemption=False,
        completions: Optional[bool] = None,
        retry_buffer: int = 0,
        granularity_guard: bool = True,
        lazy_boundary: bool = True,
        double_buffer: bool = True,
        telemetry=None,
        paged: bool = False,
        flight_recorder=None,
    ):
        """The device program is :func:`ops.tpu3.make_wave_step3`
        (domain-space state, wave-deferred commits). ``preemption``:
        ``"tier"``/``True`` = the greedy engines' in-scan tier preemption
        (sim.greedy docstring); ``"kube"`` (round 5) = the EXACT
        kube minimal-victims PostFilter run at chunk boundaries through the
        retry buffer (sim.boundary docstring — the device program is
        unchanged; victims/binds land on the carry as rank-1 plane deltas).
        ``"kube"`` requires ``retry_buffer > 0``.
        ``completions``: chunk-granular pod completions — before each chunk,
        placed pods whose ``arrival + duration`` is at or before the chunk
        start release their resources and count contributions (host-computed
        delta planes subtracted from the carry). Active when the trace has
        finite durations. Works WITH tier ``preemption`` since round 4:
        releases also drop the per-tier planes (pod tiers are static), folds
        run eagerly so eviction events precede the next boundary's release
        decisions, and evicted pods never release (their assignment is PAD
        by the time their boundary arrives); completed pods can no longer
        be evicted. Anchored by
        ``greedy_replay(preemption=True, completions_chunk_waves=...)``.
        ``retry_buffer`` (round 5, task r4-#3): the [K8S] activeQ analogue
        on the single-replay engine — failed non-gang pods re-attempt
        placement at every chunk boundary via the host boundary pass
        (sim.boundary), bit-identical to
        ``greedy_replay(retry_buffer=...)``.
        ``lazy_boundary`` (round 6): quiet chunks — no failures, empty
        retry queue — skip the mirror plane fold entirely and overlap the
        choices fetch with the next chunk's dispatch; only a scalar
        failure count blocks per chunk. Bit-identical to the eager path
        (set False to force the old per-chunk blocking folds).
        ``double_buffer`` (round 10, on top of lazy): stage boundary
        b's RELEASE passes (sim.boundary.boundary_releases) before
        blocking on chunk b−1's failure scalar — the host release
        bookkeeping overlaps device compute instead of serializing after
        the fetch. Exact by the one-chunk slack (the release decision
        never reads chunk b−1); skipped per-boundary when chaos events
        are due or series-level telemetry is sampling. Bit-identical
        results and checkpoint blobs either way (pinned by
        tests/test_double_buffer.py).
        ``telemetry``: granularity knob (str | sim.telemetry.TelemetryConfig
        | None → "summary"). "summary" never changes any device program
        (latency bookkeeping + phase timers only), and neither does any
        other: "series" adds rejection attribution on the host, from the
        CPU framework's own filter chain over a mirror of the program's
        answers (sim.boundary.charge_first_rejects; the boundary mirror in
        retry/kube modes, one kept beside the chunk loop on the plain
        path), plus boundary-sampled depth series; "timeline" adds the
        event log for the Chrome-trace export. "off" disables everything
        (``ReplayResult.telemetry`` is None).
        ``flight_recorder`` (round 16): None (default, off), a JSONL path,
        or a :class:`sim.flight.FlightRecorderConfig` — streams one
        in-flight event per chunk boundary (sim.flight docstring).
        Bit-parity pinned: placements, deterministic JSONL and checkpoint
        blobs are identical with the recorder on or off
        (tests/test_flight.py)."""
        from ..ops import tpu3 as V3
        from .greedy import normalize_preemption

        mode = normalize_preemption(preemption)
        if mode == "tier" and retry_buffer:
            raise ValueError(
                "retry_buffer is not supported with tier preemption"
            )
        if mode == "kube" and not retry_buffer:
            raise ValueError(
                "preemption='kube' requires retry_buffer > 0 (failed pods "
                "reach the PostFilter through the boundary retry pass)"
            )
        # Round 14 big-scenario mode: stream pod pages host->device (paged)
        # instead of keeping the whole trace resident.
        self.paged = bool(paged)
        if self.paged and (mode == "kube" or retry_buffer):
            raise ValueError(
                "paged=True is not supported with retry_buffer / "
                "preemption='kube' yet — the boundary mirror pre-stages the "
                "whole wave index tensor; run paged replays on the plain path"
            )
        self.ec = ec
        self.pods = pods
        self.spec = StepSpec.from_config(ec, config, pods)
        self._config = config
        self.chunk_waves = chunk_waves
        # The one device engine: what the flight recorder's meta and the
        # JSONL rows stamp.
        self.engine = "v3"
        self.dmax_coarse = dmax_coarse
        # self.preemption stays the TIER flag (the in-scan feature the
        # compiled program and the what-if collect paths key off).
        self.preemption = mode == "tier"
        self.kube = mode == "kube"
        self.retry_buffer = int(retry_buffer)
        self.lazy_boundary = bool(lazy_boundary)
        self._replay_calls = 0  # ordinal of the next replay()'s root span
        self.double_buffer = bool(double_buffer)
        self.completions = completions
        self.granularity_guard = granularity_guard
        self.telemetry_cfg = TelemetryConfig.resolve(telemetry)
        # Flight recorder (round 16): validate the spec up front (a bad
        # path string should fail at construction, not mid-replay); each
        # replay() opens its own stream from it.
        from .flight import FlightRecorder, FlightRecorderConfig

        self.flight_recorder = (
            flight_recorder
            if isinstance(flight_recorder, FlightRecorder)
            else FlightRecorderConfig.resolve(flight_recorder)
        )
        # Replicated-residency refusal (Borg-scale guard): with a per-device
        # byte budget set, a replicated run whose single-scenario planes
        # exceed it is refused UP FRONT with the fix spelled out, instead of
        # dying in an opaque device OOM mid-replay.
        import os

        budget = os.environ.get("KSIM_MAX_REPLICATED_BYTES")
        if budget:
            est = replicated_resident_bytes(
                ec, pods, pods_resident=not self.paged
            )
            if est > int(budget):
                raise ValueError(
                    f"replicated single-scenario residency ~{est / 2**20:.0f} "
                    f"MiB/device exceeds KSIM_MAX_REPLICATED_BYTES "
                    f"({int(budget) / 2**20:.0f} MiB): stream pod pages "
                    "(paged=True) instead of keeping the trace resident"
                )
        self.dc = T.DevCluster.from_encoded(ec)
        # "auto": measured optimum is W=8 across shapes (W=16 loses to the
        # W² in-wave coupling even on coarse-only traces) — kept as a
        # resolution point for when the kernel cost model changes.
        if wave_width == "auto":
            wave_width = 8
        self.wave_width = wave_width
        self.static3 = V3.V3Static.build(
            ec, pods, self.spec, dmax_coarse, preemption=self.preemption,
            wave_width=wave_width,
        )
        self.shared3 = V3.Shared3.build(ec, self.static3)
        self.chunk_fn = make_chunk_fn3_src(
            self.static3, self.shared3, rep_slots_for(self.static3, pods),
            wave_width, self.spec,
        )
        # A pod group wider than the wave runs on the plain arrivals-only
        # replay (sim.waves.WIDE_GANG_UNSUPPORTED).
        refuse_wide_gangs(
            wave_width, widest_gang(pods),
            retry_buffer=bool(self.retry_buffer), kube_preemption=self.kube,
        )
        self.waves = pack_waves(
            pods, wave_width,
            page_pods=(chunk_waves * wave_width if self.paged else None),
        )
        # Slot data lives on device once; chunks gather rows inside jit
        # (ops.tpu.SlotSource) — only wave indices cross the host boundary.
        # Paged mode keeps slots on host and streams per-chunk pages
        # instead (SlotSource.page).
        self._slot_src = None if self.paged else T.SlotSource.build(pods)
        self._extra_src = (
            None
            if self.paged
            else V3.ExtraSource.build(self.static3, pods.num_pods)
        )

    @property
    def _wide_gangs(self) -> bool:
        """The trace has a pod group wider than the wave: the state carries
        its transaction (``ops.tpu3.GangTxn``)."""
        return self.static3.has_wide_gangs

    def _init_dev_state(self):
        from ..ops import tpu3 as V3

        host = init_state(self.ec, self.pods)  # applies pre-bound pods
        self._Dhost = host.match_count.shape[1]
        return V3.DevState3.from_host(
            host.used, host.match_count, host.anti_active, host.pref_wsum,
            self.ec, self.static3, ep=self.pods,
        )

    def _open_recorder(self):
        """(recorder, owns) for this replay: a fresh stream per replay()
        from the configured spec (owns=True → this replay closes it), or
        a live shared recorder passed in by the caller (owns=False), or
        (None, False) — the default, recorder off."""
        from .flight import FlightRecorder, FlightRecorderConfig

        # Re-resolve here (not just in __init__): callers may assign a
        # raw path onto .flight_recorder between replays.
        spec = FlightRecorderConfig.resolve(self.flight_recorder)
        if spec is None:
            return None, False
        if isinstance(spec, FlightRecorder):
            return spec, False
        meta = {
            "nodes": int(self.ec.num_nodes),
            "pods": int(self.pods.num_pods),
            "paged": bool(self.paged),
            "engine": self.engine,
            "chunk_waves": int(self.chunk_waves),
            "resident_bytes": int(
                replicated_resident_bytes(
                    self.ec, self.pods, pods_resident=not self.paged
                )
            ),
        }
        self._last_flight = FlightRecorder(spec, meta=meta)
        return self._last_flight, True

    def _save_checkpoint(self, state, cursor: int, all_choices, path: str,
                         released=None, boundary=None) -> None:
        from .checkpoint import ReplayCheckpoint

        used, mc, aa, pw = state.to_host(self.ec, self.static3, self._Dhost)
        ReplayCheckpoint(
            used=used, match_count=mc, anti_active=aa, pref_wsum=pw,
            chunk_cursor=cursor, outs=[np.asarray(o) for o in all_choices],
            released=released, boundary=boundary,
        ).save(path)

    def _preemption_walk(self, idx: np.ndarray, finals: np.ndarray,
                         ev_node: np.ndarray, ev_tier: np.ndarray):
        ep = self.pods
        assignments = np.where(ep.bound_node >= 0, ep.bound_node, PAD).astype(np.int32)
        preemption_walk(
            assignments, idx, finals, ev_node, ev_tier,
            self.static3.pod_tier, ep.group_id == PAD,
        )
        scheduled = ep.bound_node == PAD
        placed = int((assignments[scheduled] >= 0).sum())
        return assignments, placed

    def _apply_release(self, state, rel_idx: np.ndarray, rel_nodes: np.ndarray):
        """Subtract the completed pods' aggregate contribution (resources +
        count planes) from the carried device state — the device twin of
        models.state.unbind, applied at a chunk boundary."""
        from ..models.state import release_delta
        from ..ops import tpu3 as V3

        used_d, mc_d, aa_d, pw_d = release_delta(
            self.ec, self.pods, rel_idx, rel_nodes
        )
        delta = V3.DevState3.from_host(
            used_d, mc_d, aa_d, pw_d, self.ec, self.static3
        )
        if self.preemption and len(rel_idx):
            # Tier planes drop completed pods too (pod tiers are
            # static, so releases ARE attributable — the former
            # exclusivity only held for evicted pods, which never
            # release because their assignment is PAD by walk time).
            # NON-GANG ONLY: the tier planes never accumulate gang
            # pods (gangs are not evictable — the wave step and
            # from_host both gate on group_id == PAD), so a gang
            # completion must not be subtracted from them either.
            st3 = self.static3
            ng = self.pods.group_id[rel_idx] == PAD
            ng_idx = np.asarray(rel_idx)[ng]
            ng_nodes = np.asarray(rel_nodes)[ng]
            R, N = self.ec.num_resources, self.ec.num_nodes
            ut = np.zeros((st3.Tt, R, N), np.float32)
            nt = np.zeros((st3.Tt, N), np.float32)
            if ng_idx.size:
                t_arr = st3.pod_tier[ng_idx]
                np.add.at(nt, (t_arr, ng_nodes), 1.0)
                np.add.at(
                    ut,
                    (
                        t_arr[:, None],
                        np.arange(R)[None, :],
                        ng_nodes[:, None],
                    ),
                    self.pods.requests[ng_idx],
                )
            delta = delta._replace(
                used_tier=jnp.asarray(ut), npods_tier=jnp.asarray(nt)
            )
        return self._donated_subtract(state, delta)

    def _donated_subtract(self, state, delta):
        """Subtract a delta tree from the carried state with the STATE
        buffers donated (round 11 donation audit): the eager
        ``jax.tree.map(jnp.subtract, ...)`` the release/boundary paths
        used allocated a second full state copy per boundary. Cached on
        the engine — jit caches by function identity."""
        return self._release_jit()(state, delta)

    def _release_jit(self):
        if getattr(self, "_sub_jit", None) is None:
            def release_subtract(s, d):
                with stage("ksim.release"):
                    return jax.tree.map(jnp.subtract, s, d)

            self._sub_jit = jax.jit(release_subtract, donate_argnums=(0,))
        return self._sub_jit

    def _program_forms(self) -> dict:
        """The static forms the resident chunk program was built with, as
        the collector's keywords: the in-wave usage corrections and the
        select (ops.tpu3)."""
        from ..ops import tpu3 as V3

        return {
            "inwave_corrections": V3.inwave_corrections(self.static3),
            "select_form": V3.select_form(
                self.static3, self.spec, self.ec.num_nodes
            ),
        }

    def _register_programs(self, state, idx_chunks, release: bool) -> None:
        """With profiling armed: hand the resident v3 chunk program this
        replay is about to call (and its release program) to
        ``utils.profiling.stage_tables``, by XLA module name, as thunks
        over the calls' shapes. Nothing is lowered here."""
        if not idx_chunks or not profiling_active():
            return
        chunk_fn = self.chunk_fn
        args = shape_structs(
            (self.dc, state, self._slot_src, self._extra_src, idx_chunks[0])
        )
        register_program(
            f"jit_{chunk_fn.__name__}", lambda: chunk_fn.lower(*args)
        )
        if release:
            sub, st = self._release_jit(), args[1]
            register_program(
                f"jit_{sub.__name__}", lambda: sub.lower(st, st)
            )

    def _apply_boundary_delta(self, state, sub_pairs, add_pairs):
        """Net host-layout plane delta of one boundary pass — releases and
        evictions (``sub_pairs``) minus retried/preempting binds
        (``add_pairs``), each a (pods, nodes) int-array pair — transformed
        to the device layout and subtracted from the carry. The
        generalization of :meth:`_apply_release`; the transform is linear,
        so one application carries the whole pass."""
        from ..models.state import release_delta
        from ..ops import tpu3 as V3

        s_idx, s_nodes = sub_pairs
        a_idx, a_nodes = add_pairs
        du, dmc, daa, dpw = release_delta(self.ec, self.pods, s_idx, s_nodes)
        au, amc, aaa, apw = release_delta(self.ec, self.pods, a_idx, a_nodes)
        net = (du - au, dmc - amc, daa - aaa, dpw - apw)
        delta = V3.DevState3.from_host(*net, self.ec, self.static3)
        return self._donated_subtract(state, delta)

    def _state_from_checkpoint(self, ck):
        """Device carry from a ReplayCheckpoint (shared by the plain and
        boundary resume paths)."""
        from ..ops import tpu3 as V3

        return V3.DevState3.from_host(
            ck.used, ck.match_count, ck.anti_active, ck.pref_wsum,
            self.ec, self.static3,
        )

    def _replay_boundary(
        self, _tick, node_events=None, chunk_req: Optional[int] = None,
        retry_req: Optional[int] = None,
        checkpoint_path: Optional[str] = None, checkpoint_every: int = 0,
        resume: bool = False, budget=None,
    ) -> ReplayResult:
        """Replay with the host boundary pass active (``retry_buffer`` > 0
        and/or ``preemption='kube'``; :mod:`.boundary`).

        Lazy sync (round 6, default): the boundary pass at b only needs
        the mirror current through chunk b−1 when it will actually READ
        it — i.e. when the retry queue is non-empty. Per chunk the loop
        fetches ONE device scalar (the non-gang failure count); quiet
        chunks (zero failures, empty queue) skip the blocking choices
        fetch entirely — the fold is deferred past the next chunk's
        dispatch (bookkeeping lags one chunk; the plane delta is only
        appended to the mirror's op log and applied if a later boundary
        flushes). The static-release decision at boundary b never needs
        chunk b−1 (one-chunk slack: ``bind_chunk < b-1``), so deferral is
        exact. Eager mode (``lazy_boundary=False``) folds every chunk with
        a blocking fetch — bit-identical results, kept as the reference
        path. The device chunk program is the plain one either way: retry
        placements and kube preemption decisions are host arithmetic
        (bit-identical to the CPU path by construction) landing on the
        carry as rank-1 plane deltas."""
        from dataclasses import replace as dc_replace

        from ..framework.framework import FrameworkConfig, SchedulerFramework
        from .boundary import BoundaryOps

        idx = self.waves.idx
        # (chunk_req, retry_req) arrive guard-adjusted from replay() —
        # the single guard call site.
        chunk_req = self.chunk_waves if chunk_req is None else chunk_req
        retry_req = self.retry_buffer if retry_req is None else retry_req
        C = min(chunk_req, max(idx.shape[0], 1))
        pad_to = ((idx.shape[0] + C - 1) // C) * C
        if pad_to != idx.shape[0]:
            idx = np.concatenate(
                [idx, np.full((pad_to - idx.shape[0], idx.shape[1]), PAD, np.int32)]
            )
        cfg = dc_replace(
            self._config if self._config is not None else FrameworkConfig(),
            enable_preemption=self.kube,
        )
        fw = SchedulerFramework(self.ec, self.pods, cfg)
        lazy = self.lazy_boundary
        tel = (
            TelemetryCollector(
                self.telemetry_cfg, chunk_waves=C,
                **self._program_forms(),
            )
            if self.telemetry_cfg.enabled
            else None
        )
        # Flight recorder (round 16): same contract as the plain path —
        # host-side observation only, parity-pinned against recorder-off.
        rec, rec_own = self._open_recorder()
        _tick.timers = getattr(tel if tel is not None else rec, "phases", None)
        with _tick("stage"):
            bops = BoundaryOps(
                self.ec, self.pods, fw,
                WaveBatch(idx=idx, wave_width=self.wave_width),
                self.wave_width, C,
                retry_buffer=retry_req, kube=self.kube, lazy=lazy,
                telemetry=tel,
            )
            self._last_bops = bops  # probe for the quiet-path tests/bench
            if budget is not None:
                bops.set_budget(budget)
            state = self._init_dev_state()
            pending_events = sorted(node_events or [], key=lambda e: e.time)
            ev_hash = events_hash(pending_events)
            ev_applied = 0  # checkpoint event cursor
            saved_alloc = np.asarray(self.dc.allocatable).copy()
            saved_alloc_ec = self.ec.allocatable.copy()
            start_chunk = 0
            if resume and checkpoint_path:
                from .checkpoint import ReplayCheckpoint

                ck = ReplayCheckpoint.load(checkpoint_path)
                if ck.boundary is None:
                    raise ValueError(
                        "checkpoint was not written by a boundary-mode "
                        "(retry/kube) replay — resume it on a plain engine"
                    )
                ck_hash = ck.boundary.get("ev_hash")
                if ck_hash is not None and not np.array_equal(
                    np.asarray(ck_hash, np.uint8), ev_hash
                ):
                    raise ValueError(
                        "checkpoint was written under a different node_events "
                        "timeline — resuming would re-apply or skip events "
                        "(evictions are not idempotent); pass the original "
                        "event list or restart the replay from scratch"
                    )
                state = self._state_from_checkpoint(ck)
                bops.restore(
                    ck.boundary, ck.used, ck.match_count, ck.anti_active,
                    ck.pref_wsum,
                )
                start_chunk = ck.chunk_cursor
                cur = ck.boundary.get("ev_cursor")
                if cur is not None and int(np.asarray(cur).reshape(-1)[0]):
                    # Catch-up: past events re-shape allocatable (the device
                    # cluster starts unperturbed) WITHOUT re-evicting — the
                    # restored mirror already reflects their evictions.
                    ev_applied = int(np.asarray(cur).reshape(-1)[0])
                    done = pending_events[:ev_applied]
                    self._apply_node_events(done, saved_alloc)
                    for ev in done:
                        if ev.kind == "node_down":
                            self.ec.allocatable[ev.node] = 0.0
                        elif ev.kind == "node_up":
                            self.ec.allocatable[ev.node] = saved_alloc_ec[ev.node]
                        elif ev.kind == "capacity_scale":
                            self.ec.allocatable[ev.node] = (
                                saved_alloc_ec[ev.node] * ev.scale
                            )
                    pending_events = pending_events[ev_applied:]
            wave_times = self._wave_start_times(idx)
            idx_chunks = [
                jnp.asarray(idx[c0 : c0 + C]) for c0 in range(0, idx.shape[0], C)
            ]
            self._register_programs(state, idx_chunks, True)
            # Scalar boundary summary: count of failed NON-GANG slots (the only
            # failures that enter the retry buffer — gang failures never do).
            if not hasattr(self, "_bfail_fn"):
                self._bfail_fn = jax.jit(
                    lambda ch, ix, ng: (
                        (ix >= 0)
                        & (ch.reshape(ix.shape) < 0)
                        & ng[jnp.clip(ix, 0)]
                    ).sum(dtype=jnp.int32)
                )
            ng_dev = jnp.asarray(self.pods.group_id == PAD)
            # Deferred fold of the previous chunk: (ci, rows, choices_dev,
            # nfail_dev). Resolved eagerly when the boundary will read the
            # mirror planes; otherwise folded AFTER the next dispatch so the
            # D2H copy overlaps device compute.
            pending = None

            def _fold_pending():
                nonlocal pending
                if pending is not None:
                    ci_p, rows_p, ch_d, _nf = pending
                    t_f = time.perf_counter()
                    with _tick("device_wait"):
                        ch_np = np.asarray(ch_d)
                    with _tick("boundary_fold"):
                        bops.fold_chunk(ci_p, rows_p, ch_np)
                    if rec is not None:
                        rec.fold(ci_p, time.perf_counter() - t_f)
                    pending = None

            dbuf = self.double_buffer and lazy
            rec_valid = (
                np.add.accumulate(
                    [
                        int((idx[c0 : c0 + C] >= 0).sum())
                        for c0 in range(0, idx.shape[0], C)
                    ]
                )
                if rec is not None
                else None
            )
            rec_pub = None
            rec_retry = None
            if rec is not None:
                from ..parallel import dcn as _dcn

                rec_pub = _dcn.publish_stats()
                rec_retry = _dcn.retry_stats()
        t0 = time.perf_counter()
        try:
            for ci, c0 in enumerate(range(0, idx.shape[0], C)):
                if ci < start_chunk:
                    continue
                rel_staged = None
                if (
                    dbuf
                    and pending is not None
                    and not (tel is not None and tel.cfg.want_series)
                    and not (
                        pending_events
                        and pending_events[0].time <= wave_times[c0]
                    )
                    and budget is None
                ):
                    # Double-buffer (round 10): run boundary ci's RELEASE
                    # passes before blocking on chunk ci-1's failure
                    # scalar — the device is still computing, so this
                    # host bookkeeping is free. Exact: the release
                    # decision reads only chunks ≤ ci−2 (one-chunk
                    # slack), and the op-log's key sort restores eager
                    # flush order. Skipped when chaos events are due at
                    # this boundary (eviction must precede the release
                    # decision) or series telemetry samples (its depth
                    # series reads post-fold state).
                    with _tick("boundary_fold"):
                        rel_staged = bops.boundary_releases(
                            ci, wave_times[c0]
                        )
                if pending is not None and (
                    int(pending[3]) > 0
                    or bops.retry_q
                    or (tel is not None and tel.cfg.want_series)
                ):
                    # The boundary below will run the retry pass (new
                    # failures or a carried-over queue): it needs chunk
                    # ci-1 folded and the mirror planes flushed. Series
                    # telemetry also forces the fold — the boundary's
                    # utilization sample reads the mirror's committed
                    # planes, and a quiet lazy chunk would leave them one
                    # chunk stale.
                    _fold_pending()
                chaos_p: List[np.ndarray] = []
                chaos_n: List[np.ndarray] = []
                if budget is not None:
                    # A drain under budgets: what leaves, and when a node
                    # goes out and is back, follows from the mirror's own
                    # state at every boundary, not from the events alone.
                    chunk_t = wave_times[c0]
                    due = [e for e in pending_events if e.time <= chunk_t]
                    if due or bops.closed.any():
                        _fold_pending()
                        for cp, cn in bops.budget_events(ci, float(chunk_t), due):
                            chaos_p.append(cp)
                            chaos_n.append(cn)
                        alloc = np.where(
                            bops.closed[:, None], 0.0, saved_alloc
                        ).astype(saved_alloc.dtype)
                        self.dc = self.dc._replace(allocatable=jnp.asarray(alloc))
                        self.ec.allocatable[:] = np.where(
                            bops.closed[:, None], 0.0, saved_alloc_ec
                        )
                    pending_events = pending_events[len(due):]
                    ev_applied += len(due)
                elif pending_events:
                    chunk_t = wave_times[c0]
                    due = [e for e in pending_events if e.time <= chunk_t]
                    if due:
                        if any(e.kind == "node_down" for e in due):
                            # NoExecute eviction reads the mirror's bound
                            # state — it must be current through chunk
                            # ci-1 (quiet lazy chunks may not be yet).
                            _fold_pending()
                        self._apply_node_events(due, saved_alloc)
                        if tel is not None and tel.cfg.want_timeline:
                            for ev in due:
                                if ev.kind in ("node_down", "node_up"):
                                    tel.event(
                                        ev.kind, float(ev.time), -1, int(ev.node)
                                    )
                        # The host mirror's plugins read ec.allocatable
                        # live — keep it in lockstep with the device copy.
                        for ev in due:
                            if ev.kind == "node_down":
                                self.ec.allocatable[ev.node] = 0.0
                                # NoExecute: evict the node's bound pods
                                # through the mirror (they re-enter the
                                # retry buffer and are re-attempted in
                                # THIS boundary's retry pass, like the
                                # CPU engine's requeue-at-event-instant).
                                cp, cn = bops.evict_node(
                                    ev.node, ci, float(chunk_t)
                                )
                                if cp.size:
                                    chaos_p.append(cp)
                                    chaos_n.append(cn)
                            elif ev.kind == "node_up":
                                self.ec.allocatable[ev.node] = saved_alloc_ec[ev.node]
                            elif ev.kind == "capacity_scale":
                                self.ec.allocatable[ev.node] = (
                                    saved_alloc_ec[ev.node] * ev.scale
                                )
                        pending_events = pending_events[len(due):]
                        ev_applied += len(due)
                with _tick("boundary_fold"):
                    if rel_staged is not None:
                        rel = rel_staged
                        binds, evicts = bops.boundary_retry(
                            ci, wave_times[c0]
                        )
                    else:
                        rel, binds, evicts = bops.boundary(
                            ci, wave_times[c0]
                        )
                if (
                    rel[0].size or binds[0].size or evicts[0].size or chaos_p
                ):
                    with _tick("host_mirror"):
                        state = self._apply_boundary_delta(
                            state,
                            (
                                np.concatenate([rel[0], evicts[0], *chaos_p]),
                                np.concatenate([rel[1], evicts[1], *chaos_n]),
                            ),
                            binds,
                        )
                with _tick("dispatch"), _tick.mark(f"chunk:{ci}"):
                    state, choices = self.chunk_fn(
                        self.dc, state, self._slot_src, self._extra_src,
                        idx_chunks[ci],
                    )
                if lazy:
                    nf_d = self._bfail_fn(choices, idx_chunks[ci], ng_dev)
                    if hasattr(choices, "copy_to_host_async"):
                        choices.copy_to_host_async()
                    # Quiet previous chunk: fold it now — its D2H copy was
                    # launched an iteration ago and chunk ci is already in
                    # flight, so this host work overlaps device compute.
                    _fold_pending()
                    pending = (ci, idx[c0 : c0 + C], choices, nf_d)
                else:
                    # Eager fold: one blocking fetch per chunk. (The
                    # choices buffer is fully consumed here — the mirror
                    # carries the placements, so checkpoints save NO outs.)
                    t_f = time.perf_counter()
                    with _tick("device_wait"):
                        ch_np = np.asarray(choices)
                    with _tick("boundary_fold"):
                        bops.fold_chunk(ci, idx[c0 : c0 + C], ch_np)
                    if rec is not None:
                        rec.fold(ci, time.perf_counter() - t_f)
                if (
                    checkpoint_path
                    and checkpoint_every
                    and (ci + 1) % checkpoint_every == 0
                ):
                    # Blob parity with the eager path: the mirror's
                    # bookkeeping must be current through chunk ci.
                    _fold_pending()
                    blob = bops.to_blob()
                    # Applied-event cursor + timeline hash: a resume must
                    # neither re-apply past events (evictions are not
                    # idempotent) nor skip future ones, and must reject a
                    # different event list outright.
                    blob["ev_cursor"] = np.asarray([ev_applied], np.int64)
                    blob["ev_hash"] = ev_hash
                    t_ck = time.perf_counter()
                    self._save_checkpoint(
                        state, ci + 1, [], checkpoint_path,
                        released=bops.released, boundary=blob,
                    )
                    if rec is not None:
                        rec.checkpoint(
                            ci + 1, _file_bytes(checkpoint_path),
                            time.perf_counter() - t_ck,
                        )
                if rec is not None:
                    pub_now = _dcn.publish_stats()
                    ck_pub = None
                    if pub_now != rec_pub:
                        ck_pub = {
                            "count": pub_now["count"] - rec_pub["count"],
                            "wall_s": round(
                                pub_now["wall_s"] - rec_pub["wall_s"], 6
                            ),
                            "bytes": pub_now["bytes"] - rec_pub["bytes"],
                        }
                        rec_pub = pub_now
                    retry_now = _dcn.retry_stats()
                    kv_retry = None
                    if retry_now != rec_retry:
                        kv_retry = {
                            "retries": retry_now["retries"]
                            - rec_retry["retries"],
                            "giveups": retry_now["giveups"]
                            - rec_retry["giveups"],
                            "backoff_s": round(
                                retry_now["backoff_s"]
                                - rec_retry["backoff_s"], 6
                            ),
                        }
                        rec_retry = retry_now
                    rec.chunk(
                        ci,
                        t_virtual=wave_times[c0],
                        dispatched=int(rec_valid[ci]),
                        # Mirror bookkeeping lags one chunk under lazy —
                        # a liveness gauge, not the parity-bearing count.
                        placed=int(bops.placed_total),
                        phase_acc=(
                            tel.phases.acc
                            if tel is not None
                            else rec.phases.acc
                        ),
                        ckpt_publish=ck_pub,
                        kv_retry=kv_retry,
                    )
            _fold_pending()
            if self.kube:
                # Trailing boundary (greedy anchor twin): last-chunk
                # failures still get their PostFilter attempt.
                rel, binds, evicts = bops.boundary(idx.shape[0] // C, np.inf)
                if rel[0].size or binds[0].size or evicts[0].size:
                    state = self._apply_boundary_delta(
                        state,
                        (
                            np.concatenate([rel[0], evicts[0]]),
                            np.concatenate([rel[1], evicts[1]]),
                        ),
                        binds,
                    )
                    jax.block_until_ready(state)
        finally:
            if node_events:
                self.dc = self.dc._replace(allocatable=jnp.asarray(saved_alloc))
                self.ec.allocatable[:] = saved_alloc_ec
        wall = time.perf_counter() - t0

        with _tick("gather"):
            to_schedule = int((idx >= 0).sum())
            assignments = bops.assignments
            placed = bops.placed_total
            used, mc, aa, pw = state.to_host(self.ec, self.static3, self._Dhost)
            util = utilization_means(used, self.ec.allocatable, self.ec.vocab._r)
            pending_m = (self.pods.bound_node == PAD) & (assignments == PAD)
            frag = fragmentation_gauges(
                self.ec.allocatable, used, self.pods.requests[pending_m],
                self.ec.vocab._r,
            )
            host_state = SchedState(
                used=used, match_count=mc, anti_active=aa, pref_wsum=pw,
                bound=assignments.copy(),
            )
            if rec is not None and rec_own:
                rec.close({"placed": int(placed)})
        return ReplayResult(
            assignments=assignments,
            placed=placed,
            unschedulable=to_schedule - placed,
            preemptions=bops.preemptions,
            attempts=to_schedule,
            wall_clock_s=wall,
            placements_per_sec=placed / wall if wall > 0 else 0.0,
            virtual_makespan=float(self.pods.arrival.max()) if self.pods.num_pods else 0.0,
            utilization=util,
            state=host_state,
            retry_dropped=bops.retry_dropped,
            evictions=bops.evictions,
            evict_rescheduled=bops.evict_rescheduled,
            evict_stranded=bops.evict_stranded,
            evict_latency_mean=bops.evict_latency_mean,
            fragmentation=frag,
            telemetry=tel.result() if tel is not None else None,
        )

    def _wave_start_times(self, idx: np.ndarray) -> np.ndarray:
        """Arrival time of each wave's first valid pod (for timed events)."""
        return wave_start_times(self.pods, idx)

    def _apply_node_events(self, events, saved_alloc: np.ndarray) -> None:
        """Mutate the device cluster's allocatable rows (failure
        injection). Capacity changes affect future placements; on the
        boundary path (``retry_buffer``/``kube``) the caller ALSO evicts
        ``node_down`` victims through the host mirror with NoExecute
        semantics (``BoundaryOps.evict_node``), matching the CPU event
        engine. The plain path keeps the capacity-only semantics — no
        mirror exists to requeue victims through."""
        alloc = np.asarray(self.dc.allocatable).copy()
        for ev in events:
            if ev.kind in ("node_down", "node_cordon"):
                alloc[ev.node] = 0.0
            elif ev.kind == "node_up":
                alloc[ev.node] = saved_alloc[ev.node]
            elif ev.kind == "capacity_scale":
                alloc[ev.node] = saved_alloc[ev.node] * ev.scale
        self.dc = self.dc._replace(allocatable=jnp.asarray(alloc))

    def replay(
        self,
        checkpoint_path: Optional[str] = None,
        checkpoint_every: int = 0,
        resume: bool = False,
        node_events=None,
        budget=None,
    ) -> ReplayResult:
        """Run the replay; optionally snapshot the carry every K chunks to
        ``checkpoint_path`` and/or resume from it (SURVEY.md §5).

        ``node_events`` (list of sim.runtime.NodeEvent) are applied at chunk
        boundaries: an event fires before the first chunk whose start wave's
        arrival time is past the event time (granularity = chunk_waves; use
        smaller chunks for finer timing). With ``retry_buffer``/``kube``
        active, ``node_down`` additionally evicts bound pods (NoExecute)
        through the boundary mirror; without a retry buffer only future
        placements are affected. ``budget`` (a
        ``sim.runtime.DisruptionBudget``; needs the retry buffer) makes the
        timeline a maintenance drain: a ``node_cordon`` closes a node, its
        tasks leave as the budgets allow and the node goes out when it is
        empty or at its deadline (``BoundaryOps.budget_events``, the host
        twin of the what-if eviction program).

        With profiling armed the whole call lies under one root span
        ``replay:<n>``, ``n`` this engine's call ordinal: the phases are
        its children on the calling thread, and what lies under the root
        alone is host work no span names."""
        # The call's ONE look at KSIM_PROFILE_DIR; the phase timers are bound
        # once telemetry is known. The body stays in this function: one more
        # Python frame between the caller and the jitted calls makes every
        # lowering a quarter slower (PERF.md §6, PR 35).
        _tick = make_span()
        n, self._replay_calls = self._replay_calls, self._replay_calls + 1
        with _tick.mark(f"replay:{n}"):
            from .checkpoint import ReplayCheckpoint

            validate_node_events(node_events, self.ec.num_nodes)
            if budget is not None:
                if not self.retry_buffer or self.kube:
                    raise ValueError(
                        "a disruption budget requires retry_buffer > 0 and "
                        "no kube preemption (the evicted re-enter the queue)"
                    )
                if checkpoint_path or resume or any(
                    e.kind == "capacity_scale" for e in node_events or []
                ):
                    raise ValueError(
                        "a disruption budget runs without checkpoints and "
                        "without capacity_scale events"
                    )
            elif any(e.kind == "node_cordon" for e in node_events or []) and (
                self.retry_buffer or self.kube
            ):
                raise ValueError(
                    "node_cordon on the boundary path is the start of a "
                    "budgeted drain: pass budget= (sim.runtime."
                    "DisruptionBudget)"
                )
            if self.preemption and (checkpoint_path or resume):
                raise ValueError(
                    "checkpoint/resume is not supported with device preemption "
                    "(tier planes are not checkpointed)"
                )
            if self.retry_buffer or self.kube:
                if self.completions is False:
                    raise ValueError(
                        "completions=False is not supported with retry_buffer/"
                        "kube preemption (the boundary pass owns releases)"
                    )
            # Granularity-envelope guard (round 5, VERDICT r4 #2; see
            # sim.granularity) — ONE call site for every replay path; no-op
            # for duration-free traces, shapes inside the measured-safe
            # regime, and explicit completions=False (which the boundary
            # modes reject above).
            chunk_req, retry_req = self.chunk_waves, self.retry_buffer
            if self.completions is not False:
                from .granularity import guard as _gran_guard

                chunk_req, retry_req = _gran_guard(
                    self.pods, self.waves.idx, chunk_req, retry_req,
                    enabled=self.granularity_guard,
                    engine_name="jax replay engine",
                )
            if self.retry_buffer or self.kube:
                return self._replay_boundary(
                    _tick, node_events=node_events, chunk_req=chunk_req,
                    retry_req=retry_req, checkpoint_path=checkpoint_path,
                    checkpoint_every=checkpoint_every, resume=resume,
                    budget=budget,
                )
            if (
                node_events
                and (self.static3.mc_h_bf16 or self.static3.anti_h_bf16)
                and any(e.kind == "capacity_scale" for e in node_events)
            ):
                # Capacity scaling can push per-node pod counts past the bf16
                # exactness bound baked into the kernel — rebuild without it.
                from ..ops import tpu3 as V3

                self.static3 = V3.V3Static.build(
                    self.ec, self.pods, self.spec, self.dmax_coarse,
                    preemption=self.preemption, allow_bf16_host=False,
                    wave_width=self.wave_width,
                )
                self.shared3 = V3.Shared3.build(self.ec, self.static3)
                self.chunk_fn = make_chunk_fn3_src(
                    self.static3, self.shared3,
                    rep_slots_for(self.static3, self.pods),
                    self.wave_width, self.spec,
                )
                # Keep the device-resident per-pod rows in lockstep with the
                # rebuilt static tables (value-identical today, but a silent
                # desync trap if V3Static ever derives them from a rebuild
                # parameter).
                self._extra_src = V3.ExtraSource.build(
                    self.static3, self.pods.num_pods
                )

            idx = self.waves.idx
            C = min(chunk_req, max(idx.shape[0], 1))
            pad_to = ((idx.shape[0] + C - 1) // C) * C
            if pad_to != idx.shape[0]:
                idx = np.concatenate(
                    [idx, np.full((pad_to - idx.shape[0], idx.shape[1]), PAD, np.int32)]
                )
            from ..ops import tpu3 as V3
            from ..utils.metrics import log

            tel = (
                TelemetryCollector(
                    self.telemetry_cfg, chunk_waves=C,
                    **self._program_forms(),
                )
                if self.telemetry_cfg.enabled
                else None
            )
            # Flight recorder (round 16): pure host-side observation — with
            # telemetry off it owns the phase timers, so recorder rows still
            # carry PHASE_NAMES deltas without a collector. Nothing below
            # changes a device program, a fold order or a checkpoint payload.
            rec, rec_own = self._open_recorder()
            _tick.timers = getattr(tel if tel is not None else rec, "phases", None)
            with _tick("stage"):
                # First-reject attribution (series+) happens on the host, on a
                # mirror of the program's answers kept beside the chunk loop
                # (sim.boundary.fold_answers: the boundary path's rule). The
                # device program is the one every granularity runs; series
                # costs one blocking fetch per chunk.
                attribute = tel is not None and tel.cfg.want_series
                if attribute and self._wide_gangs:
                    log.info(
                        "telemetry: rejection attribution is not available with "
                        "a pod group wider than the wave (a member's answer is "
                        "final only at the end of its group's transaction) — "
                        "latency/phase telemetry still collected"
                    )
                    attribute = False
                if attribute and self.preemption:
                    log.info(
                        "telemetry: rejection attribution is not available with "
                        "in-scan tier preemption (the host mirror cannot follow "
                        "the scan's evictions) — latency/phase telemetry still "
                        "collected"
                    )
                    attribute = False
                if attribute and (checkpoint_path or resume):
                    log.info(
                        "telemetry: rejection attribution is disabled under "
                        "checkpoint/resume (the host mirror is not part of "
                        "checkpoints) — latency/phase telemetry still collected"
                    )
                    attribute = False
                if attribute:
                    from ..framework.framework import SchedulerFramework
                    from .boundary import apply_planes, fold_answers

                    fw = SchedulerFramework(self.ec, self.pods, self._config)
                    mirror = init_state(self.ec, self.pods)

                state = self._init_dev_state()
                all_choices = []
                start_chunk = 0
                if resume and checkpoint_path:
                    ck = ReplayCheckpoint.load(checkpoint_path)
                    if ck.boundary is not None:
                        raise ValueError(
                            "checkpoint was written by a boundary-mode (retry/"
                            "kube) replay — its placements live in the host "
                            "mirror, not the saved outs; resume it with the "
                            "same retry_buffer/preemption configuration"
                        )
                    state = self._state_from_checkpoint(ck)
                    all_choices = [jnp.asarray(o) for o in ck.outs]
                    start_chunk = ck.chunk_cursor
                pending_events = sorted(node_events or [], key=lambda e: e.time)
                rel_time = self.pods.arrival + np.where(
                    np.isfinite(self.pods.duration), self.pods.duration, np.inf
                )
                completions_on = bool(
                    self.completions is not False  # None (the default) = on
                    and np.isfinite(rel_time).any()
                )
                refuse_wide_gangs(
                    self.wave_width, widest_gang(self.pods),
                    completions=completions_on,
                    checkpoint=bool(checkpoint_path or resume),
                )
                wave_times = (
                    self._wave_start_times(idx)
                    # attribute: series telemetry also samples utilization at chunk
                    # boundaries, which needs the chunk start times. The recorder
                    # stamps the chunk's virtual time on every row (host numpy
                    # only — no program effect).
                    if (pending_events or completions_on or attribute or rec is not None)
                    else None
                )
                pending_fold = None  # (rows, choices) of the not-yet-folded chunk
                nongang = self.pods.group_id == PAD
                if completions_on and self.preemption:
                    # Completions × preemption (round 4): folds run EAGERLY (the
                    # chunk's eviction events must land in the host bookkeeping
                    # BEFORE the next boundary's release decisions, or a pod the
                    # device evicted would "release" resources it no longer
                    # holds). The one-chunk slack therefore becomes an explicit
                    # bind-chunk check instead of a fold lag; the pipeline eats
                    # one blocking fetch per chunk — correctness over overlap for
                    # this opt-in combination.
                    chunk_of_arr = bind_chunk_of(self.pods, idx, C)
                if completions_on:
                    host_assign = np.where(
                        self.pods.bound_node >= 0, self.pods.bound_node, PAD
                    ).astype(np.int32)
                    released = np.zeros(self.pods.num_pods, bool)
                    if start_chunk:
                        # Resume: the saved state already carries pre-resume
                        # releases — seed from the persisted mask (or reconstruct
                        # from the saved outs for pre-field checkpoints). The
                        # one-chunk slack is restored by folding only chunks
                        # ≤ start_chunk−2 and re-pending the last saved chunk.
                        have_mask = getattr(ck, "released", None) is not None
                        host_assign, _ = rebuild_fork_state(
                            self.pods, idx, C, all_choices, wave_times,
                            max(start_chunk - 1, 0), reconstruct_released=False,
                        )
                        if have_mask:
                            released = ck.released.astype(bool)
                        else:
                            # released=None ⟹ a checkpoint from before the field
                            # existed ⟹ its state was built under the OLD
                            # (no-slack) release rule — reconstruct with slack=0.
                            _, released = rebuild_fork_state(
                                self.pods, idx, C, all_choices, wave_times,
                                start_chunk, slack=0,
                            )
                        if start_chunk >= 1:
                            pending_fold = (
                                idx[(start_chunk - 1) * C : start_chunk * C],
                                np.asarray(all_choices[start_chunk - 1]),
                            )
                saved_alloc = np.asarray(self.dc.allocatable).copy()
                # Pre-stage the per-chunk wave indices on device (a few MB total):
                # the timed loop then issues ONE call per chunk with no H2D.
                idx_chunks = (
                    None
                    if self.paged
                    else [
                        jnp.asarray(idx[c0 : c0 + C])
                        for c0 in range(0, idx.shape[0], C)
                    ]
                )
                self._register_programs(state, idx_chunks, completions_on)
                # Paged pod waves (round 14): per-chunk pages of the slot planes
                # stream host->device with one-chunk prefetch instead of whole-trace
                # residency. Pages carry page-LOCAL row indices (the kernels only
                # consume pod_id as a width, never as an identity).
                pager = None
                if self.paged:
                    def _fetch_page(pci):
                        rows = idx[pci * C : (pci + 1) * C]
                        flat = rows.reshape(-1)
                        local = np.where(
                            rows >= 0,
                            np.arange(
                                rows.size, dtype=np.int32
                            ).reshape(rows.shape),
                            PAD,
                        ).astype(np.int32)
                        return (
                            T.SlotSource.page(self.pods, flat),
                            V3.ExtraSource.page(self.static3, flat),
                            jnp.asarray(local),
                        )
                    pager = _PodPager(_fetch_page, threaded=_pager_thread_enabled())
                rec_valid = (
                    np.add.accumulate(
                        [
                            int((idx[c0 : c0 + C] >= 0).sum())
                            for c0 in range(0, idx.shape[0], C)
                        ]
                    )
                    if rec is not None
                    else None
                )
                rec_stalls_seen = 0
                rec_inval_seen = 0
                rec_pub = None
                rec_retry = None
                if rec is not None:
                    from ..parallel import dcn as _dcn

                    rec_pub = _dcn.publish_stats()
                    rec_retry = _dcn.retry_stats()
            t0 = time.perf_counter()
            for ci, c0 in enumerate(range(0, idx.shape[0], C)):
                if ci < start_chunk:
                    continue
                if pending_events:
                    chunk_t = wave_times[c0]
                    due = [e for e in pending_events if e.time <= chunk_t]
                    if due:
                        self._apply_node_events(due, saved_alloc)
                        if attribute:
                            # The mirror's plugins read ec.allocatable live.
                            self.ec.allocatable[:] = np.asarray(
                                self.dc.allocatable
                            )
                        if tel is not None and tel.cfg.want_timeline:
                            for ev in due:
                                if ev.kind in ("node_down", "node_up"):
                                    tel.event(
                                        ev.kind, float(ev.time), -1, int(ev.node)
                                    )
                        pending_events = pending_events[len(due):]
                if completions_on:
                    if self.preemption and pending_fold is not None:
                        # Eager eviction-aware fold of the previous chunk.
                        with _tick("boundary_fold"):
                            rows_p, out_p = pending_fold
                            preemption_walk(
                                host_assign, rows_p,
                                np.asarray(out_p[0]).reshape(rows_p.shape),
                                np.asarray(out_p[1]), np.asarray(out_p[2]),
                                self.static3.pod_tier, nongang,
                                released=released,
                            )
                        pending_fold = None
                    t_chunk = wave_times[c0]
                    if np.isfinite(t_chunk):
                        # The whole of what a boundary costs the host: the
                        # scan over all pods for due releases, the delta
                        # build and the release program's dispatch.
                        with _tick("host_mirror"):
                            due_m = (
                                (host_assign != PAD)
                                & ~released
                                & np.isfinite(rel_time)
                                & (rel_time <= t_chunk)
                            )
                            if self.preemption:
                                # Folds are eager here, so the one-chunk slack
                                # is the explicit bind-chunk rule.
                                due_m &= chunk_of_arr < ci - 1
                            due_p = np.nonzero(due_m)[0]
                            if due_p.size:
                                state = self._apply_release(
                                    state, due_p, host_assign[due_p]
                                )
                                if attribute:
                                    apply_planes(
                                        self.ec, self.pods, mirror, -1.0,
                                        due_p, host_assign[due_p],
                                    )
                                    mirror.bound[due_p] = PAD
                                released[due_p] = True
                if attribute and np.isfinite(wave_times[c0]):
                    # Utilization economics (round 13): chunk-boundary sample
                    # of the committed device state (binds through chunk ci-1
                    # plus the releases applied above), read off the carry
                    # itself: the mirror's float32 sums are added in another
                    # order. A series-mode-only fetch; summary never syncs
                    # here.
                    with _tick("host_mirror"):
                        tel.sample(
                            float(wave_times[c0]),
                            **series_gauges(
                                state.to_host(
                                    self.ec, self.static3, self._Dhost
                                )[0],
                                np.asarray(self.dc.allocatable),
                                self.ec.vocab._r,
                            ),
                        )
                with _tick("dispatch"), _tick.mark(f"chunk:{ci}"):
                    if pager is not None:
                        src, xsrc, lidx = pager.get(ci)
                        state, choices = self.chunk_fn(
                            self.dc, state, src, xsrc, lidx
                        )
                    else:
                        state, choices = self.chunk_fn(
                            self.dc, state, self._slot_src, self._extra_src,
                            idx_chunks[ci],
                        )
                if pager is not None and c0 + C < idx.shape[0]:
                    # Stage the next page while this chunk is still on device.
                    pager.prefetch(ci + 1)
                all_choices.append(choices)
                if attribute:
                    with _tick("device_wait"):
                        ch_np = np.asarray(choices)
                    with _tick("boundary_fold"):
                        fold_answers(fw, mirror, idx[c0 : c0 + C], ch_np, tel)
                if completions_on and self.preemption:
                    pending_fold = (idx[c0 : c0 + C], choices)
                elif completions_on:
                    # Fold the PREVIOUS chunk's choices AFTER dispatching this
                    # one: the blocking fetch overlaps the in-flight chunk, and
                    # boundary b only ever sees chunks ≤ b−2 (the one-chunk
                    # slack; the greedy anchor implements the same rule).
                    if pending_fold is not None:
                        with _tick("boundary_fold"):
                            rows_p, ch_p = pending_fold
                            ch = np.asarray(ch_p).reshape(rows_p.shape)
                            v = rows_p >= 0
                            host_assign[rows_p[v]] = ch[v]
                    pending_fold = (idx[c0 : c0 + C], choices)
                if checkpoint_path and checkpoint_every and (ci + 1) % checkpoint_every == 0:
                    t_ck = time.perf_counter()
                    self._save_checkpoint(
                        state, ci + 1, all_choices, checkpoint_path,
                        released=(
                            released
                            if completions_on
                            else np.zeros(self.pods.num_pods, bool)
                        ),
                    )
                    if rec is not None:
                        rec.checkpoint(
                            ci + 1, _file_bytes(checkpoint_path),
                            time.perf_counter() - t_ck,
                        )
                if rec is not None:
                    if pager is not None and (
                        pager.stalls > rec_stalls_seen
                        or pager.invalidations > rec_inval_seen
                    ):
                        rec.page(
                            ci, pager.last_stall_s, pager.stalls,
                            invalidations=pager.invalidations,
                        )
                        rec_stalls_seen = pager.stalls
                        rec_inval_seen = pager.invalidations
                    pub_now = _dcn.publish_stats()
                    ck_pub = None
                    if pub_now != rec_pub:
                        ck_pub = {
                            "count": pub_now["count"] - rec_pub["count"],
                            "wall_s": round(
                                pub_now["wall_s"] - rec_pub["wall_s"], 6
                            ),
                            "bytes": pub_now["bytes"] - rec_pub["bytes"],
                        }
                        rec_pub = pub_now
                    retry_now = _dcn.retry_stats()
                    kv_retry = None
                    if retry_now != rec_retry:
                        kv_retry = {
                            "retries": retry_now["retries"]
                            - rec_retry["retries"],
                            "giveups": retry_now["giveups"]
                            - rec_retry["giveups"],
                            "backoff_s": round(
                                retry_now["backoff_s"]
                                - rec_retry["backoff_s"], 6
                            ),
                        }
                        rec_retry = retry_now
                    rec.chunk(
                        ci,
                        t_virtual=(
                            wave_times[c0] if wave_times is not None else None
                        ),
                        dispatched=int(rec_valid[ci]),
                        placed=(
                            int((host_assign >= 0).sum())
                            if completions_on
                            else None
                        ),
                        phase_acc=(
                            tel.phases.acc if tel is not None else rec.phases.acc
                        ),
                        pager=pager,
                        ckpt_publish=ck_pub,
                        kv_retry=kv_retry,
                    )
            with _tick("device_wait"):
                jax.block_until_ready(all_choices[-1] if all_choices else state)
            wall = time.perf_counter() - t0
            with _tick("gather"):
                if node_events:
                    self.dc = self.dc._replace(allocatable=jnp.asarray(saved_alloc))
                    if attribute:
                        self.ec.allocatable[:] = saved_alloc

                preemptions = 0
                gangs = None
                to_schedule = int((idx >= 0).sum())
                if self.preemption and completions_on:
                    # The incremental eviction-aware folds ARE the walk; finish
                    # the last pending chunk and read the result off the host
                    # bookkeeping (a fresh full walk would replay evictions
                    # against completed pods with the wrong interleaving).
                    if pending_fold is not None:
                        rows_p, out_p = pending_fold
                        preemption_walk(
                            host_assign, rows_p,
                            np.asarray(out_p[0]).reshape(rows_p.shape),
                            np.asarray(out_p[1]), np.asarray(out_p[2]),
                            self.static3.pod_tier, nongang, released=released,
                        )
                    assignments = host_assign
                    scheduled = self.pods.bound_node == PAD
                    placed = int((assignments[scheduled] >= 0).sum())
                    preemptions = int(
                        np.concatenate(
                            [np.asarray(c[4]) for c in all_choices]
                        ).sum()
                    )
                elif self.preemption:
                    finals = np.concatenate([np.asarray(c[0]) for c in all_choices])
                    ev_node = np.concatenate([np.asarray(c[1]) for c in all_choices])
                    ev_tier = np.concatenate([np.asarray(c[2]) for c in all_choices])
                    ev_total = np.concatenate([np.asarray(c[4]) for c in all_choices])
                    assignments, placed = self._preemption_walk(
                        idx, finals, ev_node, ev_tier
                    )
                    preemptions = int(ev_total.sum())
                else:
                    choices_np = np.asarray(jnp.concatenate(all_choices, axis=0))
                    assignments = np.where(
                        self.pods.bound_node >= 0, self.pods.bound_node, PAD
                    ).astype(np.int32)
                    flat_idx = idx.reshape(-1)
                    flat_choice = choices_np.reshape(-1)
                    valid = flat_idx >= 0
                    assignments[flat_idx[valid]] = flat_choice[valid]
                    if self._wide_gangs:
                        # A member of a group wider than the wave wrote its
                        # node when its wave ran; the verdict of its group
                        # is in the final state's log, by ordinal.
                        tab = self.static3.txn_tab
                        rolled = np.asarray(state.txn.log)
                        members = np.nonzero(tab[:, 0] >= 0)[0]
                        assignments[members[rolled[tab[members, 2]]]] = PAD
                        gangs = V3.gangs_summary(
                            self.static3, rolled.sum(), state.txn.undone
                        )
                    placed = int((assignments[flat_idx[valid]] >= 0).sum())

                if tel is not None:
                    # Plain replay: every placement is a wave placement — bound in
                    # the same chunk it arrived in, zero virtual-time latency by
                    # the chunk-granular convention (SURVEY.md §5).
                    tel.bind_zero(placed)

                used, mc, aa, pw = state.to_host(self.ec, self.static3, self._Dhost)
                util = utilization_means(used, self.ec.allocatable, self.ec.vocab._r)
                pending_m = (self.pods.bound_node == PAD) & (assignments == PAD)
                frag = fragmentation_gauges(
                    self.ec.allocatable, used, self.pods.requests[pending_m],
                    self.ec.vocab._r,
                )
                host_state = SchedState(
                    used=used, match_count=mc, anti_active=aa, pref_wsum=pw,
                    bound=assignments.copy(),
                )
                if rec is not None:
                    # Pager walls join the phase accumulators (keys only present
                    # when paging is on AND the recorder observed them, so the
                    # canonical PHASE_NAMES-only runs are unchanged).
                    # ``pager_stall`` is the EXPOSED wall; ``pager_prefetch`` the
                    # fetch wall itself — hidden under the round-19 thread,
                    # loop-exposed without it.
                    if pager is not None and tel is not None:
                        tel.phases.add("pager_stall", pager.stall_s)
                        tel.phases.add("pager_prefetch", pager.prefetch_wall_s)
                    if rec_own:
                        rec.close({"placed": int(placed)})
                if pager is not None:
                    pager.close()
            tel_out = tel.result() if tel is not None else None
            if tel_out is not None:
                tel_out.gangs = gangs
            return ReplayResult(
                assignments=assignments,
                placed=placed,
                unschedulable=to_schedule - placed,
                preemptions=preemptions,
                attempts=to_schedule,
                wall_clock_s=wall,
                placements_per_sec=placed / wall if wall > 0 else 0.0,
                virtual_makespan=float(self.pods.arrival.max()) if self.pods.num_pods else 0.0,
                utilization=util,
                state=host_state,
                fragmentation=frag,
                telemetry=tel_out,
            )


@register_strategy("jax")
def _make_jax(ec: EncodedCluster, pods: EncodedPods, config: Optional[FrameworkConfig] = None, **kw):
    return JaxReplayEngine(ec, pods, config, **kw)
