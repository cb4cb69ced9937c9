"""Metrics & observability (layer L7; SURVEY.md §5).

Structured JSONL results (per-run and per-scenario rows), plain-text
progress logging, and a BASELINE.md-compatible table emitter. The headline
metric is pod-placements/sec ([BASELINE])."""

from __future__ import annotations

import hashlib
import json
import logging
import os
import sys
import time
from typing import IO, Dict, Iterable, Optional

import numpy as np


def deterministic_jsonl() -> bool:
    """``KSIM_DETERMINISTIC_JSONL=1`` zeroes every wall-clock-derived
    JSONL field (``ts``, ``wall_clock_s``, ``placements_per_sec``) while
    keeping the fields PRESENT as numbers, so v2-schema rows stay valid.
    This is what makes the round-11 DCN parity bar byte-for-byte testable:
    a 2-process replay and its single-process oracle differ only in
    timing, never in results — with timing zeroed, the JSONL files must
    be identical down to the byte (tests/test_dcn.py)."""
    return os.environ.get("KSIM_DETERMINISTIC_JSONL", "") == "1"

log = logging.getLogger("k8sim")
if not log.handlers:
    _h = logging.StreamHandler(sys.stderr)
    _h.setFormatter(logging.Formatter("%(asctime)s %(name)s %(levelname)s %(message)s"))
    log.addHandler(_h)
    log.setLevel(logging.INFO)

# JSONL row schema version. Bump on any breaking change to the row shape;
# scripts/check_metrics_schema.py validates emitted files against it.
#   v1 — rows carried only "ts" + payload (implicit, unversioned).
#   v2 — every row stamped with "schema" plus writer context
#        (seed / engine / config_hash from the CLI).
#   v3 — tuner rows (sim.tuner): "run_type" required, "ts" optional —
#        trajectory files are bit-deterministic for a fixed seed + config,
#        so no wall-clock fields. Non-tuner rows stay v2.
#   v4 — utilization economics (round 13): replay rows may carry a
#        "fragmentation" dict (stranded / frag_index / packing gauges);
#        whatif-scenario rows may carry stranded_cpu / frag_index_cpu /
#        packing_efficiency (None on paths without host mirrors). All new
#        fields are virtual-time-deterministic — KSIM_DETERMINISTIC_JSONL
#        needs no new scrubs.
#   v5 — flight recorder (round 16): a new "flight" row kind
#        (sim.flight.FlightRecorder) with a relaxed base — flight streams
#        are engine-internal, so rows carry ts/schema/kind but no CLI
#        context (seed/engine/config_hash). Non-flight rows keep the v4
#        rules; v1–v4 files validate byte-unchanged.
#        KSIM_DETERMINISTIC_JSONL zeroes every wall-clock-derived flight
#        field (sim.flight.FLIGHT_WALL_FIELDS) so fixed-seed recorder
#        streams are byte-stable.
#   v6 — fleet black box (round 21): rows may carry the causal trace
#        identity fields "trace"/"span"/"parent"/"link" (parallel.trace
#        — pure functions of protocol state, never scrubbed), flight
#        streams may carry "fleet" event rows (dcn fleet events
#        flattened by the recorder), and a new "postmortem" row kind
#        (scripts/fleet_postmortem.py audit summary: events ingested,
#        links resolved, invariant verdicts, audit wall). Non-flight
#        rows keep the v4 rules; v1–v5 files validate byte-unchanged.
#   v7 — simulator-as-a-service (round 22, sim.service): three new row
#        kinds on the serving plane — "query" (admission: tenant /
#        query id / family / queue depth), "query-result" (per-tenant
#        demux of a coalesced batch: slot, occupancy, warm flag, batch
#        latency, eviction cost + fragmentation benefit vs the baseline
#        slot) and "query-error" (a malformed serve line, structured —
#        the service keeps serving). Flight streams gain a "query"
#        event (queue depth, batch occupancy, cold-vs-warm latency).
#        KSIM_DETERMINISTIC_JSONL zeroes the new wall-derived fields
#        ("latency_s" / "queue_wait_s"). v1–v6 files validate
#        byte-unchanged.
SCHEMA_VERSION = 7
TUNE_SCHEMA_VERSION = 3


def config_hash(cfg_dict: dict) -> str:
    """Short stable hash of a config mapping (canonical-JSON sha256).
    Stamped on every JSONL row so runs are attributable to the exact
    config that produced them."""
    blob = json.dumps(cfg_dict, sort_keys=True, separators=(",", ":"), default=str)
    return hashlib.sha256(blob.encode()).hexdigest()[:12]


# -- utilization economics (round 13) ------------------------------------
#
# Every engine (CPU event engine, device boundary mirror, plain device
# path after D2H) funnels its end-of-replay and per-sample utilization /
# fragmentation arithmetic through the three helpers below. One shared
# float64 code path is what makes the CPU↔device bit-parity bar hold BY
# CONSTRUCTION: both engines hand over the same committed state, so the
# gauges cannot drift through reimplementation.

_UTIL_RESOURCES = ("cpu", "memory")


def utilization_means(used, allocatable, rindex) -> Dict[str, float]:
    """Mean per-node utilization fraction per resource name.

    ``used``/``allocatable`` are [N, R]; ``rindex`` maps resource name →
    column. Nodes with zero allocatable (drained / chaos node_down before
    restore) count as 0 utilization, matching the historical inline loops
    this replaces."""
    used = np.asarray(used, dtype=np.float64)
    alloc_all = np.asarray(allocatable, dtype=np.float64)
    util: Dict[str, float] = {}
    for rname in _UTIL_RESOURCES:
        ri = rindex.get(rname)
        if ri is not None:
            alloc = alloc_all[:, ri]
            with np.errstate(invalid="ignore", divide="ignore"):
                u = np.where(alloc > 0, used[:, ri] / np.where(alloc > 0, alloc, 1), 0)
            util[rname] = float(u.mean())
    return util


def series_gauges(used, allocatable, rindex) -> Dict[str, float]:
    """Per-sample utilization gauges for the telemetry series (round 13).

    Keys: ``util_cpu`` (mean per-node CPU utilization), ``util_mem``
    (only when the vocab has a memory column — series keys must stay
    consistent within one run), and ``frag_cpu`` (CPU fragmentation
    index: 1 − largest free block / total free; 0 when nothing is free).
    Called at every event-loop sample on the CPU engine and at every
    chunk boundary on the device path — same helper, bit-parity by
    construction."""
    means = utilization_means(used, allocatable, rindex)
    out = {"util_cpu": means.get("cpu", 0.0)}
    if "memory" in means:
        out["util_mem"] = means["memory"]
    ci = rindex.get("cpu")
    frag = 0.0
    if ci is not None:
        alloc = np.asarray(allocatable, dtype=np.float64)[:, ci]
        u = np.asarray(used, dtype=np.float64)[:, ci]
        free = np.maximum(alloc - u, 0.0)
        total_free = float(free.sum())
        if total_free > 0.0:
            frag = 1.0 - float(free.max()) / total_free
    out["frag_cpu"] = frag
    return out


def fragmentation_gauges(allocatable, used, pending_requests, rindex) -> dict:
    """End-of-replay fragmentation / packing gauges (round 13).

    - ``stranded[r]``: free capacity on nodes that cannot fit the largest
      still-pending pod (largest by CPU request, memory tie-break, lowest
      pod index last) — the classic stranded-capacity gauge. 0 when no
      pod is pending. The fit test is vector-wise over ALL resource
      columns, so a node is only "usable" if the whole pod fits.
    - ``frag_index[r]``: 1 − largest free block / total free (0 when the
      cluster is fully packed or fully empty).
    - ``packing_efficiency``: ideal node count (sum-of-usage lower bound,
      per-resource ceiling against the largest node) / nodes actually
      touched. 1.0 when nothing is placed.

    Pure float64 numpy on host state — both engines call it with the
    restored allocatable and their committed ``used``/pending sets, so
    the outputs are bit-identical CPU ↔ device."""
    alloc = np.asarray(allocatable, dtype=np.float64)
    used = np.asarray(used, dtype=np.float64)
    req = np.asarray(pending_requests, dtype=np.float64)
    if req.ndim == 1:
        req = req.reshape(0, alloc.shape[1]) if req.size == 0 else req.reshape(1, -1)
    free = np.maximum(alloc - used, 0.0)
    names = [r for r in _UTIL_RESOURCES if rindex.get(r) is not None]

    stranded: Dict[str, float] = {r: 0.0 for r in names}
    stranded_frac: Dict[str, float] = {r: 0.0 for r in names}
    npend = int(req.shape[0])
    if npend:
        n = npend
        ci, mi = rindex.get("cpu"), rindex.get("memory")
        key_cpu = req[:, ci] if ci is not None else np.zeros(n)
        key_mem = req[:, mi] if mi is not None else np.zeros(n)
        # lexsort: last key is primary — biggest CPU, then biggest memory,
        # then lowest index, so the "largest pending pod" is deterministic.
        big = req[int(np.lexsort((np.arange(n), -key_mem, -key_cpu))[0])]
        # The scheduler's own fit arithmetic decides "cannot fit" (local
        # import: ops pulls the model stack, metrics must stay light).
        from ..ops.cpu import pending_fit_mask

        fits = pending_fit_mask(used, alloc, big)
        for r in names:
            ri = rindex[r]
            stranded[r] = float(free[~fits, ri].sum())
            total = float(alloc[:, ri].sum())
            stranded_frac[r] = stranded[r] / total if total > 0 else 0.0

    frag_index: Dict[str, float] = {}
    for r in names:
        ri = rindex[r]
        total_free = float(free[:, ri].sum())
        frag_index[r] = (
            1.0 - float(free[:, ri].max()) / total_free if total_free > 0 else 0.0
        )

    nodes_active = int(np.any(used > 0, axis=1).sum())
    nodes_ideal = 0
    for r in names:
        ri = rindex[r]
        cap = float(alloc[:, ri].max()) if alloc.shape[0] else 0.0
        total_used = float(used[:, ri].sum())
        if cap > 0 and total_used > 0:
            nodes_ideal = max(nodes_ideal, int(np.ceil(total_used / cap)))
    packing = float(nodes_ideal) / nodes_active if nodes_active else 1.0
    return {
        "stranded": stranded,
        "stranded_frac": stranded_frac,
        "frag_index": frag_index,
        "packing_efficiency": packing,
        "nodes_active": nodes_active,
        "nodes_ideal": nodes_ideal,
        "pending": npend,
    }


def round_fragmentation(frag: Optional[dict]) -> Optional[dict]:
    """JSONL/summary-friendly copy of a fragmentation_gauges() dict with
    floats rounded to 6 places (virtual-time-deterministic, so no
    KSIM_DETERMINISTIC_JSONL scrub is needed)."""
    if frag is None:
        return None
    out: dict = {}
    for k, v in frag.items():
        if isinstance(v, dict):
            out[k] = {kk: round(float(vv), 6) for kk, vv in v.items()}
        elif isinstance(v, float):
            out[k] = round(v, 6)
        else:
            out[k] = v
    return out


class JsonlWriter:
    """Append-mode JSONL sink (stdout when ``path`` is None). Usable as a
    context manager — the CLI wraps whole commands in ``with`` so the file
    is closed (rows flushed) even when the run raises. Every row is
    stamped with ``ts``, ``schema`` and the writer's ``context`` (seed /
    engine / config hash); explicit row keys win over context keys."""

    def __init__(self, path: Optional[str] = None, context: Optional[dict] = None):
        self.path = path
        self.context = dict(context or {})
        self._f: Optional[IO] = open(path, "a") if path else None
        self._proc: Optional[dict] = None  # lazy DCN process stamp

    def _process_stamp(self) -> dict:
        """``process_id``/``process_count`` under DCN (round 12): rows from
        a fleet are attributable to the worker that wrote them. Empty in
        single-process runs — v1–v3 rows are byte-unchanged there, and the
        DCN parity bar strips exactly these two keys before comparing
        against the single-process oracle (tests/dcn_case_worker.py)."""
        if self._proc is None:
            try:
                from ..parallel import dcn

                nproc, pid = dcn.process_info()
                self._proc = (
                    {"process_id": int(pid), "process_count": int(nproc)}
                    if nproc > 1
                    else {}
                )
            except Exception:
                self._proc = {}
        return self._proc

    def write(self, row: dict, stamp_ts: bool = True) -> None:
        # stamp_ts=False drops the wall-clock stamp — the policy tuner's
        # trajectory rows must be byte-identical across same-seed runs.
        stamp = (
            {"ts": 0.0 if deterministic_jsonl() else time.time()}
            if stamp_ts
            else {}
        )
        row = {
            **stamp,
            "schema": SCHEMA_VERSION,
            **self._process_stamp(),
            **self.context,
            **row,
        }
        line = json.dumps(row)
        if self._f:
            self._f.write(line + "\n")
            self._f.flush()
        else:
            print(line)

    def close(self) -> None:
        if self._f:
            self._f.close()
            self._f = None

    def __enter__(self) -> "JsonlWriter":
        return self

    def __exit__(self, *exc) -> bool:
        self.close()
        return False


def _scrub_timing(row: dict) -> dict:
    """Zero wall-clock-derived fields under KSIM_DETERMINISTIC_JSONL
    (fields stay present as numbers — schema v2 requires them)."""
    if deterministic_jsonl():
        for k in (
            "wall_clock_s", "placements_per_sec", "latency_s",
            "queue_wait_s",
        ):
            if k in row:
                row[k] = 0.0
    return row


def replay_row(kind: str, res, extra: Optional[dict] = None) -> dict:
    row = {"kind": kind, **res.summary()} if hasattr(res, "summary") else {"kind": kind}
    if extra:
        row.update(extra)
    return _scrub_timing(row)


def whatif_rows(res, extra: Optional[dict] = None) -> Iterable[dict]:
    base = extra or {}
    yield _scrub_timing({
        "kind": "whatif-aggregate",
        "scenarios": int(res.placed.shape[0]),
        "total_placed": res.total_placed,
        "wall_clock_s": round(res.wall_clock_s, 4),
        "placements_per_sec": round(res.placements_per_sec, 1),
        "completions_on": bool(res.completions_on),
        "engine": res.engine,
        **base,
    })
    pre = getattr(res, "preemptions", None)
    drop = getattr(res, "retry_dropped", None)
    evi = getattr(res, "evictions", None)
    lat50 = getattr(res, "latency_p50", None)
    str_cpu = getattr(res, "stranded_cpu", None)
    bound_at = getattr(res, "bind_boundary", None)
    for s in range(res.placed.shape[0]):
        row = {
            "kind": "whatif-scenario",
            "scenario": s,
            "placed": int(res.placed[s]),
            "unschedulable": int(res.unschedulable[s]),
            "utilization_cpu": (
                round(float(res.utilization_cpu[s]), 4) if res.utilization_cpu is not None else None
            ),
            **base,
        }
        if bound_at is not None:
            # device retry path with placements: the queue's outcome
            row["retry_placed"] = int((bound_at[s] >= 0).sum())
            row["queued_at_end"] = int((bound_at[s] == -2).sum())
            row["retry_dropped"] = int((bound_at[s] == -3).sum())
        if pre is not None:
            # kube batches: drops mean placements lost to buffer
            # capacity, not infeasibility.
            row["preemptions"] = int(pre[s])
            row["retry_dropped"] = int(drop[s])
        if evi is not None:
            # chaos disruption — distinct from scheduler-initiated
            # preemption above.
            row["evictions"] = int(evi[s])
            row["evict_rescheduled"] = int(res.evict_rescheduled[s])
            row["evict_stranded"] = int(res.evict_stranded[s])
            row["evict_latency_mean"] = round(
                float(res.evict_latency_mean[s]), 4
            )
        out_at = getattr(res, "node_out_at", None)
        if out_at is not None:
            # a drain under disruption budgets: what the budgets let go,
            # what was forced out (at a deadline, by a failure), the nodes
            # that went out and the boundary the last of them did
            kinds = res.eviction_log[s][:, 4]
            row["evict_voluntary"] = int((kinds == 0).sum())
            row["evict_forced_deadline"] = int((kinds == 1).sum())
            row["evict_forced_failure"] = int((kinds == 2).sum())
            row["nodes_out"] = int((out_at[s] >= 0).sum())
            row["last_node_out_at"] = int(out_at[s].max())
        if lat50 is not None:
            # Telemetry layer: per-scenario first-bind latency quantiles
            # (virtual seconds); None when the scenario bound nothing.
            import math

            for key, arr in (
                ("latency_p50", lat50),
                ("latency_p90", res.latency_p90),
                ("latency_p99", res.latency_p99),
            ):
                v = float(arr[s])
                row[key] = None if math.isnan(v) else round(v, 6)
        if str_cpu is not None:
            # Fragmentation economics (schema v4, kube what-if paths with
            # host mirrors); virtual-time-deterministic by construction.
            row["stranded_cpu"] = round(float(str_cpu[s]), 6)
            row["frag_index_cpu"] = round(float(res.frag_index_cpu[s]), 6)
            row["packing_efficiency"] = round(
                float(res.packing_efficiency[s]), 6
            )
        yield row


def baseline_table(rows: Iterable[dict]) -> str:
    """Markdown table in the BASELINE.md format."""
    out = ["| Metric | Value | Hardware | Source |", "|---|---|---|---|"]
    for r in rows:
        out.append(
            f"| {r.get('metric', r.get('kind'))} | {r.get('value', r.get('placements_per_sec'))} "
            f"| {r.get('hardware', '-')} | {r.get('source', 'this run')} |"
        )
    return "\n".join(out)
