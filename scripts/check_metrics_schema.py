#!/usr/bin/env python
"""Validate emitted JSONL metrics files against the versioned row schema
(utils.metrics.SCHEMA_VERSION).

    python scripts/check_metrics_schema.py results.jsonl [more.jsonl ...]

Exit 0 when every row validates, 1 otherwise (one line per offending row).
Wired as a tier-1 test (tests/test_metrics_schema.py) over a fresh CLI
run, so schema drift between the writers and this contract fails CI.
"""

from __future__ import annotations

import json
import sys
from typing import List, Optional

_NUM = (int, float)

# Base stamp every v2 row carries (JsonlWriter.write + CLI context).
_BASE_V2 = {
    "ts": _NUM,
    "schema": int,
    "seed": int,
    "engine": str,
    "config_hash": str,
    "kind": str,
}

# kind → required payload fields. "replay-*" kinds share one shape.
_REPLAY_REQUIRED = {
    "placed": int,
    "unschedulable": int,
    "wall_clock_s": _NUM,
    "placements_per_sec": _NUM,
}
_WHATIF_AGG_REQUIRED = {
    "scenarios": int,
    "total_placed": int,
    "wall_clock_s": _NUM,
    "placements_per_sec": _NUM,
    "completions_on": bool,
}
_WHATIF_SCEN_REQUIRED = {
    "scenario": int,
    "placed": int,
    "unschedulable": int,
}

# Optional typed fields (present ⇒ must have this type; None allowed
# where the writer emits explicit nulls).
_OPTIONAL = {
    "preemptions": _NUM,
    "attempts": _NUM,
    "retry_dropped": _NUM,
    "evictions": _NUM,
    "evict_rescheduled": _NUM,
    "evict_stranded": _NUM,
    "evict_latency_mean": _NUM,
    "virtual_makespan": _NUM,
    "utilization": dict,
    "utilization_cpu": (*_NUM, type(None)),
    "latency_p50": (*_NUM, type(None)),
    "latency_p90": (*_NUM, type(None)),
    "latency_p99": (*_NUM, type(None)),
    "telemetry": dict,
    "config": str,
    "mesh": bool,
    # Round 11 (multi-host DCN): provenance fields stamped by
    # DCN-aware writers. Round 12: JsonlWriter stamps process_id +
    # process_count on every row of a multi-process fleet (so rows are
    # attributable to the worker that wrote them); single-process files
    # are byte-unchanged, and the DCN parity bar strips exactly these two
    # keys before comparing against the single-process oracle
    # (tests/dcn_case_worker.py).
    "process_count": int,
    "process_id": int,
    "n_devices": int,
    "mesh_shape": (dict, type(None)),
    "dcn_scaling": dict,
}

_TEL_GRANULARITIES = ("summary", "series", "timeline")

# v4 (utilization economics, round 13): v2 rules plus optional typed
# fragmentation fields — replay rows may carry a "fragmentation" gauge
# dict; whatif-scenario rows may carry per-scenario stranded/frag-index/
# packing gauges. v1–v3 rows validate byte-unchanged.
_OPTIONAL_V4 = {
    "fragmentation": dict,
    "stranded_cpu": (*_NUM, type(None)),
    "frag_index_cpu": (*_NUM, type(None)),
    "packing_efficiency": (*_NUM, type(None)),
}

# v5 (flight recorder, round 16): a new "flight" row kind
# (sim.flight.FlightRecorder) with a RELAXED base — flight streams are
# engine-internal (not CLI result files), so rows carry ts/schema/kind
# but no seed/engine/config_hash context. Non-flight v5 rows follow the
# v4 rules unchanged; v1–v4 files validate byte-unchanged.
_FLIGHT_REQUIRED = {
    "event": str,
    "chunk": int,
}
_FLIGHT_EVENTS = (
    "start", "chunk", "page", "checkpoint", "boundary_fold", "end",
)
_OPTIONAL_FLIGHT = {
    "wall_s": _NUM,
    "rolling_pps": _NUM,
    "phases": dict,
    "rss_peak_mib": _NUM,
    "t_virtual": (*_NUM, type(None)),
    "dispatched": int,
    "placed": int,
    "pager_depth": int,
    "pager_stalls": int,
    "pager_stall_s": _NUM,
    "stall_s": _NUM,
    "exchange_probe_s": _NUM,
    "exchange_slots": int,
    "exchange_est_s": _NUM,
    "ckpt_bytes": int,
    "ckpt_wall_s": _NUM,
    "ckpt_sink": str,
    "dcn_publish": dict,
    "events": int,
    "resident_bytes": _NUM,
    "nodes": int,
    "pods": int,
    "node_shards": int,
    "paged": bool,
    "engine": str,
    "chunk_waves": int,
    "process_id": int,
    "process_count": int,
}


# v6 (fleet black box, round 21): any row may carry the causal trace
# identity fields stamped by parallel.trace — pure functions of
# protocol state (pid/gen/bid/cursor), so they survive the
# deterministic scrub. Flight streams gain "fleet" event rows (dcn
# fleet events flattened by the recorder; their payload keys are
# event-specific and intentionally open, like every flight row), and a
# new "postmortem" row kind carries the fleet_postmortem.py audit
# summary with the same relaxed base as flight rows. v1–v5 files
# validate byte-unchanged — the v5 dispatch below is untouched.
_OPTIONAL_TRACE = {
    "trace": str,
    "span": str,
    "parent": str,
    "link": str,
}
_FLIGHT_EVENTS_V6 = _FLIGHT_EVENTS + ("fleet",)
_OPTIONAL_FLIGHT_V6 = {
    **_OPTIONAL_FLIGHT,
    **_OPTIONAL_TRACE,
    "fleet_event": str,
    "renew_age_s": _NUM,
    "threshold_s": _NUM,
    "dcn_retry": dict,
}
_POSTMORTEM_REQUIRED = {
    "events_ingested": int,
    "links_resolved": int,
    "violations": int,
    "warnings": int,
    "audit_wall_s": _NUM,
    "invariants": dict,
}

# v7 (simulator-as-a-service, round 22 — sim.service): three new row
# kinds on the serving plane. "query" (admission) and "query-result"
# (per-tenant demux of a coalesced batch) carry a RELAXED base like
# flight rows — API-driven services write without CLI context — but the
# serve CLI stamps the full v2 context, so those keys stay optional
# typed, never required. "query-error" is a structured malformed-line
# report (the service keeps serving). Flight streams gain a "query"
# event. v1–v6 files validate byte-unchanged — the dispatch arms below
# only widen for schema == 7.
_QUERY_REQUIRED = {
    "tenant": str,
    "query": str,
    "family": str,
    "queue_depth": int,
}
_QUERY_RESULT_REQUIRED = {
    "tenant": str,
    "query": str,
    "family": str,
    "batch": int,
    "slot": int,
    "warm": bool,
    "latency_s": _NUM,
    "placed": int,
    "unschedulable": int,
}
_QUERY_ERROR_REQUIRED = {
    "error": str,
}
_OPTIONAL_QUERY = {
    "batch_occupancy": _NUM,
    "queue_wait_s": _NUM,
    "placed_delta": int,
    "evictions": (*_NUM, type(None)),
    "evict_rescheduled": (*_NUM, type(None)),
    "evict_stranded": (*_NUM, type(None)),
    "evict_latency_mean": (*_NUM, type(None)),
    "stranded_cpu": (*_NUM, type(None)),
    "frag_index_cpu": (*_NUM, type(None)),
    "packing_efficiency": (*_NUM, type(None)),
    "baseline_stranded_cpu": (*_NUM, type(None)),
    "baseline_frag_index_cpu": (*_NUM, type(None)),
    "baseline_packing_efficiency": (*_NUM, type(None)),
    "telemetry": dict,
    "raw": str,
    # Serve-CLI context stamp (optional here — API writers omit it).
    "seed": int,
    "engine": str,
    "config_hash": str,
    "process_id": int,
    "process_count": int,
}
_FLIGHT_EVENTS_V7 = _FLIGHT_EVENTS_V6 + ("query",)
_OPTIONAL_FLIGHT_V7 = {
    **_OPTIONAL_FLIGHT_V6,
    "batch": int,
    "queue_depth": int,
    "batch_occupancy": _NUM,
    "warm": bool,
    "engines": int,
    "latency_s": _NUM,
}


def _validate_query(row: dict, required: dict) -> List[str]:
    errs = []
    if not isinstance(row.get("ts"), _NUM):
        errs.append(f"ts: expected a number, got {row.get('ts')!r}")
    for k, t in required.items():
        v = row.get(k)
        if not isinstance(v, t) or (isinstance(v, bool) and t is not bool):
            errs.append(f"{k}: expected {t}, got {v!r}")
    for k, t in _OPTIONAL_QUERY.items():
        if k in row and (
            not isinstance(row[k], t)
            or (isinstance(row[k], bool) and t is not bool)
        ):
            errs.append(f"{k}: expected {t}, got {row[k]!r}")
    return errs


def _validate_flight(
    row: dict, events=_FLIGHT_EVENTS, optional=_OPTIONAL_FLIGHT
) -> List[str]:
    errs = []
    if not isinstance(row.get("ts"), _NUM):
        errs.append(f"ts: expected a number, got {row.get('ts')!r}")
    for k, t in _FLIGHT_REQUIRED.items():
        v = row.get(k)
        if not isinstance(v, t) or isinstance(v, bool):
            errs.append(f"{k}: expected {t}, got {v!r}")
    ev = row.get("event")
    if isinstance(ev, str) and ev not in events:
        errs.append(f"event: unknown {ev!r}")
    for k, t in optional.items():
        if k in row and (
            not isinstance(row[k], t)
            or (isinstance(row[k], bool) and t is not bool)
        ):
            errs.append(f"{k}: expected {t}, got {row[k]!r}")
    return errs


def _validate_postmortem(row: dict) -> List[str]:
    errs = []
    if not isinstance(row.get("ts"), _NUM):
        errs.append(f"ts: expected a number, got {row.get('ts')!r}")
    for k, t in _POSTMORTEM_REQUIRED.items():
        v = row.get(k)
        if not isinstance(v, t) or isinstance(v, bool):
            errs.append(f"{k}: expected {t}, got {v!r}")
    return errs


# v3 (policy tuner, sim.tuner): "run_type" is required and "ts" becomes
# OPTIONAL — trajectory rows are bit-deterministic for a fixed seed +
# config, so the writer omits the wall-clock stamp (JsonlWriter
# stamp_ts=False). The CLI context stamp (seed/engine/config_hash) is
# optional too: API-driven tuner runs write without a context.
_BASE_V3 = {
    "schema": int,
    "run_type": str,
    "kind": str,
}
_OPTIONAL_V3 = {
    "ts": _NUM,
    "seed": int,
    "engine": str,
    "config_hash": str,
    "config": str,
    # Round 12: tuner trajectories written by a DCN fleet carry the same
    # process stamp as v2 rows.
    "process_id": int,
    "process_count": int,
}
_TUNE_CAND_REQUIRED = {
    "round": int,
    "candidate": int,
    "policy": dict,
    "objective": _NUM,
    "split": str,
}
_TUNE_ROUND_REQUIRED = {
    "round": int,
    "best_objective": _NUM,
    "round_best_objective": _NUM,
    "mean_objective": _NUM,
    "best_candidate": int,
}
_TUNE_RESULT_REQUIRED = {
    "best_policy": dict,
    "train_objective": _NUM,
    "heldout_objective": _NUM,
    "default_heldout_objective": _NUM,
    "cpu_objective": (*_NUM, type(None)),
    "cpu_envelope": (*_NUM, type(None)),
    "rounds": int,
    "population": int,
    "evaluations": int,
    "objective_weights": dict,
    "algo": str,
}


def _validate_v3(row: dict) -> List[str]:
    errs = []
    for k, t in _BASE_V3.items():
        v = row.get(k)
        if v is None or not isinstance(v, t) or isinstance(v, bool):
            errs.append(f"{k}: expected {t}, got {v!r}")
    for k, t in _OPTIONAL_V3.items():
        if k in row and (not isinstance(row[k], t) or isinstance(row[k], bool)):
            errs.append(f"{k}: expected {t}, got {row[k]!r}")
    kind = row.get("kind")
    if isinstance(kind, str):
        required = {
            "tune-candidate": _TUNE_CAND_REQUIRED,
            "tune-round": _TUNE_ROUND_REQUIRED,
            "tune-result": _TUNE_RESULT_REQUIRED,
        }.get(kind)
        if required is None:
            return errs + [f"kind: unknown {kind!r}"]
        for k, t in required.items():
            v = row.get(k)
            if not isinstance(v, t) or (isinstance(v, bool) and t is not bool):
                errs.append(f"{k}: expected {t}, got {v!r}")
    return errs


def _check_telemetry(tel: dict) -> List[str]:
    errs = []
    if tel.get("granularity") not in _TEL_GRANULARITIES:
        errs.append(
            f"telemetry.granularity: expected one of "
            f"{_TEL_GRANULARITIES}, got {tel.get('granularity')!r}"
        )
    if not isinstance(tel.get("phases"), dict):
        errs.append("telemetry.phases: expected an object")
    lat = tel.get("latency")
    if lat is not None:
        for k in ("count", "mean", "max", "p50", "p90", "p99", "buckets"):
            if k not in lat:
                errs.append(f"telemetry.latency.{k}: missing")
        b = lat.get("buckets")
        if isinstance(b, dict) and "le_inf" not in b:
            errs.append("telemetry.latency.buckets.le_inf: missing")
    for k in ("reasons", "rejection_attempts"):
        v = tel.get(k)
        if v is not None and not isinstance(v, dict):
            errs.append(f"telemetry.{k}: expected an object")
    return errs


def _check_fragmentation(frag: dict) -> List[str]:
    errs = []
    for k in ("stranded", "stranded_frac", "frag_index"):
        if not isinstance(frag.get(k), dict):
            errs.append(f"fragmentation.{k}: expected an object")
    for k in ("packing_efficiency",):
        if not isinstance(frag.get(k), _NUM):
            errs.append(f"fragmentation.{k}: expected a number")
    for k in ("nodes_active", "nodes_ideal", "pending"):
        v = frag.get(k)
        if not isinstance(v, int) or isinstance(v, bool):
            errs.append(f"fragmentation.{k}: expected an int")
    return errs


def validate_row(row: dict) -> List[str]:
    """Errors for one parsed row ([] = valid)."""
    errs = []
    schema = row.get("schema")
    if schema is None:
        # v1 (pre-versioning) rows: "ts" + payload only; accepted as-is
        # so old result files keep validating.
        return [] if isinstance(row.get("ts"), _NUM) else ["ts: missing"]
    if schema == 3:
        return _validate_v3(row)
    if schema == 5 and row.get("kind") == "flight":
        return _validate_flight(row)
    if schema == 6 and row.get("kind") == "flight":
        return _validate_flight(
            row, events=_FLIGHT_EVENTS_V6, optional=_OPTIONAL_FLIGHT_V6
        )
    if schema == 7 and row.get("kind") == "flight":
        return _validate_flight(
            row, events=_FLIGHT_EVENTS_V7, optional=_OPTIONAL_FLIGHT_V7
        )
    if schema in (6, 7) and row.get("kind") == "postmortem":
        return _validate_postmortem(row)
    if schema == 7 and row.get("kind") == "query":
        return _validate_query(row, _QUERY_REQUIRED)
    if schema == 7 and row.get("kind") == "query-result":
        return _validate_query(row, _QUERY_RESULT_REQUIRED)
    if schema == 7 and row.get("kind") == "query-error":
        return _validate_query(row, _QUERY_ERROR_REQUIRED)
    if schema in (4, 5, 6, 7):
        for k, t in _OPTIONAL_V4.items():
            if k in row and not isinstance(row[k], t):
                errs.append(f"{k}: expected {t}, got {row[k]!r}")
        if schema in (6, 7):
            for k, t in _OPTIONAL_TRACE.items():
                if k in row and not isinstance(row[k], t):
                    errs.append(f"{k}: expected {t}, got {row[k]!r}")
        if isinstance(row.get("fragmentation"), dict):
            errs.extend(_check_fragmentation(row["fragmentation"]))
        # Fall through: everything else follows the v2 rules.
    elif schema != 2:
        return [f"schema: unknown version {schema!r}"]
    for k, t in _BASE_V2.items():
        v = row.get(k)
        if v is None or (not isinstance(v, t)) or isinstance(v, bool):
            errs.append(f"{k}: expected {t}, got {v!r}")
    kind = row.get("kind")
    if isinstance(kind, str):
        if kind.startswith("replay-"):
            required = _REPLAY_REQUIRED
        elif kind == "whatif-aggregate":
            required = _WHATIF_AGG_REQUIRED
        elif kind == "whatif-scenario":
            required = _WHATIF_SCEN_REQUIRED
        else:
            return errs + [f"kind: unknown {kind!r}"]
        for k, t in required.items():
            v = row.get(k)
            if not isinstance(v, t) or (
                isinstance(v, bool) and t is not bool
            ):
                errs.append(f"{k}: expected {t}, got {v!r}")
    for k, t in _OPTIONAL.items():
        if k in row and not isinstance(row[k], t):
            errs.append(f"{k}: expected {t}, got {row[k]!r}")
    if isinstance(row.get("telemetry"), dict):
        errs.extend(_check_telemetry(row["telemetry"]))
    return errs


def validate_file(path: str) -> List[str]:
    """All errors in a JSONL file, prefixed ``path:lineno:`` ([] = valid)."""
    errs = []
    with open(path) as f:
        for i, line in enumerate(f, 1):
            line = line.strip()
            if not line:
                continue
            try:
                row = json.loads(line)
            except json.JSONDecodeError as e:
                errs.append(f"{path}:{i}: invalid JSON: {e}")
                continue
            if not isinstance(row, dict):
                errs.append(f"{path}:{i}: row is not an object")
                continue
            for e in validate_row(row):
                errs.append(f"{path}:{i}: {e}")
    return errs


def main(argv: Optional[List[str]] = None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if not argv:
        print(__doc__.strip())
        return 2
    all_errs = []
    for path in argv:
        all_errs.extend(validate_file(path))
    for e in all_errs:
        print(e)
    if not all_errs:
        print(
            f"ok: {len(argv)} file(s) validate against schema "
            f"v2/v3/v4/v5/v6/v7"
        )
    return 1 if all_errs else 0


if __name__ == "__main__":
    sys.exit(main())
