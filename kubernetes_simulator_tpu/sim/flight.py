"""Flight recorder (round 16): streaming in-flight observability for
long replays — one JSONL event per chunk boundary (plus per page-stall /
checkpoint / boundary-fold) so an hour-scale Borg-headline run is
watchable while it executes and attributable afterwards.

Every row carries: virtual time at the chunk boundary, placements /
slots dispatched so far, a rolling placements-per-second gauge,
PHASE_NAMES phase-timer deltas since the previous event, pager state
(prefetch depth, cumulative stall count, stall wall-time), checkpoint
blob bytes, and memory residency (the ``replicated_resident_bytes``
estimate plus the host RSS high-water from ``getrusage``).

The recorder is OFF by default and bit-parity pinned
(tests/test_flight.py): placements, deterministic JSONL and checkpoint
blobs are identical with the recorder on or off — it never changes a
device program, a fold ordering or a checkpoint payload; it only reads
clocks and counters at chunk cadence. Rows are written through
:class:`utils.metrics.JsonlWriter` (schema-stamped, process-stamped
under DCN); ``KSIM_DETERMINISTIC_JSONL=1`` zeroes every wall-clock-
derived field (``FLIGHT_WALL_FIELDS``) so a fixed-seed recorder stream
is byte-stable — the flight twin of the replay-row scrub.

Consumer: ``scripts/dcn_launch.py --watch`` (live recorder lines).
"""

from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass
from typing import Dict, Optional

from .telemetry import PhaseTimers

# Wall-clock-derived row fields zeroed under KSIM_DETERMINISTIC_JSONL
# (kept PRESENT as numbers so schema-v5 validation still sees them).
# Values inside the "phases" delta dict are zeroed too — phase timers
# are perf_counter deltas. Everything else in a flight row (chunk
# cursor, virtual time, dispatch/placement counts, pager stall/
# invalidation COUNTS, prefetch depth, checkpoint blob bytes, residency
# estimate) is deterministic for a fixed seed and stays.
# ``pager_waits`` is a COUNT but rides this list anyway: whether a
# threaded prefetch finished before ``get`` asked is a race outcome
# (round 19), unlike miss/invalidation counts which are structural.
FLIGHT_WALL_FIELDS = (
    "wall_s",
    "rolling_pps",
    "stall_s",
    # Round 21: the renewal age observed at a steal/speculate decision
    # is wall-clock evidence (the threshold it exceeded is config and
    # stays). Trace stamps (trace/span/parent/link) are handled in
    # _emit: dropped entirely in deterministic mode so streams are
    # byte-identical with KSIM_TRACE on and off.
    "renew_age_s",
    "pager_stall_s",
    "pager_prefetch_s",
    "pager_wait_s",
    "pager_waits",
    "ckpt_wall_s",
    "rss_peak_mib",
    # Round 22: serving-plane query rows carry the batch's wall latency
    # (cold-vs-warm evidence). Queue depth / occupancy / warm flag are
    # structural and stay.
    "latency_s",
)

# Rolling placements/sec window: events, not seconds — chunk cadence is
# workload-dependent and the gauge should react within a few chunks.
_ROLL_WINDOW = 8


def rss_peak_mib() -> float:
    """Host RSS high-water in MiB (``getrusage`` ``ru_maxrss``; KiB on
    Linux, bytes on macOS). 0.0 where the resource module is absent —
    never raises, the recorder must not take a run down."""
    try:
        import resource
        import sys

        peak = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
        scale = 2**20 if sys.platform == "darwin" else 2**10
        return round(peak * scale / 2**20, 1)
    except Exception:
        return 0.0


@dataclass
class FlightRecorderConfig:
    """``flightRecorder:`` YAML section / ``flight_recorder=`` engine
    kwarg. ``path`` is the JSONL sink (suffixed ``.p<pid>`` per process
    under DCN, like every other sink); ``every`` is the chunk cadence
    (1 = every chunk boundary; page/checkpoint/fold events always
    emit)."""

    path: str
    every: int = 1

    @classmethod
    def resolve(cls, v) -> Optional["FlightRecorderConfig"]:
        """None stays None (recorder off — the default); a path string
        becomes a config; a config or live recorder passes through."""
        if v is None or isinstance(v, (FlightRecorderConfig, FlightRecorder)):
            return v
        if isinstance(v, str):
            return cls(path=v)
        raise ValueError(
            f"flight_recorder: expected a path, FlightRecorderConfig or "
            f"None, got {v!r}"
        )


class FlightRecorder:
    """Streaming JSONL emitter for one replay. Construct via
    :meth:`open` (engines) or directly with a config; call
    :meth:`chunk` once per chunk boundary and :meth:`page` /
    :meth:`checkpoint` / :meth:`fold` as those events occur, then
    :meth:`close`. Owns a :class:`PhaseTimers` so a telemetry-off run
    still gets phase deltas (the engine routes its ``_tick`` here when
    no collector exists)."""

    def __init__(self, cfg: FlightRecorderConfig, meta: Optional[dict] = None):
        from ..parallel import dcn
        from ..utils.metrics import JsonlWriter

        self.cfg = cfg
        self.phases = PhaseTimers()  # used when telemetry is off
        self._meta = dict(meta or {})
        self._writer = JsonlWriter(dcn.output_path_for_process(cfg.path))
        self._t0 = time.perf_counter()
        self._last_phases: Dict[str, float] = {}
        self._roll: deque = deque(maxlen=_ROLL_WINDOW)  # (wall, progressed)
        self._events = 0
        self._emit(
            {
                "event": "start",
                "chunk": -1,
                "wall_s": 0.0,
                "rss_peak_mib": rss_peak_mib(),
                **self._meta,
            }
        )
        # Fleet-event subscription (round 18): lease/steal/speculation/
        # claim events from parallel.dcn land in this stream as "fleet"
        # rows, interleaved with the chunk rows — the straggler tests pin
        # the trail here. Unregistered on close; a raising sink is
        # dropped by dcn itself.
        self._fleet_sink = self.fleet_event
        dcn.EVENT_SINKS.append(self._fleet_sink)
        self._dcn_mod = dcn

    @classmethod
    def open(cls, spec, meta: Optional[dict] = None) -> Optional["FlightRecorder"]:
        """Engine entry point: ``spec`` is whatever the ``flight_recorder``
        kwarg carried (None / path / config / live recorder). Returns a
        live recorder or None (off). A recorder instance passes through
        so callers can share one across resume legs."""
        cfg = FlightRecorderConfig.resolve(spec)
        if cfg is None:
            return None
        if isinstance(cfg, FlightRecorder):
            return cfg
        return cls(cfg, meta=meta)

    # -- event emitters ----------------------------------------------------

    def chunk(
        self,
        ci: int,
        t_virtual: Optional[float] = None,
        dispatched: Optional[int] = None,
        placed: Optional[int] = None,
        phase_acc: Optional[Dict[str, float]] = None,
        pager=None,
        ckpt_publish: Optional[dict] = None,
        kv_retry: Optional[dict] = None,
    ) -> None:
        """One chunk-boundary row. ``phase_acc`` is the CUMULATIVE phase
        accumulator (the collector's or this recorder's own) — the row
        carries deltas since the previous chunk row. ``pager`` is a
        ``_PodPager`` (or anything with stalls/stall_s/prefetches/depth).
        ``kv_retry`` (round 17) is the chunk's KV retry delta — retries
        burned, give-ups, backoff wall — attributing coordination-plane
        flakiness (real or faultline-injected) to the chunk it hit."""
        self._events += 1
        if self.cfg.every > 1 and (ci % self.cfg.every) != 0:
            return
        wall = time.perf_counter() - self._t0
        acc = dict(phase_acc if phase_acc is not None else self.phases.acc)
        delta = {
            k: round(v - self._last_phases.get(k, 0.0), 6)
            for k, v in sorted(acc.items())
        }
        self._last_phases = acc
        progressed = placed if placed is not None else dispatched
        rolling = 0.0
        if progressed is not None:
            self._roll.append((wall, int(progressed)))
            if len(self._roll) >= 2:
                (w0, p0), (w1, p1) = self._roll[0], self._roll[-1]
                if w1 > w0:
                    rolling = (p1 - p0) / (w1 - w0)
        row = {
            "event": "chunk",
            "chunk": int(ci),
            "wall_s": round(wall, 6),
            "rolling_pps": round(rolling, 1),
            "phases": delta,
            "rss_peak_mib": rss_peak_mib(),
        }
        if t_virtual is not None:
            import math

            row["t_virtual"] = (
                round(float(t_virtual), 6)
                if math.isfinite(float(t_virtual))
                else None
            )
        if dispatched is not None:
            row["dispatched"] = int(dispatched)
        if placed is not None:
            row["placed"] = int(placed)
        if pager is not None:
            row["pager_depth"] = int(getattr(pager, "depth", 0))
            row["pager_stalls"] = int(getattr(pager, "stalls", 0))
            row["pager_stall_s"] = round(
                float(getattr(pager, "stall_s", 0.0)), 6
            )
            # Round-19 overlap ledger: the prefetch fetches' own wall
            # (hidden when the pager thread is on, loop-exposed when
            # off), blocking waits on in-flight prefetches, and staged
            # pages invalidated by resume jumps. Always present so the
            # stream is byte-identical threaded on vs off under the
            # deterministic scrub.
            row["pager_prefetch_s"] = round(
                float(getattr(pager, "prefetch_wall_s", 0.0)), 6
            )
            row["pager_waits"] = int(getattr(pager, "waits", 0))
            row["pager_wait_s"] = round(
                float(getattr(pager, "wait_s", 0.0)), 6
            )
            row["pager_invalidations"] = int(
                getattr(pager, "invalidations", 0)
            )
        if ckpt_publish:
            row["dcn_publish"] = dict(ckpt_publish)
        if kv_retry:
            row["dcn_retry"] = dict(kv_retry)
        self._emit(row)

    def page(
        self, ci: int, stall_s: float, stalls: int,
        invalidations: Optional[int] = None,
    ) -> None:
        """A pager prefetch MISS (the synchronous fetch the prefetch
        exists to hide) — emitted per stall, they are the exceptional
        case the report looks for. ``invalidations`` (round 19) rides
        along when a resume jump discarded the staged page: previously
        that surfaced as a plain stall, under-reporting what the pager
        threw away."""
        row = {
            "event": "page",
            "chunk": int(ci),
            "stall_s": round(float(stall_s), 6),
            "pager_stalls": int(stalls),
            "wall_s": round(time.perf_counter() - self._t0, 6),
        }
        if invalidations:
            row["pager_invalidations"] = int(invalidations)
        self._emit(row)

    def checkpoint(
        self, ci: int, nbytes: int, wall_s: float, sink: str = "local"
    ) -> None:
        """A checkpoint left the engine: ``sink`` is "local" (npz blob on
        disk) or "dcn" (KV publication). ``nbytes`` is the blob size —
        deterministic, so it survives the JSONL scrub."""
        self._emit(
            {
                "event": "checkpoint",
                "chunk": int(ci),
                "ckpt_bytes": int(nbytes),
                "ckpt_wall_s": round(float(wall_s), 6),
                "ckpt_sink": sink,
                "wall_s": round(time.perf_counter() - self._t0, 6),
            }
        )

    def fold(self, ci: int, wall_s: float) -> None:
        """A boundary-mode mirror fold resolved (the host-side D2H +
        bookkeeping the lazy path tries to overlap)."""
        self._emit(
            {
                "event": "boundary_fold",
                "chunk": int(ci),
                "stall_s": round(float(wall_s), 6),
                "wall_s": round(time.perf_counter() - self._t0, 6),
            }
        )

    def query(
        self,
        batch: int,
        queued: int,
        occupancy: float,
        warm: bool,
        latency_s: float,
        engines: int,
    ) -> None:
        """One serving-plane batch resolved (round 22, sim.service): how
        many queries coalesced, the scenario-axis occupancy, whether the
        pool answered warm (value swap against a resident executable) or
        cold (fresh compile), and the batch wall. Everything but
        ``latency_s`` is deterministic for a fixed query sequence."""
        self._emit(
            {
                "event": "query",
                "chunk": -1,
                "batch": int(batch),
                "queue_depth": int(queued),
                "batch_occupancy": round(float(occupancy), 4),
                "warm": bool(warm),
                "engines": int(engines),
                "latency_s": round(float(latency_s), 6),
                "wall_s": round(time.perf_counter() - self._t0, 6),
            }
        )

    def fleet_event(self, event: dict) -> None:
        """One fleet coordination event (parallel.dcn._mirror_event):
        lease / steal / speculate / block_done / spec_lost / join /
        claim / recovered, plus the round-20 durability events —
        journal_adopt (a completed block adopted from the durable
        journal without re-execution) and journal_resume (a checkpoint
        restore whose winning cursor came from the journal rather than
        the live KV store). Round 21 adds ckpt_load / ckpt_fallback and
        the faultline fault_* kinds, each stamped with its causal trace
        identity (trace/span/parent — parallel.trace) by dcn before this
        sink sees it. Flattened into the row — every field but the wall
        clocks is deterministic for a fixed schedule."""
        ev = dict(event)
        # ckpt_publish events name their kind under "kind" (pinned by
        # test_durable); pop BOTH so the payload can never shadow the
        # row's own kind="flight" stamp (round 21 fix — shadowed rows
        # were invisible to read_stream).
        kind = ev.pop("event", None) or ev.pop("kind", None) or "?"
        ev.pop("kind", None)
        self._emit(
            {
                "event": "fleet",
                "fleet_event": str(kind),
                "chunk": -1,
                "wall_s": round(time.perf_counter() - self._t0, 6),
                **ev,
            }
        )

    def close(self, summary: Optional[dict] = None) -> None:
        try:
            self._dcn_mod.EVENT_SINKS.remove(self._fleet_sink)
        except (AttributeError, ValueError):
            pass
        if self._writer is None:
            return
        row = {
            "event": "end",
            "chunk": -1,
            "wall_s": round(time.perf_counter() - self._t0, 6),
            "rss_peak_mib": rss_peak_mib(),
            "events": self._events,
        }
        if summary:
            row.update(summary)
        self._emit(row)
        self._writer.close()
        self._writer = None

    # -- plumbing ----------------------------------------------------------

    def _emit(self, row: dict) -> None:
        from ..utils.metrics import deterministic_jsonl

        if self._writer is None:
            return
        row = {"kind": "flight", **row}
        if deterministic_jsonl():
            for k in FLIGHT_WALL_FIELDS:
                if k in row:
                    row[k] = 0.0
            if isinstance(row.get("phases"), dict):
                row["phases"] = {k: 0.0 for k in row["phases"]}
            # Round 19: with the background publisher and the retrying
            # publisher thread interleaving KV traffic with the loop,
            # WHICH chunk row a publish/retry delta lands on is a race
            # outcome — every numeric in these blocks is scrubbed, not
            # just the ``_s`` walls.
            for blk in ("dcn_publish", "dcn_retry"):
                if isinstance(row.get(blk), dict):
                    row[blk] = {
                        k: (
                            (0.0 if isinstance(v, float) else 0)
                            if isinstance(v, (int, float))
                            and not isinstance(v, bool)
                            else v
                        )
                        for k, v in row[blk].items()
                    }
            # Round 21: trace identity fields are deterministic values
            # but their PRESENCE depends on KSIM_TRACE — drop them so
            # deterministic streams are byte-identical stamping-on vs
            # stamping-off (the parity bar); live streams keep them.
            for k in ("trace", "span", "parent", "link"):
                row.pop(k, None)
        try:
            self._writer.write(row)
        except OSError:
            # Telemetry must never take the replay down mid-flight; a
            # full disk degrades to a truncated stream, not a crash.
            self._writer = None


def read_stream(path: str):
    """Parsed flight rows from ``path`` (list of dicts, malformed lines
    skipped)."""
    import json

    rows = []
    try:
        with open(path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    row = json.loads(line)
                except ValueError:
                    continue
                if isinstance(row, dict) and row.get("kind") == "flight":
                    rows.append(row)
    except OSError:
        return []
    return rows
