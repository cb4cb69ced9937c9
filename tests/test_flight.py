"""Round 16: the flight recorder (sim.flight) and its bit-parity pin.

The contract under test: the recorder is a pure OBSERVER. Turning it on
changes no placement, no deterministic JSONL byte, and no checkpoint
blob byte across every engine mode it instruments — plain, pagedWaves,
kube-boundary — including a cross-mode resume. Its own stream is
schema-v6 valid and byte-stable for a fixed seed under
KSIM_DETERMINISTIC_JSONL. Pager stall counters are pinned on a crafted
slow-page trace (a sleeping fetch) without any engine in the loop.
"""

import hashlib
import json
import os
import sys
import time

import numpy as np
import pytest

from kubernetes_simulator_tpu.framework.framework import FrameworkConfig
from kubernetes_simulator_tpu.models.encode import encode
from kubernetes_simulator_tpu.sim.flight import (
    FLIGHT_WALL_FIELDS,
    FlightRecorder,
    FlightRecorderConfig,
    read_stream,
    rss_peak_mib,
)
from kubernetes_simulator_tpu.sim.jax_runtime import (
    JaxReplayEngine,
    _PodPager,
)
from kubernetes_simulator_tpu.sim.synthetic import make_cluster, make_workload

sys.path.insert(
    0,
    os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "scripts")
    ),
)


def _case(n_nodes=24, n_pods=160, seed=7):
    cluster = make_cluster(n_nodes, seed=seed, taint_fraction=0.2)
    pods, _ = make_workload(
        n_pods, seed=seed, with_affinity=True, with_spread=True,
        with_tolerations=True, gang_fraction=0.1, gang_size=4,
        duration_mean=40.0,
    )
    return encode(cluster, pods)


@pytest.fixture(scope="module")
def case():
    return _case()


# Engine-mode matrix: kwargs beyond (ec, ep, cfg, chunk_waves=4).
MODES = {
    "plain": {},
    "pagedWaves": {"paged": True},
    "kube-boundary": {"preemption": "kube", "retry_buffer": 64},
}


def _stable_summary(res):
    row = dict(res.summary())
    for k in ("wall_clock_s", "placements_per_sec"):
        row.pop(k, None)
    return row


@pytest.mark.parametrize("mode", sorted(MODES))
def test_recorder_bit_parity(case, tmp_path, mode):
    """Recorder on vs off: assignments and stable summaries identical in
    every engine mode — the recorder never touches a device program."""
    ec, ep = case
    kw = dict(MODES[mode], chunk_waves=4, telemetry="off")
    off = JaxReplayEngine(ec, ep, FrameworkConfig(), **kw).replay()
    on = JaxReplayEngine(
        ec, ep, FrameworkConfig(),
        flight_recorder=str(tmp_path / f"{mode}.jsonl"), **kw,
    ).replay()
    np.testing.assert_array_equal(
        on.assignments, off.assignments,
        err_msg=f"{mode}: recorder-on assignments diverged",
    )
    assert _stable_summary(on) == _stable_summary(off)
    rows = read_stream(str(tmp_path / f"{mode}.jsonl"))
    assert rows and rows[0]["event"] == "start"
    assert rows[-1]["event"] == "end"
    assert any(r["event"] == "chunk" for r in rows)


def test_recorder_checkpoint_blobs_identical_and_cross_mode_resume(
    case, tmp_path
):
    """Checkpoint blobs byte-identical recorder on/off, and a blob
    written recorder-ON resident resumes recorder-OFF under
    pagedWaves (cross-mode resume) to the same end state."""
    ec, ep = case
    ref = JaxReplayEngine(
        ec, ep, FrameworkConfig(), chunk_waves=4, telemetry="off",
    ).replay()
    digests = {}
    for tag, rec in (("off", None), ("on", str(tmp_path / "fl.jsonl"))):
        p = tmp_path / f"ckpt_{tag}.npz"
        res = JaxReplayEngine(
            ec, ep, FrameworkConfig(), chunk_waves=4,
            telemetry="off", flight_recorder=rec,
        ).replay(checkpoint_path=str(p), checkpoint_every=2)
        np.testing.assert_array_equal(res.assignments, ref.assignments)
        digests[tag] = hashlib.sha256(p.read_bytes()).hexdigest()
    assert digests["on"] == digests["off"], (
        "flight recorder changed a checkpoint blob byte — it must be a "
        "pure observer"
    )
    # Recorder-on checkpoint blob events carry the real blob size.
    rows = read_stream(str(tmp_path / "fl.jsonl"))
    cks = [r for r in rows if r["event"] == "checkpoint"]
    assert cks and all(
        r["ckpt_bytes"] == os.path.getsize(tmp_path / "ckpt_on.npz")
        for r in cks[-1:]
    )
    # Cross-mode resume: resident+recorded blob under a paged engine.
    res = JaxReplayEngine(
        ec, ep, FrameworkConfig(), chunk_waves=4, paged=True,
        telemetry="off",
    ).replay(checkpoint_path=str(tmp_path / "ckpt_on.npz"), resume=True)
    np.testing.assert_array_equal(res.assignments, ref.assignments)


def test_deterministic_jsonl_parity_and_byte_stability(
    case, tmp_path, monkeypatch
):
    """Under KSIM_DETERMINISTIC_JSONL: (a) the replay-result JSONL is
    byte-identical recorder on/off, (b) two recorder streams of the same
    seed are byte-identical to each other (every wall-derived field is
    zeroed, counts/virtual-times stay)."""
    from kubernetes_simulator_tpu.utils.metrics import JsonlWriter, replay_row

    monkeypatch.setenv("KSIM_DETERMINISTIC_JSONL", "1")
    ec, ep = case
    blobs = {}
    for tag, rec in (
        ("off", None),
        ("on1", str(tmp_path / "fl1.jsonl")),
        ("on2", str(tmp_path / "fl2.jsonl")),
    ):
        res = JaxReplayEngine(
            ec, ep, FrameworkConfig(), chunk_waves=4, telemetry="off",
            flight_recorder=rec,
        ).replay()
        p = tmp_path / f"res_{tag}.jsonl"
        with JsonlWriter(str(p)) as w:
            w.write(replay_row("replay-jax", res))
        blobs[tag] = p.read_bytes()
    assert blobs["off"] == blobs["on1"] == blobs["on2"]
    fl1 = (tmp_path / "fl1.jsonl").read_bytes()
    fl2 = (tmp_path / "fl2.jsonl").read_bytes()
    assert fl1 == fl2, "fixed-seed recorder streams are not byte-stable"
    for row in read_stream(str(tmp_path / "fl1.jsonl")):
        for k in FLIGHT_WALL_FIELDS:
            if k in row:
                assert row[k] == 0.0, f"{row['event']}: {k} not scrubbed"
        for v in (row.get("phases") or {}).values():
            assert v == 0.0


def test_flight_stream_validates_against_schema_v6(case, tmp_path):
    from check_metrics_schema import validate_file  # noqa: E402

    ec, ep = case
    path = str(tmp_path / "fl.jsonl")
    JaxReplayEngine(
        ec, ep, FrameworkConfig(), chunk_waves=4,
        paged=False, telemetry="summary", flight_recorder=path,
    ).replay()
    assert validate_file(path) == []
    rows = read_stream(path)
    assert all(r["schema"] == 7 for r in rows)
    # The selection-exchange fields went with node sharding (PR 29): no
    # row carries one, and the recorder takes no such argument.
    cks = [r for r in rows if r["event"] == "chunk"]
    assert cks and not any(k.startswith("exchange_") for r in rows for k in r)
    assert not any(k.startswith("exchange_") for k in FLIGHT_WALL_FIELDS)
    with pytest.raises(TypeError, match="exchange_probe_s"):
        FlightRecorder(FlightRecorderConfig(path=path)).chunk(
            0, exchange_probe_s=0.1
        )


def test_pager_stall_counters_on_crafted_slow_page_trace():
    """Stall accounting pinned without an engine: a sleeping fetch, a
    prefetch-miss access pattern, exact stall counts and a wall lower
    bound. The counters are the recorder's pager evidence."""
    DELAY = 0.02
    fetched = []

    def slow_fetch(ci):
        fetched.append(ci)
        time.sleep(DELAY)
        return ci * 10

    pager = _PodPager(slow_fetch)
    assert (pager.depth, pager.stalls, pager.prefetches) == (0, 0, 0)
    # Chunk 0: nothing prefetched — a synchronous stall.
    assert pager.get(0) == 0
    assert pager.stalls == 1 and pager.stall_s >= DELAY
    assert pager.last_stall_s >= DELAY
    # Steady state: prefetch hides the fetch — no new stalls.
    pager.prefetch(1)
    assert pager.depth == 1 and pager.prefetches == 1
    assert pager.get(1) == 10
    assert pager.stalls == 1 and pager.depth == 0
    # Resume-style jump (prefetched 2, asked for 5): a second stall.
    pager.prefetch(2)
    assert pager.get(5) == 50
    assert pager.stalls == 2 and pager.stall_s >= 2 * DELAY
    assert fetched == [0, 1, 2, 5]


@pytest.mark.slow
def test_recorder_page_events_and_stall_rows(case, tmp_path):
    """A paged replay's recorder stream carries the pager gauges on
    chunk rows and a page event for the cold-start stall."""
    ec, ep = case
    path = str(tmp_path / "fl.jsonl")
    JaxReplayEngine(
        ec, ep, FrameworkConfig(), chunk_waves=4, paged=True,
        telemetry="off", flight_recorder=path,
    ).replay()
    rows = read_stream(path)
    pages = [r for r in rows if r["event"] == "page"]
    assert pages, "cold-start prefetch miss did not emit a page event"
    assert pages[0]["pager_stalls"] >= 1
    cks = [r for r in rows if r["event"] == "chunk"]
    assert all("pager_stalls" in r and "pager_depth" in r for r in cks)


def test_recorder_config_resolve_and_off_by_default(case):
    ec, ep = case
    eng = JaxReplayEngine(ec, ep, FrameworkConfig(), chunk_waves=4)
    assert eng.flight_recorder is None  # OFF by default
    assert FlightRecorderConfig.resolve(None) is None
    cfg = FlightRecorderConfig.resolve("x.jsonl")
    assert isinstance(cfg, FlightRecorderConfig) and cfg.every == 1
    assert FlightRecorderConfig.resolve(cfg) is cfg
    with pytest.raises(ValueError, match="flight_recorder"):
        FlightRecorderConfig.resolve(123)
    assert rss_peak_mib() > 0.0


def test_recorder_every_cadence(tmp_path):
    """every=N thins chunk rows to the cadence; start/end always emit."""
    rec = FlightRecorder(
        FlightRecorderConfig(path=str(tmp_path / "f.jsonl"), every=3)
    )
    for ci in range(7):
        rec.chunk(ci, dispatched=ci)
    rec.close()
    rows = read_stream(str(tmp_path / "f.jsonl"))
    assert [r["chunk"] for r in rows if r["event"] == "chunk"] == [0, 3, 6]
    assert rows[0]["event"] == "start" and rows[-1]["event"] == "end"


def test_fleetwatch_flight_lines_tolerant(tmp_path):
    """dcn_launch --watch --flight: renders recorder gauges per process
    and tolerates a missing stream / torn tail entirely."""
    from dcn_launch import FleetWatch  # noqa: E402

    fl = tmp_path / "fl.jsonl"
    w = FleetWatch(str(tmp_path), 2, flight_path=str(fl))
    assert w.flight_lines() == []  # no stream yet: silent
    fl.write_text(
        json.dumps({"kind": "flight", "event": "chunk", "chunk": 3,
                    "rolling_pps": 1234.5, "pager_stalls": 2,
                    "rss_peak_mib": 300.0})
        + "\n"
    )
    (tmp_path / "fl.jsonl.p1").write_text('{"torn json\n')
    lines = w.flight_lines()
    assert len(lines) == 1
    assert "p0 flight chunk 3" in lines[0]
    assert "1234pps" in lines[0] or "1235pps" in lines[0]
    assert "stalls=2" in lines[0] and "rss=300MiB" in lines[0]
    # Byte cursor: nothing new → nothing repeated.
    assert w.flight_lines() == []
    # Recorder off entirely: FleetWatch without a flight path is silent.
    assert FleetWatch(str(tmp_path), 2).flight_lines() == []


def test_fleetwatch_events_tail_survives_truncation(tmp_path):
    """Round 21: the --watch events tail consumes only complete lines,
    and a supervisor relaunch truncating events.jsonl underneath the
    tail resets the byte cursor instead of seeking past EOF."""
    from dcn_launch import FleetWatch  # noqa: E402

    ev = tmp_path / "events.jsonl"
    w = FleetWatch(str(tmp_path), 2)
    assert w.events() == []  # no file yet: silent

    ev.write_text(json.dumps({"event": "lease", "pid": 0, "block": 3}) + "\n")
    got = w.events()
    assert [e["event"] for e in got] == ["lease"]
    # Mid-write partial final line: held back until it completes.
    with open(ev, "a") as f:
        f.write('{"event": "steal", "pid": 1, "blo')
    assert w.events() == []
    with open(ev, "a") as f:
        f.write('ck": 3, "from": 0, "gen": 1}\n')
    assert [e["event"] for e in w.events()] == ["steal"]
    # Supervisor relaunch truncates the file to a new epoch's head: the
    # shrink resets the cursor and the new epoch's rows surface.
    ev.write_text(
        json.dumps({"event": "journal_adopt", "pid": 0, "block": 3,
                    "from": 1}) + "\n"
    )
    assert [e["event"] for e in w.events()] == ["journal_adopt"]


def test_fleetwatch_line_shows_generations_and_life(tmp_path):
    """Round 21 --watch extras: recovery claim generation, work-queue
    lease generation, and the supervised-restart life counter."""
    import time as _time

    from dcn_launch import FleetWatch  # noqa: E402

    w = FleetWatch(str(tmp_path), 2)
    now = _time.time()
    line = w.line({
        0: {"state": "recover", "recovering_for": 1, "recover_gen": 2,
            "chunk": 4, "total_chunks": 8, "t": now, "restart": 1},
        1: {"state": "run", "wq_block": 5, "wq_gen": 1,
            "leased_blocks": 1, "chunk": 6, "total_chunks": 8, "t": now},
    })
    assert "recovering-p1@g2" in line
    assert "life=1" in line
    assert "run@b5.g1" in line


def test_fleetwatch_event_line_renders_round21_kinds():
    """event_line covers the checkpoint and faultline trail kinds the
    round-21 black box stamps into the KV mirror."""
    from dcn_launch import FleetWatch  # noqa: E402

    el = FleetWatch.event_line
    assert "loads p1's checkpoint" in el(
        {"event": "ckpt_load", "by": 2, "pid": 1, "cursor": 4})
    assert "FALLS BACK" in el(
        {"event": "ckpt_fallback", "by": 2, "pid": 1})
    assert "FAULT-KILLED" in el(
        {"event": "fault_kill", "pid": 1, "state": "run"})
    assert "fault error injected on wq/0/lease/3" in el(
        {"event": "fault_inject", "pid": 1, "class": "error",
         "key": "wq/0/lease/3"})
    assert "fault slow_io injected" in el(
        {"event": "fault_slow", "pid": 1, "class": "slow_io"})
