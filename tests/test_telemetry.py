"""Telemetry layer (SURVEY.md §5): per-pod latency histograms,
filter-rejection attribution, virtual-time series, phase timers and the
Chrome-trace exporter.

The cross-engine contracts under test: at W=1 / C=1 on queue-trivial
traces the CPU event engine and the device path produce bit-identical
latency summaries and per-episode rejection reasons (the device is
chunk-granular but the crafted instants coincide); no granularity
changes a device program; telemetry state never leaks into checkpoint
blobs."""

import json

import numpy as np
import pytest

from kubernetes_simulator_tpu.framework.framework import FrameworkConfig
from kubernetes_simulator_tpu.models.core import Cluster, Node, Pod, Taint
from kubernetes_simulator_tpu.models.encode import encode
from kubernetes_simulator_tpu.sim.jax_runtime import JaxReplayEngine
from kubernetes_simulator_tpu.sim.runtime import CpuReplayEngine
from kubernetes_simulator_tpu.sim.synthetic import make_chaos_timeline
from kubernetes_simulator_tpu.sim.telemetry import (
    PHASE_NAMES,
    TelemetryConfig,
    latency_summary,
    write_chrome_trace,
)
from kubernetes_simulator_tpu.sim.whatif import Scenario, WhatIfEngine

FIT_ONLY = lambda: FrameworkConfig(plugins=[{"name": "NodeResourcesFit"}])


def _light_trace(num_pods=28, num_nodes=5, duration=30.0, seed=None):
    """Queue-trivial parity envelope (tests/test_chaos.py twin)."""
    rng = np.random.default_rng(seed) if seed is not None else None
    nodes = [Node(f"n{i}", {"cpu": 8.0}) for i in range(num_nodes)]
    pods = []
    for i in range(num_pods):
        d = duration if rng is None else float(rng.integers(30, 61))
        pods.append(
            Pod(f"p{i}", requests={"cpu": 1.0}, arrival_time=float(i),
                duration=d)
        )
    return encode(Cluster(nodes=nodes), pods)


# -- config / units -------------------------------------------------------


def test_granularity_validation():
    assert TelemetryConfig.resolve(None).granularity == "summary"
    assert TelemetryConfig.resolve("off").enabled is False
    assert TelemetryConfig.resolve("series").want_series
    assert not TelemetryConfig.resolve("series").want_timeline
    assert TelemetryConfig.resolve("timeline").want_timeline
    with pytest.raises(ValueError, match="granularity"):
        TelemetryConfig.resolve("verbose")


def test_latency_summary_exact():
    s = latency_summary(3, [0.5, 1.0, 4.0, 600.0])
    assert s["count"] == 7
    assert s["max"] == 600.0
    # method="lower" quantiles are exact data values (sorted multiset is
    # [0, 0, 0, 0.5, 1, 4, 600]; p99 index floors to 4.0 at n=7).
    assert s["p50"] == 0.5
    assert s["p99"] == 4.0
    assert s["buckets"]["le_0"] == 3
    assert s["buckets"]["le_0.5"] == 4
    assert s["buckets"]["le_4"] == 6
    assert s["buckets"]["le_512"] == 6  # 600 overflows every finite edge
    assert s["buckets"]["le_inf"] == 7
    assert latency_summary(0, []) is None


# -- engine off/summary behavior -----------------------------------------


def test_off_granularity_yields_none():
    ec, ep = _light_trace(num_pods=6, num_nodes=2)
    assert CpuReplayEngine(ec, ep, FIT_ONLY(), telemetry="off").replay(
    ).telemetry is None
    assert JaxReplayEngine(
        ec, ep, FIT_ONLY(), wave_width=1, chunk_waves=1, telemetry="off"
    ).replay().telemetry is None


def test_default_summary_attached_both_engines():
    ec, ep = _light_trace(num_pods=6, num_nodes=2)
    for res in (
        CpuReplayEngine(ec, ep, FIT_ONLY()).replay(),
        JaxReplayEngine(ec, ep, FIT_ONLY(), wave_width=1,
                        chunk_waves=1).replay(),
    ):
        t = res.telemetry
        assert t is not None and t.granularity == "summary"
        assert t.latency["count"] == res.placed
        assert t.reasons is None  # series-only signal
        assert t.phases  # timers ran
        assert "telemetry" in res.summary()


def test_phase_timer_names_stable():
    """The instrumented phase names are API — the flight stream's and the
    benchmark's consumers attribute wall-clock by these exact strings. The
    canonical tuple is PHASE_NAMES; a boundary-mode device replay must
    emit exactly that set (a rename or a new un-registered phase fails
    here first)."""
    assert PHASE_NAMES == (
        "stage", "dispatch", "device_wait", "boundary_fold", "host_mirror",
        "gather", "handback",
    )
    ec, ep = _light_trace(duration=10.0)  # releases fire inside the run
    res = JaxReplayEngine(
        ec, ep, FIT_ONLY(), wave_width=1, chunk_waves=1, preemption="kube",
        retry_buffer=64,
    ).replay()
    # "handback" is the what-if device-release path's alone
    assert set(res.telemetry.phases) == set(PHASE_NAMES) - {"handback"}


# -- rejection attribution parity (plain path, host mirror) ---------------


def _reject_trace(num_pods=10):
    """n0 (cpu=2) fills after two pods; n1 is big but tainted NoSchedule.
    Every later pod fails with a two-plugin breakdown: NodeResourcesFit
    is charged n0 (first in Filter order), TaintToleration n1."""
    nodes = [
        Node("n0", {"cpu": 2.0}),
        Node("n1", {"cpu": 100.0},
             taints=[Taint("dedicated", "infra", "NoSchedule")]),
    ]
    pods = [
        Pod(f"p{i}", requests={"cpu": 1.0}, arrival_time=float(i))
        for i in range(num_pods)
    ]
    return encode(Cluster(nodes=nodes), pods)


def test_plain_rejection_attribution_matches_cpu():
    """The plain path's first-reject counts (series granularity: the CPU
    framework's filter chain over a host mirror of the device program's
    answers) bit-match the CPU event engine's per-episode reasons at
    W=1/C=1."""
    ec, ep = _reject_trace()
    cfg = FrameworkConfig()
    cpu = CpuReplayEngine(ec, ep, cfg, telemetry="series").replay()
    dev = JaxReplayEngine(
        ec, ep, cfg, wave_width=1, chunk_waves=1, telemetry="series",
    ).replay()
    np.testing.assert_array_equal(cpu.assignments, dev.assignments)
    assert cpu.telemetry.reasons == dev.telemetry.reasons
    assert cpu.telemetry.reasons == {
        "NodeResourcesFit": 8, "TaintToleration": 8,
    }
    # Episode semantics: CPU backoff retries grow attempts, never reasons.
    assert sum(cpu.telemetry.rejection_attempts.values()) >= sum(
        cpu.telemetry.reasons.values()
    )
    # Plain-path device failures are terminal: attempts == reasons.
    assert dev.telemetry.rejection_attempts == dev.telemetry.reasons
    assert cpu.telemetry.latency == dev.telemetry.latency


@pytest.mark.parametrize("gran", ["summary", "series", "timeline"])
def test_no_granularity_builds_a_second_chunk_program(gran):
    """Every granularity runs the engine's ONE chunk program (bench
    safety): one compiled executable after the replay, no other jitted
    chunk function on the engine, and the placements of the off-telemetry
    run."""
    from kubernetes_simulator_tpu.sim.jax_runtime import compiled_cache_size

    ec, ep = _reject_trace()
    eng = JaxReplayEngine(
        ec, ep, FrameworkConfig(), wave_width=1, chunk_waves=1,
        telemetry=gran,
    )
    chunk_fn = eng.chunk_fn
    res = eng.replay()
    assert eng.chunk_fn is chunk_fn and compiled_cache_size(chunk_fn) == 1
    assert [k for k in vars(eng) if "chunk_fn" in k] == ["chunk_fn"]
    off = JaxReplayEngine(
        ec, ep, FrameworkConfig(), wave_width=1, chunk_waves=1,
        telemetry="off",
    ).replay()
    np.testing.assert_array_equal(res.assignments, off.assignments)


def _gang_reject_trace():
    """Two nodes under W=8, ten 2-cpu pods: p0-p2 fill n0 (cpu=6); p3 and
    p4 fail in the same wave on the state those binds left
    (NodeResourcesFit charged n0, TaintToleration the tainted n1); p5-p7
    are a pod group that tolerates the taint, of which n1 (cpu=4) holds
    two: rolled back; p8 and p9, a wave later, fail as p3 did."""
    from kubernetes_simulator_tpu.models.core import Toleration

    nodes = [
        Node("n0", {"cpu": 6.0}),
        Node("n1", {"cpu": 4.0},
             taints=[Taint("dedicated", "infra", "NoSchedule")]),
    ]
    pods = [
        Pod(f"p{i}", requests={"cpu": 2.0}, arrival_time=float(i),
            pod_group="g" if 5 <= i < 8 else None,
            tolerations=([Toleration("dedicated", "Equal", "infra")]
                         if 5 <= i < 8 else []))
        for i in range(10)
    ]
    return encode(Cluster(nodes=nodes), pods)


def test_series_attribution_at_a_wide_wave_is_the_cpu_frameworks():
    """At W=8 the plain path's counts are the CPU framework's own on the
    same answers, folded in slot order: the rolled-back group charges
    nothing, and a slot is charged on the binds of the slots before it in
    its own wave (the state before the chunk would call p3 feasible)."""
    from kubernetes_simulator_tpu.framework.framework import SchedulerFramework
    from kubernetes_simulator_tpu.models.encode import PAD
    from kubernetes_simulator_tpu.models.state import bind, init_state

    ec, ep = _gang_reject_trace()
    cfg = FrameworkConfig()
    eng = JaxReplayEngine(ec, ep, cfg, wave_width=8, chunk_waves=1,
                          telemetry="series")
    res = eng.replay()
    assert (res.assignments[:3] == 0).all() and (res.assignments[3:] == PAD).all()
    assert res.telemetry.latency["count"] == res.placed == 3
    fw, st = SchedulerFramework(ec, ep, cfg), init_state(ec, ep)
    want = {}
    for p in eng.waves.idx[eng.waves.idx >= 0]:
        if res.assignments[p] >= 0:
            bind(ec, ep, st, int(p), int(res.assignments[p]))
            continue
        rc = {}
        if not fw.feasible_mask(st, int(p), reject_counts=rc).any():
            for k, v in rc.items():
                want[k] = want.get(k, 0) + v
    assert res.telemetry.reasons == want
    assert want == {"NodeResourcesFit": 4, "TaintToleration": 4}
    assert res.telemetry.rejection_attempts == want


@pytest.mark.parametrize("wave_width, chunk_waves", [(8, 1), (4, 1), (4, 2)])
def test_series_places_as_summary(wave_width, chunk_waves):
    """``series`` runs the program ``summary`` runs: the same placements,
    bit for bit, on a trace with failures and a rolled-back group."""
    ec, ep = _gang_reject_trace()
    runs = {
        gran: JaxReplayEngine(
            ec, ep, FrameworkConfig(), wave_width=wave_width,
            chunk_waves=chunk_waves, telemetry=gran,
        ).replay()
        for gran in ("summary", "series")
    }
    np.testing.assert_array_equal(
        runs["summary"].assignments, runs["series"].assignments
    )
    assert runs["series"].unschedulable > 0 and runs["series"].telemetry.reasons
    np.testing.assert_array_equal(
        runs["summary"].state.used, runs["series"].state.used
    )


# -- boundary-retry latency parity ---------------------------------------


def test_boundary_retry_latency_matches_cpu():
    """Crafted coincidence trace: p1 fails at t=1 (node full), the slot
    frees at t=1.5, the CPU backoff expiry (1 + 1.0) and the device chunk
    boundary (arrival of p2) both land at t=2 → both engines record the
    SAME latency multiset {0, 0, 1.0} and one failed attempt."""
    nodes = [Node("n0", {"cpu": 1.0})]
    pods = [
        Pod("p0", requests={"cpu": 1.0}, arrival_time=0.0, duration=1.5),
        Pod("p1", requests={"cpu": 1.0}, arrival_time=1.0),
        Pod("p2", requests={"cpu": 0.0}, arrival_time=2.0),
    ]
    ec, ep = encode(Cluster(nodes=nodes), pods)
    cfg = FIT_ONLY()
    cpu = CpuReplayEngine(ec, ep, cfg, telemetry="series").replay()
    dev = JaxReplayEngine(
        ec, ep, cfg, wave_width=1, chunk_waves=1, retry_buffer=8,
        telemetry="series",
    ).replay()
    np.testing.assert_array_equal(cpu.assignments, dev.assignments)
    for t in (cpu.telemetry, dev.telemetry):
        assert t.latency["count"] == 3
        assert t.zero_latency_binds == 2
        assert t.bind_latency == {1: 1.0}
        assert t.reasons == {"NodeResourcesFit": 1}
        assert t.rejection_attempts == {"NodeResourcesFit": 1}
    assert cpu.telemetry.latency == dev.telemetry.latency


@pytest.mark.fuzz_quick
def test_seeded_chaos_telemetry_parity():
    """Chaos fuzz slice (tests/test_chaos.py twin at series granularity):
    seeded queue-trivial traces with mttr=0 timelines must hold latency-
    histogram AND rejection-reason parity bit-for-bit alongside the
    existing assignment/eviction parity."""
    cfg = FIT_ONLY()
    evictions = 0
    for seed in (1, 2, 3):
        ec, ep = _light_trace(num_pods=28, num_nodes=6, seed=seed)
        evs = make_chaos_timeline(
            ec.num_nodes, seed=seed, horizon=float(ep.arrival.max()),
            mtbf=12.0, mttr=0.0, node_fraction=0.34,
        )
        cpu = CpuReplayEngine(ec, ep, cfg, telemetry="series").replay(
            node_events=evs
        )
        dev = JaxReplayEngine(
            ec, ep, cfg, wave_width=1, chunk_waves=1, preemption="kube",
            retry_buffer=64, telemetry="series",
        ).replay(node_events=evs)
        np.testing.assert_array_equal(cpu.assignments, dev.assignments)
        assert cpu.telemetry.latency == dev.telemetry.latency, f"seed {seed}"
        assert cpu.telemetry.reasons == dev.telemetry.reasons, f"seed {seed}"
        evictions += dev.evictions
    assert evictions > 0  # non-vacuous


# -- checkpoint purity ----------------------------------------------------


def test_checkpoint_blob_identical_with_telemetry(tmp_path):
    """Telemetry state is NOT checkpoint state: boundary-mode blobs are
    bit-identical with telemetry off vs timeline."""
    ec, ep = _light_trace(num_pods=24, num_nodes=4)
    blobs = {}
    for gran in ("off", "timeline"):
        ck = str(tmp_path / f"ck_{gran}.npz")
        JaxReplayEngine(
            ec, ep, FIT_ONLY(), wave_width=1, chunk_waves=4,
            preemption="kube", retry_buffer=64, telemetry=gran,
        ).replay(checkpoint_path=ck, checkpoint_every=3)
        blobs[gran] = np.load(ck, allow_pickle=True)
    off, tl = blobs["off"], blobs["timeline"]
    assert sorted(off.files) == sorted(tl.files)
    for k in off.files:
        np.testing.assert_array_equal(off[k], tl[k])


# -- what-if per-scenario latency ----------------------------------------


def test_whatif_kube_scenario_latency_quantiles():
    """Kube batches expose per-scenario latency quantiles; the clean
    scenario equals the single-replay telemetry, and the plain batch
    reports None."""
    ec, ep = _light_trace(num_pods=20, num_nodes=4)
    cfg = FIT_ONLY()
    evs = [e for e in make_chaos_timeline(
        ec.num_nodes, seed=7, horizon=float(ep.arrival.max()),
        mtbf=10.0, mttr=0.0, node_fraction=0.5,
    )]
    single = JaxReplayEngine(
        ec, ep, cfg, wave_width=1, chunk_waves=1, preemption="kube",
        retry_buffer=64,
    ).replay()
    res = WhatIfEngine(
        ec, ep, [Scenario(), Scenario(events=evs)], cfg, wave_width=1,
        chunk_waves=1, preemption="kube", retry_buffer=64,
        telemetry="series",
    ).run()
    assert res.latency_p50.shape == (2,)
    st = single.telemetry.latency
    assert float(res.latency_p50[0]) == st["p50"]
    assert float(res.latency_p99[0]) == st["p99"]
    assert res.scenario_telemetry[1].latency["count"] > 0
    plain = WhatIfEngine(ec, ep, [Scenario()], cfg, chunk_waves=4).run()
    assert plain.latency_p50 is None and plain.scenario_telemetry is None


# -- chrome trace exporter ------------------------------------------------


def test_chrome_trace_export(tmp_path):
    ec, ep = _light_trace(num_pods=12, num_nodes=3)
    from kubernetes_simulator_tpu.sim.runtime import NodeEvent

    evs = [
        NodeEvent(time=4.0, kind="node_down", node=0),
        NodeEvent(time=9.0, kind="node_up", node=0),
    ]
    res = CpuReplayEngine(ec, ep, FIT_ONLY(), telemetry="timeline").replay(
        node_events=evs
    )
    path = str(tmp_path / "trace.json")
    n = write_chrome_trace(path, res, arrival=ep.arrival, duration=ep.duration)
    with open(path) as f:
        doc = json.load(f)
    ev = doc["traceEvents"]
    assert len(ev) == n > 0
    phases = {e["ph"] for e in ev}
    assert "X" in phases and "M" in phases
    names = {e["name"] for e in ev}
    assert "node0 down" in names  # chaos span got stitched
    # Every pod span sits on the node it was bound to.
    for e in ev:
        if e["ph"] == "X" and e.get("pid") == 0 and e["name"].startswith("pod"):
            p = int(e["name"][3:])
            assert e["tid"] == int(res.assignments[p])


def test_series_attribution_fallback_notes(caplog, tmp_path):
    """series+ attribution fallback pin: in-scan tier preemption and
    checkpoint/resume each disable the host mirror with a log note —
    placements stay unchanged and latency/phase telemetry is still
    collected; only ``reasons`` goes dark."""
    import logging

    ec, ep = _reject_trace()
    cfg = FrameworkConfig()
    # Tier preemption: the mirror cannot follow the scan's evictions.
    ref = JaxReplayEngine(ec, ep, cfg, wave_width=1, chunk_waves=1,
                          preemption=True, telemetry="summary").replay()
    with caplog.at_level(logging.INFO, logger="k8sim"):
        res = JaxReplayEngine(ec, ep, cfg, wave_width=1, chunk_waves=1,
                              preemption=True, telemetry="series").replay()
    assert "not available with in-scan tier preemption" in caplog.text
    np.testing.assert_array_equal(ref.assignments, res.assignments)
    assert res.telemetry is not None and not res.telemetry.reasons
    assert res.telemetry.latency["count"] == res.placed
    # Checkpointing: the mirror is not part of checkpoints.
    caplog.clear()
    plain = JaxReplayEngine(ec, ep, cfg, wave_width=1, chunk_waves=1,
                            telemetry="series").replay()
    with caplog.at_level(logging.INFO, logger="k8sim"):
        ck = JaxReplayEngine(ec, ep, cfg, wave_width=1, chunk_waves=1,
                             telemetry="series").replay(
            checkpoint_path=str(tmp_path / "ck.npz"), checkpoint_every=2,
        )
    assert "disabled under checkpoint/resume" in caplog.text
    np.testing.assert_array_equal(plain.assignments, ck.assignments)
    assert ck.telemetry is not None and not ck.telemetry.reasons
    assert plain.telemetry.reasons  # the plain run still attributes


# -- round 12: mergeable telemetry / fleet observability -------------------


def _mk_tel(vals, zero, reasons=None, attempts=None, series=None,
            phases=None, events=(), gran="series"):
    from kubernetes_simulator_tpu.sim.telemetry import ReplayTelemetry

    t = ReplayTelemetry(
        granularity=gran,
        latency=latency_summary(zero, vals),
        phases=dict(phases or {}),
        bind_latency={i: v for i, v in enumerate(vals)},
        zero_latency_binds=zero,
    )
    t.reasons = reasons
    t.rejection_attempts = attempts
    t.series = series
    t.events = list(events)
    return t


def test_merge_partition_bit_parity():
    """The merge contract: merging disjoint halves reproduces EXACTLY the
    telemetry of the union — histogram, counters, raw values, series."""
    from kubernetes_simulator_tpu.sim.telemetry import ReplayTelemetry

    a = _mk_tel([1.0, 4.0], 2, reasons={"A": 2}, attempts={"A": 3},
                series={"t": [0.0, 1.0], "queue": [1.0, 0.0]},
                phases={"dispatch": 0.5})
    b = _mk_tel([0.5], 1, reasons={"B": 1}, attempts={"A": 1, "B": 1},
                series={"t": [2.0], "queue": [2.0]},
                phases={"dispatch": 0.25, "device_wait": 0.1})
    whole = _mk_tel([1.0, 4.0, 0.5], 3, reasons={"A": 2, "B": 1},
                    attempts={"A": 4, "B": 1},
                    series={"t": [0.0, 1.0, 2.0], "queue": [1.0, 0.0, 2.0]})
    m = ReplayTelemetry.merge([a, b])
    assert m.latency == whole.latency
    assert m.reasons == whole.reasons
    assert m.rejection_attempts == whole.rejection_attempts
    assert m.series == whole.series
    assert m.zero_latency_binds == 3
    assert m.bind_latency == {0: 1.0, 1: 4.0, 2: 0.5}
    # Same-process merge (no process_ids): phase timers key-wise summed.
    assert m.phases == {"dispatch": 0.75, "device_wait": 0.1}


def test_merge_process_phase_namespaces():
    """With process_ids the wall clocks of different hosts stay DISTINCT
    (p<pid>/<phase>), and re-merging a merge never double-prefixes."""
    from kubernetes_simulator_tpu.sim.telemetry import ReplayTelemetry

    a = _mk_tel([1.0], 0, phases={"dispatch": 0.5})
    b = _mk_tel([2.0], 0, phases={"dispatch": 0.25, "device_wait": 0.1})
    m = ReplayTelemetry.merge([a, b], process_ids=[0, 1])
    assert m.phases == {
        "p0/dispatch": 0.5, "p1/dispatch": 0.25, "p1/device_wait": 0.1,
    }
    # Latency is identical to the unprefixed merge (phases never feed it).
    assert m.latency == ReplayTelemetry.merge([a, b]).latency
    m2 = ReplayTelemetry.merge([m], process_ids=[7])
    assert m2.phases == m.phases  # "/" keys pass through unprefixed


def test_merge_edge_cases():
    from kubernetes_simulator_tpu.sim.telemetry import ReplayTelemetry

    assert ReplayTelemetry.merge([]) is None
    assert ReplayTelemetry.merge([None, None]) is None
    a = _mk_tel([1.0], 0)
    # None parts are skipped, not counted.
    m = ReplayTelemetry.merge([None, a, None], process_ids=[0, 1, 2])
    assert m.latency["count"] == 1
    b = _mk_tel([], 0, gran="summary")
    with pytest.raises(ValueError, match="granularity"):
        ReplayTelemetry.merge([a, b])
    with pytest.raises(ValueError, match="process_ids"):
        ReplayTelemetry.merge([a], process_ids=[0, 1])
    # summary-granularity parts carry no counters/series: stays None.
    c = _mk_tel([2.0], 1, gran="summary")
    m = ReplayTelemetry.merge([b, c])
    assert m.reasons is None and m.series is None
    assert m.latency["count"] == 2


def test_merge_associative_on_results():
    """Partitioning 3 parts as (a+b)+c or a+(b+c) or all-at-once gives
    the same virtual-time-derived telemetry (the DCN fleet merge relies
    on this: per-process merges happen first, the gather merge second)."""
    from kubernetes_simulator_tpu.sim.telemetry import ReplayTelemetry

    a = _mk_tel([1.0, 8.0], 1, reasons={"A": 1})
    b = _mk_tel([0.25], 0, reasons={"B": 2})
    c = _mk_tel([16.0], 2, reasons={"A": 3})
    flat = ReplayTelemetry.merge([a, b, c])
    left = ReplayTelemetry.merge([ReplayTelemetry.merge([a, b]), c])
    right = ReplayTelemetry.merge([a, ReplayTelemetry.merge([b, c])])
    for m in (left, right):
        assert m.latency == flat.latency
        assert m.reasons == flat.reasons
        assert m.bind_latency == flat.bind_latency
        assert m.zero_latency_binds == flat.zero_latency_binds


def test_whatif_fleet_telemetry_single_process():
    """Every what-if result now carries a merged fleet view: engine-level
    phase timers under the p0/ namespace (single process) and a latency
    histogram equal to the merge of the per-scenario telemetries."""
    from kubernetes_simulator_tpu.sim.telemetry import ReplayTelemetry

    ec, ep = _light_trace(num_pods=20, num_nodes=4)
    res = WhatIfEngine(
        ec, ep, [Scenario(), Scenario()], FIT_ONLY(), wave_width=1,
        chunk_waves=1, preemption="kube", retry_buffer=64,
        telemetry="series",
    ).run()
    ft = res.fleet_telemetry
    assert ft is not None
    assert ft.granularity == "series"
    assert all(k.startswith("p0/") for k in ft.phases)
    assert {k.split("/", 1)[1] for k in ft.phases} <= set(PHASE_NAMES)
    oracle = ReplayTelemetry.merge(res.scenario_telemetry)
    assert ft.latency == oracle.latency
    assert ft.reasons == oracle.reasons
    # Plain batches (no per-scenario telemetry) still get the phase view.
    plain = WhatIfEngine(
        ec, ep, [Scenario()], FIT_ONLY(), chunk_waves=4,
    ).run()
    assert plain.fleet_telemetry is not None
    assert plain.fleet_telemetry.latency is None
    assert any(k.startswith("p0/") for k in plain.fleet_telemetry.phases)


def test_chrome_trace_merged_track_groups(tmp_path):
    """write_chrome_trace_merged renders one track group PER PROCESS
    (pids 2p/2p+1, suffixed names) while the single-result exporter keeps
    the pre-round-12 pid 0/1 layout byte-for-byte."""
    from kubernetes_simulator_tpu.sim.telemetry import (
        write_chrome_trace_merged,
    )

    ec, ep = _light_trace(num_pods=8, num_nodes=2)
    res = CpuReplayEngine(ec, ep, FIT_ONLY(), telemetry="timeline").replay()
    single = str(tmp_path / "single.json")
    write_chrome_trace(single, res, arrival=ep.arrival, duration=ep.duration)
    with open(single) as f:
        names = {
            (e["pid"], e["args"]["name"])
            for e in json.load(f)["traceEvents"]
            if e["name"] == "process_name"
        }
    assert names == {(0, "cluster"), (1, "chaos")}

    merged = str(tmp_path / "merged.json")
    n = write_chrome_trace_merged(
        merged,
        [(res, ep.arrival, ep.duration), (res, ep.arrival, ep.duration)],
    )
    with open(merged) as f:
        ev = json.load(f)["traceEvents"]
    assert len(ev) == n
    names = {
        (e["pid"], e["args"]["name"])
        for e in ev if e["name"] == "process_name"
    }
    assert names == {
        (0, "cluster (p0)"), (1, "chaos (p0)"),
        (2, "cluster (p1)"), (3, "chaos (p1)"),
    }
    # Pod spans land inside their process's track group.
    assert {e["pid"] for e in ev if e["name"].startswith("pod")} == {0, 2}


def test_profiler_annotations_bit_parity(tmp_path, monkeypatch):
    """KSIM_PROFILE_DIR arms TraceAnnotation markers on every phase tick
    and chunk dispatch — results must stay bit-identical with the hooks
    on (no active trace needed: annotations outside a trace are no-ops)."""
    from kubernetes_simulator_tpu.utils import profiling

    monkeypatch.delenv("KSIM_PROFILE_DIR", raising=False)
    assert not profiling.profiling_active()
    ec, ep = _light_trace(num_pods=16, num_nodes=4)
    cfg = FIT_ONLY()
    off = JaxReplayEngine(
        ec, ep, cfg, wave_width=1, chunk_waves=1, preemption="kube",
        retry_buffer=64, telemetry="series",
    ).replay()
    woff = WhatIfEngine(
        ec, ep, [Scenario(), Scenario()], cfg, wave_width=1, chunk_waves=1,
        preemption="kube", retry_buffer=64, telemetry="series",
    ).run()
    monkeypatch.setenv("KSIM_PROFILE_DIR", str(tmp_path))
    assert profiling.profiling_active()
    on = JaxReplayEngine(
        ec, ep, cfg, wave_width=1, chunk_waves=1, preemption="kube",
        retry_buffer=64, telemetry="series",
    ).replay()
    won = WhatIfEngine(
        ec, ep, [Scenario(), Scenario()], cfg, wave_width=1, chunk_waves=1,
        preemption="kube", retry_buffer=64, telemetry="series",
    ).run()
    np.testing.assert_array_equal(off.assignments, on.assignments)
    assert off.telemetry.latency == on.telemetry.latency
    np.testing.assert_array_equal(woff.placed, won.placed)
    # The plain completions path (the benchmark's): armed, it also hands
    # its programs to profiling.stage_tables(), which nobody reads here —
    # registering lowers nothing and changes no answer or checkpoint.
    profiling._PROGRAMS.clear()
    ec, ep = _light_trace(num_pods=16, num_nodes=4, duration=4.0)
    plain, whatif = {}, {}
    for armed in (False, True):
        if armed:
            monkeypatch.setenv("KSIM_PROFILE_DIR", str(tmp_path))
        else:
            monkeypatch.delenv("KSIM_PROFILE_DIR")
        ck = tmp_path / f"plain{int(armed)}.npz"
        plain[armed] = JaxReplayEngine(
            ec, ep, cfg, wave_width=2, chunk_waves=2,
        ).replay(checkpoint_path=str(ck), checkpoint_every=3), ck.read_bytes()
        assert set(profiling._PROGRAMS) == (
            {"jit_chunk_fn", "jit_release_subtract"} if armed else set()
        )
        # ... and the what-if on its device-release path, every task's
        # node handed back: stage, dispatch, boundary_fold, device_wait,
        # gather and handback are spans when armed, and change no answer.
        whatif[armed] = WhatIfEngine(
            ec, ep, [Scenario(), Scenario()], cfg, wave_width=2,
            chunk_waves=2, completions=True, collect_assignments=True,
        ).run()
    assert whatif[True].fleet_telemetry.phases.keys() == (
        whatif[False].fleet_telemetry.phases.keys()
    )
    np.testing.assert_array_equal(
        whatif[False].assignments, whatif[True].assignments
    )
    (poff, ck_off), (pon, ck_on) = plain[False], plain[True]
    assert poff.placed == 16 and poff.state.used[:, 0].sum() < 16  # released
    np.testing.assert_array_equal(poff.assignments, pon.assignments)
    assert poff.telemetry.latency == pon.telemetry.latency
    assert ck_off == ck_on
    np.testing.assert_array_equal(
        np.asarray(woff.latency_p50, np.float64),
        np.asarray(won.latency_p50, np.float64),
    )
