"""Chaos campaigns (round 7): timed failure/recovery injection with
device-path eviction parity.

``node_down`` on the boundary-mode device path evicts bound pods with kube
NoExecute semantics — victims free resources through the keyed plane-op
log and re-enter the retry buffer exactly like preemption victims. The
CPU event engine is the parity oracle: at wave_width=1 / chunk_waves=1 on
queue-trivial traces the eviction path matches bit-for-bit, lazy and
eager boundary sync stay bit-identical, checkpoints carry the applied-
event cursor + timeline hash, and the what-if batch runs one timeline per
scenario through the per-scenario host mirrors."""

import numpy as np
import pytest

from kubernetes_simulator_tpu.framework.framework import FrameworkConfig
from kubernetes_simulator_tpu.models.core import Cluster, Node, Pod
from kubernetes_simulator_tpu.models.encode import PAD, encode
from kubernetes_simulator_tpu.sim.jax_runtime import JaxReplayEngine
from kubernetes_simulator_tpu.sim.runtime import (
    CpuReplayEngine,
    NodeEvent,
    validate_node_events,
)
from kubernetes_simulator_tpu.sim.synthetic import make_chaos_timeline
from kubernetes_simulator_tpu.sim.whatif import Scenario, WhatIfEngine

FIT_ONLY = lambda: FrameworkConfig(plugins=[{"name": "NodeResourcesFit"}])


def _light_trace(num_pods=28, num_nodes=5, duration=30.0, seed=None):
    """Queue-trivial shape (the documented parity envelope): distinct
    strictly-increasing integer arrivals, priority 0, and load that fits
    the cluster even under the injected failures — the queue stays empty
    except for eviction victims, so no pod ever waits on a completion
    PAST the last arrival (device boundaries end there; the CPU engine
    keeps draining, which is the documented divergence outside this
    envelope)."""
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


# All event times stay BELOW the last arrival (27): device boundaries end
# at the last wave, so a later event would fire on the CPU engine only.
EVS = [
    NodeEvent(time=8.0, kind="node_down", node=0),
    NodeEvent(time=18.0, kind="node_up", node=0),
    NodeEvent(time=24.0, kind="node_down", node=1),
]


def test_cpu_device_eviction_parity_and_lazy_eager():
    """W=1 / C=1 queue-trivial: device NoExecute eviction matches the CPU
    event engine bit-for-bit (assignments AND disruption counters), and
    lazy boundary sync stays bit-identical to eager with chaos on."""
    ec, ep = _light_trace()
    cfg = FIT_ONLY()
    cpu = CpuReplayEngine(ec, ep, cfg).replay(node_events=EVS)
    dev = JaxReplayEngine(
        ec, ep, cfg, wave_width=1, chunk_waves=1, preemption="kube",
        retry_buffer=64,
    ).replay(node_events=EVS)
    np.testing.assert_array_equal(cpu.assignments, dev.assignments)
    assert dev.evictions == cpu.evictions > 0  # non-vacuous
    assert dev.evict_rescheduled == cpu.evict_rescheduled
    assert dev.evict_stranded == cpu.evict_stranded
    eager = JaxReplayEngine(
        ec, ep, cfg, wave_width=1, chunk_waves=1, preemption="kube",
        retry_buffer=64, lazy_boundary=False,
    ).replay(node_events=EVS)
    np.testing.assert_array_equal(dev.assignments, eager.assignments)
    assert dev.evictions == eager.evictions
    assert dev.evict_latency_mean == eager.evict_latency_mean


def test_eviction_counters_distinct_from_preemption():
    """Chaos disruption is reported separately from scheduler-initiated
    preemption: a priority-0 chaos run has evictions > 0, preemptions
    == 0, and summary() carries the four eviction fields."""
    ec, ep = _light_trace()
    res = JaxReplayEngine(
        ec, ep, FIT_ONLY(), wave_width=1, chunk_waves=1, preemption="kube",
        retry_buffer=64,
    ).replay(node_events=EVS)
    assert res.evictions > 0 and res.preemptions == 0
    s = res.summary()
    for k in ("evictions", "evict_rescheduled", "evict_stranded",
              "evict_latency_mean"):
        assert k in s
    assert s["evictions"] == res.evictions


def test_checkpoint_resume_with_events(tmp_path):
    """The applied-event cursor rides the checkpoint blob: a resumed
    chaos replay equals the uninterrupted one exactly, and resuming under
    a DIFFERENT (or missing) timeline is rejected via the event hash."""
    ec, ep = _light_trace(num_pods=60, num_nodes=4)
    cfg = FIT_ONLY()
    evs = [
        NodeEvent(time=8.0, kind="node_down", node=0),
        NodeEvent(time=20.0, kind="node_up", node=0),
        NodeEvent(time=30.0, kind="node_down", node=2),
        NodeEvent(time=44.0, kind="node_up", node=2),
    ]
    mk = lambda: JaxReplayEngine(
        ec, ep, cfg, wave_width=1, chunk_waves=4, preemption="kube",
        retry_buffer=64,
    )
    full = mk().replay(node_events=evs)
    assert full.evictions > 0
    ck = str(tmp_path / "chaos.npz")
    mk().replay(node_events=evs, checkpoint_path=ck, checkpoint_every=2)
    resumed = mk().replay(node_events=evs, checkpoint_path=ck, resume=True)
    np.testing.assert_array_equal(full.assignments, resumed.assignments)
    assert resumed.evictions == full.evictions
    assert resumed.evict_rescheduled == full.evict_rescheduled
    assert resumed.evict_latency_mean == full.evict_latency_mean
    changed = evs[:-1] + [NodeEvent(time=45.0, kind="node_down", node=2)]
    with pytest.raises(ValueError, match="different node_events"):
        mk().replay(node_events=changed, checkpoint_path=ck, resume=True)
    with pytest.raises(ValueError, match="different node_events"):
        mk().replay(checkpoint_path=ck, resume=True)


def test_whatif_per_scenario_timelines(tmp_path):
    """The batch engine runs one timed timeline per scenario: a scenario
    carrying the single-replay's events bit-matches that replay, and
    scenarios differing ONLY in failure timing produce differing
    disruption metrics."""
    ec, ep = _light_trace()
    cfg = FIT_ONLY()
    ev_late = [NodeEvent(time=25.0, kind="node_down", node=0)]
    single = JaxReplayEngine(
        ec, ep, cfg, wave_width=1, chunk_waves=1, preemption="kube",
        retry_buffer=64,
    ).replay(node_events=EVS)
    eng = WhatIfEngine(
        ec, ep,
        [Scenario(), Scenario(events=EVS), Scenario(events=ev_late)],
        cfg, wave_width=1, chunk_waves=1, preemption="kube",
        retry_buffer=64, collect_assignments=True,
    )
    res = eng.run()
    np.testing.assert_array_equal(res.assignments[1], single.assignments)
    assert int(res.evictions[0]) == 0  # clean reference scenario
    assert int(res.evictions[1]) == single.evictions
    assert int(res.evict_rescheduled[1]) == single.evict_rescheduled
    assert int(res.evict_stranded[1]) == single.evict_stranded
    assert float(res.evict_latency_mean[1]) == single.evict_latency_mean
    # timing-only difference → different disruption
    assert int(res.evictions[2]) != int(res.evictions[1])
    # engine reuse: the mutated alloc stacks were restored
    res2 = eng.run()
    np.testing.assert_array_equal(res.assignments[1], res2.assignments[1])
    np.testing.assert_array_equal(res.evictions, res2.evictions)


def _refused_batches():
    """What a what-if batch with timelines is still refused for, off the
    kube mirrors: (engine keywords, timeline, what the message names)."""
    from kubernetes_simulator_tpu.parallel.mesh import make_mesh

    scale = [NodeEvent(time=1.0, kind="capacity_scale", node=0, scale=0.5)]
    return {
        "no_retry_buffer": (dict(), EVS, "retry_buffer > 0"),
        "mesh": (dict(retry_buffer=8, mesh=make_mesh(1)), EVS, "no mesh"),
        "capacity_scale": (dict(retry_buffer=8), scale, "capacity_scale"),
    }


@pytest.mark.parametrize("case", ["no_retry_buffer", "mesh", "capacity_scale"])
def test_whatif_timeline_guards(case):
    """Timelines run on the device retry path (tests/
    test_whatif_events_device.py) or through the kube mirrors; the rest
    stays refused, with the reason."""
    ec, ep = _light_trace(num_pods=4, num_nodes=2)
    kw, events, names = _refused_batches()[case]
    with pytest.raises(ValueError, match=names):
        WhatIfEngine(
            ec, ep, [Scenario(events=events)], FIT_ONLY(), wave_width=1,
            chunk_waves=1, **kw,
        )


def test_whatif_timeline_validation_names_the_scenario():
    ec, ep = _light_trace(num_pods=4, num_nodes=2)
    with pytest.raises(ValueError, match="scenario 1"):
        WhatIfEngine(
            ec, ep,
            [Scenario(),
             Scenario(events=[NodeEvent(time=1.0, kind="node_down",
                                        node=99)])],
            FIT_ONLY(), wave_width=1, chunk_waves=1, preemption="kube",
            retry_buffer=8,
        )


def test_validation_actionable_on_every_engine():
    """Malformed timelines raise up front — same messages on the CPU and
    device engines, before any scheduling work happens."""
    ec, ep = _light_trace(num_pods=4, num_nodes=2)
    bad = {
        "unknown kind": [NodeEvent(time=1.0, kind="node_reboot", node=0)],
        "out of range": [NodeEvent(time=1.0, kind="node_down", node=7)],
        "must be sorted": [
            NodeEvent(time=5.0, kind="node_down", node=0),
            NodeEvent(time=1.0, kind="node_down", node=1),
        ],
        "finite value": [NodeEvent(time=-2.0, kind="node_down", node=0)],
        "without a prior node_down": [
            NodeEvent(time=1.0, kind="node_up", node=0)
        ],
    }
    dev = JaxReplayEngine(ec, ep, FIT_ONLY(), wave_width=1, chunk_waves=1)
    for pat, evs in bad.items():
        with pytest.raises(ValueError, match=pat):
            validate_node_events(evs, ec.num_nodes)
        with pytest.raises(ValueError, match=pat):
            CpuReplayEngine(ec, ep, FIT_ONLY()).replay(node_events=evs)
        with pytest.raises(ValueError, match=pat):
            dev.replay(node_events=evs)


def test_chaos_timeline_generator():
    """Seeded, sorted, validation-clean, MTBF/MTTR-shaped; mttr=0 keeps
    nodes down; max_events truncation never strands a node_up."""
    evs = make_chaos_timeline(50, seed=3, horizon=100.0, mtbf=40.0,
                              mttr=10.0, node_fraction=0.3)
    assert evs and evs == make_chaos_timeline(
        50, seed=3, horizon=100.0, mtbf=40.0, mttr=10.0, node_fraction=0.3
    )
    times = [e.time for e in evs]
    assert times == sorted(times) and times[-1] < 100.0
    assert validate_node_events(evs, 50) is evs
    pure_fail = make_chaos_timeline(50, seed=3, horizon=100.0, mtbf=20.0,
                                    mttr=0.0, node_fraction=0.5)
    assert pure_fail and all(e.kind == "node_down" for e in pure_fail)
    capped = make_chaos_timeline(50, seed=3, horizon=400.0, mtbf=30.0,
                                 mttr=10.0, node_fraction=1.0, max_events=9)
    assert len(capped) <= 9
    validate_node_events(capped, 50)
    with pytest.raises(ValueError, match="mtbf"):
        make_chaos_timeline(10, mtbf=0.0)


@pytest.mark.fuzz_quick
def test_seeded_chaos_slice():
    """Default-gate randomized chaos evidence: three seeded queue-trivial
    traces at ONE compile shape (same pod/node counts — only arrivals,
    durations and the seeded timeline vary) must hold CPU-vs-device
    eviction parity bit-for-bit."""
    cfg = FIT_ONLY()
    total = 0
    for seed in (1, 2, 3):
        ec, ep = _light_trace(num_pods=28, num_nodes=6, seed=seed)
        # mttr=0 (nodes stay down) keeps the comparison in the envelope:
        # a down→up pair landing between two arrivals would let the
        # device retry pass see the recovered node that the CPU rebind
        # (at the event instant) could not.
        evs = make_chaos_timeline(
            ec.num_nodes, seed=seed, horizon=float(ep.arrival.max()),
            mtbf=12.0, mttr=0.0, node_fraction=0.34,
        )
        cpu = CpuReplayEngine(ec, ep, cfg).replay(node_events=evs)
        dev = JaxReplayEngine(
            ec, ep, cfg, wave_width=1, chunk_waves=1, preemption="kube",
            retry_buffer=64,
        ).replay(node_events=evs)
        np.testing.assert_array_equal(cpu.assignments, dev.assignments)
        assert dev.evictions == cpu.evictions, f"seed {seed}"
        assert dev.evict_rescheduled == cpu.evict_rescheduled, f"seed {seed}"
        total += dev.evictions
    assert total > 0  # non-vacuous across the slice


def test_cli_chaos_envelope_warning(caplog):
    """Config-validation-time envelope guard: chaos events beyond the
    trace's last arrival warn loudly — device engines replay no chunks
    past the final wave, so those events could only ever fire on the CPU
    engine (usually a mis-set chaos.horizon)."""
    import logging

    from kubernetes_simulator_tpu.cli import _chaos_timeline
    from kubernetes_simulator_tpu.utils.config import SimConfig

    ec, ep = _light_trace(num_pods=28, num_nodes=5)  # last arrival t=27
    cfg = SimConfig.from_dict({
        "chaos": {"horizon": 1000.0, "mtbf": 50.0, "mttr": 10.0,
                  "nodeFraction": 1.0},
    })
    with caplog.at_level(logging.WARNING, logger="k8sim"):
        events = _chaos_timeline(cfg, ec, ep, seed=0)
    assert any(e.time > 27.0 for e in events)
    assert "beyond the trace's last arrival" in caplog.text
    # Default horizon (None -> last arrival) stays inside the envelope.
    caplog.clear()
    cfg = SimConfig.from_dict({
        "chaos": {"mtbf": 5.0, "mttr": 2.0, "nodeFraction": 1.0},
    })
    with caplog.at_level(logging.WARNING, logger="k8sim"):
        events = _chaos_timeline(cfg, ec, ep, seed=0)
    assert events and all(e.time <= 27.0 for e in events)
    assert "beyond the trace's last arrival" not in caplog.text
