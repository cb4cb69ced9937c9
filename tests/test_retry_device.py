"""Device-path unschedulable RETRY at release boundaries (round 4;
SURVEY.md §2 L3 — the [K8S] activeQ flush-on-event analogue for the
arrival-order device engine). Anchor = greedy_replay(retry_buffer=...);
the device twin is WhatIfEngine(retry_buffer=...)'s bounded boundary
retry pass."""

import numpy as np
import pytest

from kubernetes_simulator_tpu.framework.framework import FrameworkConfig
from kubernetes_simulator_tpu.models.core import Cluster, Node, Pod
from kubernetes_simulator_tpu.models.encode import PAD, encode
from kubernetes_simulator_tpu.sim.greedy import greedy_replay
from kubernetes_simulator_tpu.sim.synthetic import make_cluster, make_workload
from kubernetes_simulator_tpu.sim.whatif import Scenario, WhatIfEngine


def test_retry_places_after_release_tiny():
    # b fails while a holds the only cpu; a's completion frees it at a
    # boundary and the retry pass places b. Without retry b stays
    # unscheduled forever (the r01-r03 device semantics).
    cluster = Cluster(nodes=[Node("n0", {"cpu": 1})])
    pods = [
        Pod("a", requests={"cpu": 1}, arrival_time=0.0, duration=3.0),
        Pod("b", requests={"cpu": 1}, arrival_time=1.0),
        Pod("f1", requests={}, arrival_time=6.0),
        Pod("f2", requests={}, arrival_time=8.0),
    ]
    ec, ep = encode(cluster, pods)
    cfg = FrameworkConfig(plugins=[{"name": "NodeResourcesFit"}])
    anchor = greedy_replay(
        ec, ep, cfg, wave_width=1, completions_chunk_waves=1, retry_buffer=1
    )
    assert anchor.assignments[1] == 0  # b placed on retry
    assert anchor.placed == 4
    eng = WhatIfEngine(
        ec, ep, [Scenario()], cfg, wave_width=1, chunk_waves=1,
        retry_buffer=1,
    )
    res = eng.run()
    assert int(res.placed[0]) == anchor.placed
    no_retry = WhatIfEngine(
        ec, ep, [Scenario()], cfg, wave_width=1, chunk_waves=1
    ).run()
    assert int(no_retry.placed[0]) == 3  # b permanently missed


def test_retry_parity_random_contended():
    """Contended workload (tight capacity, short durations): device placed
    counts must equal the anchor's, scenario by scenario, and retry must
    place strictly more than no-retry (non-vacuous)."""
    cluster = make_cluster(3, seed=11)
    pods, _ = make_workload(
        120, seed=11, arrival_rate=60.0, duration_mean=1.5,
        with_spread=True, with_tolerations=True,
    )
    ec, ep = encode(cluster, pods)
    cfg = FrameworkConfig()
    W, C, RB = 4, 4, 8
    anchor = greedy_replay(
        ec, ep, cfg, wave_width=W, completions_chunk_waves=C,
        retry_buffer=RB,
    )
    eng = WhatIfEngine(
        ec, ep, [Scenario()], cfg, wave_width=W, chunk_waves=C,
        retry_buffer=RB,
    )
    assert eng._completions_dev
    res = eng.run()
    assert int(res.placed[0]) == anchor.placed
    no_retry = WhatIfEngine(
        ec, ep, [Scenario()], cfg, wave_width=W, chunk_waves=C
    ).run()
    assert anchor.placed > int(no_retry.placed[0])
    # The anchor's retried pods really are late placements, not arrivals.
    base = greedy_replay(
        ec, ep, cfg, wave_width=W, completions_chunk_waves=C
    )
    retried = (anchor.assignments >= 0) & (base.assignments == PAD)
    assert retried.any()


def test_retry_buffer_overflow_drops_newest():
    """With a 1-slot buffer only the FIRST failed pod retries; the rest
    stay permanently unscheduled — device and anchor agree."""
    cluster = Cluster(nodes=[Node("n0", {"cpu": 1})])
    pods = [
        Pod("a", requests={"cpu": 1}, arrival_time=0.0, duration=2.0),
        Pod("b", requests={"cpu": 1}, arrival_time=0.5, duration=100.0),
        Pod("c", requests={"cpu": 1}, arrival_time=0.6, duration=100.0),
        Pod("f1", requests={}, arrival_time=5.0),
        Pod("f2", requests={}, arrival_time=8.0),
    ]
    ec, ep = encode(cluster, pods)
    cfg = FrameworkConfig(plugins=[{"name": "NodeResourcesFit"}])
    anchor = greedy_replay(
        ec, ep, cfg, wave_width=1, completions_chunk_waves=1, retry_buffer=1
    )
    # b took the only buffer slot; c was dropped.
    assert anchor.assignments[1] == 0 and anchor.assignments[2] == PAD
    eng = WhatIfEngine(
        ec, ep, [Scenario()], cfg, wave_width=1, chunk_waves=1,
        retry_buffer=1,
    )
    res = eng.run()
    assert int(res.placed[0]) == anchor.placed == 4
    # Round 6: the device retry path reports its FIFO-capacity drops on
    # the result, matching the host anchor's count (c overflowed).
    assert res.retry_dropped is not None
    assert int(res.retry_dropped[0]) == anchor.retry_dropped == 1


def test_retry_placed_pod_releases_later():
    """A pod placed on retry starts AT the boundary and must itself
    release t_b + duration later, freeing capacity for a third pod —
    pinned against the anchor's pending-release bookkeeping."""
    cluster = Cluster(nodes=[Node("n0", {"cpu": 1})])
    pods = [
        Pod("a", requests={"cpu": 1}, arrival_time=0.0, duration=2.0),
        Pod("b", requests={"cpu": 1}, arrival_time=0.5, duration=1.0),
        Pod("f1", requests={}, arrival_time=4.0),
        Pod("f2", requests={}, arrival_time=6.0),
        # b retried ~t=4, releases by t=6+; c then fits via retry too.
        Pod("c", requests={"cpu": 1}, arrival_time=5.0),
        Pod("f3", requests={}, arrival_time=8.0),
        Pod("f4", requests={}, arrival_time=10.0),
        Pod("f5", requests={}, arrival_time=12.0),
    ]
    ec, ep = encode(cluster, pods)
    cfg = FrameworkConfig(plugins=[{"name": "NodeResourcesFit"}])
    anchor = greedy_replay(
        ec, ep, cfg, wave_width=1, completions_chunk_waves=1, retry_buffer=2
    )
    assert anchor.assignments[1] == 0 and anchor.assignments[4] == 0
    eng = WhatIfEngine(
        ec, ep, [Scenario()], cfg, wave_width=1, chunk_waves=1,
        retry_buffer=2,
    )
    res = eng.run()
    assert int(res.placed[0]) == anchor.placed


def test_retry_requires_device_release_path():
    cluster = make_cluster(4, seed=0)
    pods, _ = make_workload(16, seed=0)  # no durations
    ec, ep = encode(cluster, pods)
    with pytest.raises(ValueError, match="retry_buffer requires"):
        WhatIfEngine(
            ec, ep, [Scenario()], FrameworkConfig(), retry_buffer=8
        )


def test_retry_gang_pods_excluded():
    """Gang pods never enter the retry buffer (all-or-nothing groups
    cannot re-commit individually) — device and anchor agree."""
    cluster = Cluster(nodes=[Node("n0", {"cpu": 2})])
    pods = [
        Pod("a", requests={"cpu": 2}, arrival_time=0.0, duration=2.0),
        Pod("g0", requests={"cpu": 1}, arrival_time=0.5, pod_group="g"),
        Pod("g1", requests={"cpu": 1}, arrival_time=0.5, pod_group="g"),
        Pod("s", requests={"cpu": 1}, arrival_time=0.7),
        Pod("f1", requests={}, arrival_time=5.0),
        Pod("f2", requests={}, arrival_time=8.0),
        Pod("f3", requests={}, arrival_time=10.0),
    ]
    ec, ep = encode(cluster, pods)
    cfg = FrameworkConfig(plugins=[{"name": "NodeResourcesFit"}])
    anchor = greedy_replay(
        ec, ep, cfg, wave_width=2, completions_chunk_waves=1, retry_buffer=2
    )
    # s retried and placed; the gang stays unplaced (never buffered).
    assert anchor.assignments[3] == 0
    assert anchor.assignments[1] == PAD and anchor.assignments[2] == PAD
    eng = WhatIfEngine(
        ec, ep, [Scenario()], cfg, wave_width=2, chunk_waves=1,
        retry_buffer=2,
    )
    res = eng.run()
    assert int(res.placed[0]) == anchor.placed


def test_retry_multi_scenario_counts():
    """Perturbed scenarios run the same retry machinery per scenario;
    scenario 0 equals the anchor and a capacity-halved scenario places
    no more than the base."""
    from kubernetes_simulator_tpu.sim.whatif import Perturbation

    cluster = make_cluster(6, seed=13)
    pods, _ = make_workload(
        100, seed=13, arrival_rate=25.0, duration_mean=1.2,
        with_spread=True,
    )
    ec, ep = encode(cluster, pods)
    cfg = FrameworkConfig()
    scen = [
        Scenario(),
        Scenario([
            Perturbation(
                "scale_capacity", nodes=np.arange(3), resource="cpu",
                factor=0.5,
            )
        ]),
    ]
    eng = WhatIfEngine(
        ec, ep, scen, cfg, wave_width=4, chunk_waves=4, retry_buffer=8
    )
    res = eng.run()
    anchor = greedy_replay(
        ec, ep, cfg, wave_width=4, completions_chunk_waves=4, retry_buffer=8
    )
    assert int(res.placed[0]) == anchor.placed
    assert int(res.placed[1]) <= int(res.placed[0])


@pytest.mark.slow
def test_retry_full_plugin_envelope_parity():
    """Round 4 widening: retry works on traces WITH anti/pref count
    planes, multi-topology spread and singleton host rows — the pend
    release rides the same commit-block core as the static lists.
    Device placed counts == anchor, and retry matters."""
    cluster = make_cluster(3, seed=23)
    pods, _ = make_workload(
        150, seed=23, arrival_rate=60.0, duration_mean=1.5,
        with_affinity=True, with_spread=True, with_tolerations=True,
    )
    ec, ep = encode(cluster, pods)
    cfg = FrameworkConfig()
    W, C, RB = 4, 4, 8
    eng = WhatIfEngine(
        ec, ep, [Scenario()], cfg, wave_width=W, chunk_waves=C,
        retry_buffer=RB,
    )
    assert eng.static3.maintain_anti or eng.static3.maintain_pref
    assert eng.static3.has_host_rows or not eng.static3.single_topo
    res = eng.run()
    anchor = greedy_replay(
        ec, ep, cfg, wave_width=W, completions_chunk_waves=C,
        retry_buffer=RB,
    )
    assert int(res.placed[0]) == anchor.placed
    no_retry = greedy_replay(
        ec, ep, cfg, wave_width=W, completions_chunk_waves=C
    )
    assert anchor.placed > no_retry.placed  # non-vacuous


def test_single_replay_engine_retry_matches_greedy():
    """Round 5 (VERDICT r4 next #3): retry_buffer on JaxReplayEngine —
    the config-4 CLI path can re-attempt failed pods. Host boundary pass
    (sim.boundary), bit-identical to greedy_replay(retry_buffer=...)."""
    from kubernetes_simulator_tpu.sim.jax_runtime import JaxReplayEngine

    cluster = make_cluster(3, seed=11)
    pods, _ = make_workload(
        120, seed=11, arrival_rate=60.0, duration_mean=1.5,
        with_spread=True, with_tolerations=True,
    )
    ec, ep = encode(cluster, pods)
    cfg = FrameworkConfig()
    anchor = greedy_replay(
        ec, ep, cfg, wave_width=4, completions_chunk_waves=4, retry_buffer=8
    )
    eng = JaxReplayEngine(
        ec, ep, cfg, wave_width=4, chunk_waves=4, retry_buffer=8
    ).replay()
    np.testing.assert_array_equal(anchor.assignments, eng.assignments)
    assert eng.placed == anchor.placed
    assert eng.retry_dropped == anchor.retry_dropped
    # Non-vacuous: retry places strictly more than the no-retry engine.
    no_retry = JaxReplayEngine(ec, ep, cfg, wave_width=4, chunk_waves=4).replay()
    assert eng.placed > no_retry.placed


@pytest.mark.slow
def test_single_replay_retry_borg_scale():
    """Borg-shaped mid-size trace through the config-4 path: retry places
    >= the no-retry count and parity with the anchor holds end-to-end."""
    from kubernetes_simulator_tpu.sim.borg import BorgSpec, make_borg_encoded
    from kubernetes_simulator_tpu.sim.jax_runtime import JaxReplayEngine
    from kubernetes_simulator_tpu.utils.config import BorgWorkloadSpec

    spec = BorgSpec.from_spec(BorgWorkloadSpec(nodes=400, tasks=20_000, seed=3))
    ec, ep, _ = make_borg_encoded(spec)
    cfg = FrameworkConfig()
    eng = JaxReplayEngine(
        ec, ep, cfg, chunk_waves=64, retry_buffer=256
    ).replay()
    anchor = greedy_replay(
        ec, ep, cfg, completions_chunk_waves=64, retry_buffer=256
    )
    np.testing.assert_array_equal(anchor.assignments, eng.assignments)
    no_retry = JaxReplayEngine(ec, ep, cfg, chunk_waves=64).replay()
    assert eng.placed >= no_retry.placed


def test_single_replay_retry_sees_node_events():
    """Boundary mode mirrors node events into the HOST cluster view (the
    retry pass must not place onto a downed node): n0 goes down before
    the blocked pod's retry; the retry lands on n1 instead, and the
    cluster's allocatable is restored after the run."""
    from kubernetes_simulator_tpu.sim.jax_runtime import JaxReplayEngine
    from kubernetes_simulator_tpu.sim.runtime import NodeEvent

    cluster = Cluster(nodes=[Node("n0", {"cpu": 2}), Node("n1", {"cpu": 1})])
    pods = [
        # filler holds BOTH nodes so b must wait in the buffer.
        Pod("f0", requests={"cpu": 2}, arrival_time=0.0, duration=3.0),
        Pod("f1", requests={"cpu": 1}, arrival_time=0.0, duration=3.0),
        Pod("b", requests={"cpu": 1}, arrival_time=1.0),
        Pod("t1", requests={}, arrival_time=6.0),
        Pod("t2", requests={}, arrival_time=7.0),
    ]
    ec, ep = encode(cluster, pods)
    cfg = FrameworkConfig(plugins=[{"name": "NodeResourcesFit"}])
    saved = ec.allocatable.copy()
    res = JaxReplayEngine(
        ec, ep, cfg, wave_width=1, chunk_waves=1, retry_buffer=4
    ).replay(node_events=[NodeEvent(time=5.0, kind="node_down", node=0)])
    # b retried after the fillers released; n0 was down by then -> n1.
    assert res.assignments[2] == 1
    np.testing.assert_array_equal(ec.allocatable, saved)  # restored
    # Without the event, LeastAllocated prefers the emptier n0.
    res2 = JaxReplayEngine(
        ec, ep, cfg, wave_width=1, chunk_waves=1, retry_buffer=4
    ).replay()
    assert res2.assignments[2] == 0


def test_host_and_device_retry_paths_agree():
    """The single-replay HOST retry pass (sim.boundary) and the what-if
    DEVICE retry pass (the in-program boundary step) both anchor to
    greedy — pin their agreement with each other directly."""
    from kubernetes_simulator_tpu.sim.jax_runtime import JaxReplayEngine

    cluster = make_cluster(3, seed=11)
    pods, _ = make_workload(
        120, seed=11, arrival_rate=60.0, duration_mean=1.5,
        with_spread=True, with_tolerations=True,
    )
    ec, ep = encode(cluster, pods)
    cfg = FrameworkConfig()
    host = JaxReplayEngine(
        ec, ep, cfg, wave_width=4, chunk_waves=4, retry_buffer=8
    ).replay()
    dev = WhatIfEngine(
        ec, ep, [Scenario()], cfg, wave_width=4, chunk_waves=4,
        retry_buffer=8,
    ).run()
    assert int(dev.placed[0]) == host.placed


# -- PR 41: the queue's order, the per-task hand-back, no leaked release -----


def _contended(pods_n=140, nodes=3, seed=11, priorities=(0,), resident=0):
    """A contended trace (tight capacity, short durations) with the given
    priority tiers dealt over its pods and ``resident`` pods bound before
    t = 0, encoded; requests are dyadic, so every sum is exact."""
    cluster = make_cluster(nodes, seed=seed)
    pods, _ = make_workload(
        pods_n, seed=seed, arrival_rate=60.0, duration_mean=1.5,
        with_spread=True, with_tolerations=True,
    )
    rng = np.random.default_rng(seed)
    for p in pods:
        p.priority = int(rng.choice(priorities))
    ec, ep = encode(cluster, pods)
    if resident:
        ep.bound_node[:resident] = np.arange(resident) % nodes
        ep.arrival[:resident] = 0.0
        ep.duration[:resident] = rng.exponential(3.0, size=resident)
    return ec, ep


def _device_and_anchor(ec, ep, W=4, C=4, RB=8, scenarios=None):
    cfg = FrameworkConfig()
    anchor = greedy_replay(
        ec, ep, cfg, wave_width=W, completions_chunk_waves=C, retry_buffer=RB,
    )
    eng = WhatIfEngine(
        ec, ep, scenarios or [Scenario()], cfg, wave_width=W, chunk_waves=C,
        retry_buffer=RB, collect_assignments=True,
    )
    assert eng.release_path == "device"
    return eng, eng.run(), anchor


RETRY_CASES = {
    "one_priority": dict(),
    "mixed_priorities": dict(priorities=(0, 100, 200, 360, 450)),
    "resident_set": dict(priorities=(0, 200), resident=24),
    "small_buffer_overflows": dict(priorities=(0, 100, 200)),
}


@pytest.mark.parametrize("case", sorted(RETRY_CASES))
def test_retry_handback_equals_the_anchor_task_for_task(case):
    """``assignments`` and ``bind_boundary`` of the device retry path are
    ``greedy_replay``'s, task for task: the queue in priority-then-arrival
    order, residents held from t = 0, drops at a full buffer, and every
    code of a task with no node."""
    ec, ep = _contended(**RETRY_CASES[case])
    RB = 4 if case == "small_buffer_overflows" else 16
    eng, res, anchor = _device_and_anchor(ec, ep, RB=RB)
    np.testing.assert_array_equal(res.assignments[0], anchor.assignments)
    np.testing.assert_array_equal(res.bind_boundary[0], anchor.bind_boundary)
    assert int(res.placed[0]) == anchor.placed
    assert int(res.retry_dropped[0]) == anchor.retry_dropped
    bb = res.bind_boundary[0]
    assert (bb >= 0).any()  # the retry pass bound something
    assert ((bb >= -1) == (res.assignments[0] >= 0)).all()
    scheduled = ep.bound_node < 0
    assert int(res.placed[0]) == int((res.assignments[0][scheduled] >= 0).sum())
    assert int(res.placed[0] + res.unschedulable[0]) == int(scheduled.sum())
    if case == "small_buffer_overflows":
        assert anchor.retry_dropped > 0 and (bb == -3).any()
    if case == "resident_set":
        assert (bb[: RETRY_CASES[case]["resident"]] == -1).all()
    retry = res.fleet_telemetry.summary()["retry"]
    assert retry["buffer"] == RB and retry["release_leaked"]["max"] == 0
    assert retry["scenario0"]["retry_placed"] == int((bb >= 0).sum())
    assert retry["scenario0"]["retry_dropped"] == anchor.retry_dropped
    # a second batch on the same engine answers the same
    again = eng.run()
    np.testing.assert_array_equal(again.assignments, res.assignments)
    np.testing.assert_array_equal(again.bind_boundary, res.bind_boundary)


@pytest.mark.parametrize("case", sorted(RETRY_CASES))
def test_retry_handback_merges_every_bind_the_passes_made(case):
    """``summary()["retry"]["handback_merged"]``, the re-tried binds the
    hand-back program wrote into ``bind_boundary`` on the device, is
    ``retry_placed`` scenario for scenario (two scenarios: the mean, the
    max and scenario 0's own fix both)."""
    from kubernetes_simulator_tpu.sim.whatif import Perturbation

    ec, ep = _contended(**RETRY_CASES[case])
    scen = [Scenario(), Scenario([Perturbation(
        "scale_capacity", nodes=np.arange(3), resource="cpu", factor=0.5)])]
    RB = 4 if case == "small_buffer_overflows" else 16
    _, res, _ = _device_and_anchor(ec, ep, RB=RB, scenarios=scen)
    retry = res.fleet_telemetry.summary()["retry"]
    merged = (res.bind_boundary >= 0).sum(axis=1)
    assert merged.min() > 0
    assert retry["handback_merged"] == retry["retry_placed"] == {
        "mean": float(merged.mean()), "max": int(merged.max())}
    assert (retry["scenario0"]["handback_merged"]
            == retry["scenario0"]["retry_placed"] == int(merged[0]))
    assert (res.bind_boundary == -2).sum(axis=1).tolist() == [
        retry["scenario0"]["depth_at_end"],
        2 * retry["depth_at_end"]["mean"] - retry["scenario0"]["depth_at_end"]]


def queue_depths(ep, bind_boundary, W, C, log=()):
    """``[boundaries]``: how many tasks stand in each pass's queue, worked
    out on the host from ONE scenario's answers (never from the program).
    A task that failed in chunk c (its arrival chunk from ``pack_waves``)
    stands in the queue of every pass from c + 1 to the pass that bound it
    (``bind_boundary`` >= 0), or to the end (-2); one that bound in its
    arrival wave (-1), was dropped at a full buffer (-3) or is a gang member
    (-4, -5) never stood there. Under a timeline, ``log`` is the scenario's
    eviction log (rows of boundary, task, node, the pass that had bound it
    or -1): a row ends the stay on a node that the task's previous stay in
    the queue led to, and starts a stay in the queue at the row's boundary,
    which ends as above with the next row's binding pass or, after the last
    row, with ``bind_boundary`` (of a task's LAST bind)."""
    from kubernetes_simulator_tpu.sim.waves import pack_waves

    idx = pack_waves(ep, W).idx
    B = -(-idx.shape[0] // C)
    joins = {}  # task -> the boundaries it joined the queue at, in order
    ends = {}   # task -> what ended each of those stays
    w, k = np.nonzero(idx >= 0)
    for t, c in zip(idx[w, k].tolist(), (w // C).tolist()):
        joins[t], ends[t] = [c + 1], []
    for b, t, _, by in np.asarray(log, np.int64).reshape(-1, 4).tolist():
        if t not in joins:  # a resident: never queued before its first row
            joins[t], ends[t] = [], []
        else:
            ends[t].append(by)
        joins[t].append(b)
    depth = np.zeros(B, np.int64)
    for t, starts in joins.items():
        for start, end in zip(starts, ends[t] + [int(bind_boundary[t])]):
            if end >= 0:
                depth[start:end + 1] += 1
            elif end == -2:
                depth[start:] += 1
    return depth


def pass_waves_of(depths, W):
    """The wave steps the passes of scenarios vmapped together execute: a
    pass ends with the fullest scenario's last queued wave."""
    return int((-(-np.max(depths, axis=0) // W)).sum())


def _perturbed(n_nodes):
    """The base cluster and three perturbed ones, of which only the one with
    half its cpu queues deep."""
    from kubernetes_simulator_tpu.sim.whatif import Perturbation

    scale = lambda f: Perturbation("scale_capacity", nodes=np.arange(n_nodes),
                                   resource="cpu", factor=f)
    return [Scenario(), Scenario([scale(1.25)]), Scenario([scale(0.5)]),
            Scenario([scale(1.5)])]


# name -> (the trace, the buffer, the scenarios or None for the base alone)
PASS_WAVE_CASES = {
    **{name: (trace, 4 if name == "small_buffer_overflows" else 16, None)
       for name, trace in RETRY_CASES.items()},
    "queue_never_passes_a_quarter_of_its_buffer": (dict(), 64, None),
    "one_of_several_scenarios_fills_its_buffer": (
        dict(priorities=(0, 100, 200)), 16, _perturbed),
    "queue_stays_empty": (dict(nodes=40), 16, None),
}


@pytest.mark.parametrize("case", sorted(PASS_WAVE_CASES))
def test_retry_passes_end_with_the_fullest_scenarios_last_queued_wave(case):
    """``summary()["retry"]["pass_waves"]`` is the sum over the boundaries of
    ``ceil(deepest scenario's queue / W)``, the depths from each scenario's
    anchor (``greedy_replay`` on its own cluster): no pass walks the rest of
    its buffer, and a wave not walked is no bind, so both hand-back arrays
    stay the anchor's, scenario for scenario."""
    from kubernetes_simulator_tpu.sim.whatif import ScenarioSet

    trace, RB, perturb = PASS_WAVE_CASES[case]
    ec, ep = _contended(**trace)
    scen = perturb(ec.num_nodes) if perturb else [Scenario()]
    W = C = 4
    _, res, base = _device_and_anchor(ec, ep, W=W, C=C, RB=RB, scenarios=scen)
    retry = res.fleet_telemetry.summary()["retry"]
    own = ScenarioSet(ec, scen, keep_host_stacks=True).host_clusters(ec)
    anchors = [base] + [
        greedy_replay(c, ep, FrameworkConfig(), wave_width=W,
                      completions_chunk_waves=C, retry_buffer=RB)
        for c in own[1:]
    ]
    depths = []
    for s, anchor in enumerate(anchors):
        np.testing.assert_array_equal(res.assignments[s], anchor.assignments)
        np.testing.assert_array_equal(res.bind_boundary[s], anchor.bind_boundary)
        depths.append(queue_depths(ep, anchor.bind_boundary, W, C))
    depths = np.array(depths)
    assert depths.shape == (len(scen), retry["passes"])
    want = pass_waves_of(depths, W)
    # one count for all the scenarios vmapped together
    assert retry["pass_waves"] == {"mean": float(want), "max": want}
    assert retry["scenario0"]["pass_waves"] == want
    assert retry["depth_max"]["max"] == depths.max() <= RB
    whole = retry["passes"] * RB // W
    if case == "queue_stays_empty":
        assert want == 0 and not (res.bind_boundary >= 0).any()
    elif case == "queue_never_passes_a_quarter_of_its_buffer":
        assert 0 < depths.max() <= RB // 4 and 0 < want <= whole // 4
    elif case == "one_of_several_scenarios_fills_its_buffer":
        full = depths.max(axis=1) == RB
        assert full.tolist() == [False, False, True, False]
        # the deep scenario alone sets every pass's length
        assert want == pass_waves_of(depths[full], W) > pass_waves_of(depths[~full], W)
        assert want < whole  # its queue is empty for the first boundaries
    else:
        assert 0 < want < whole


def test_retry_handback_that_loses_a_bind_raises(monkeypatch):
    """A record row lost between the pass that wrote it and the hand-back
    is a wrong answer, not a slow one: ``run()`` raises and names the
    scenario."""
    import jax.numpy as jnp

    ec, ep = _contended(priorities=(0, 100, 200))
    eng, _, _ = _device_and_anchor(ec, ep, RB=8, scenarios=[Scenario()] * 2)
    handback = WhatIfEngine._handback_retry

    def lossy(self, span, vassign_d, rq, retry_placed):
        t_id = np.array(rq.t_id)
        b, j = np.argwhere(t_id[1] >= 0)[0]
        t_id[1, b, j] = PAD
        return handback(
            self, span, vassign_d, rq._replace(t_id=jnp.asarray(t_id)),
            retry_placed)

    monkeypatch.setattr(WhatIfEngine, "_handback_retry", lossy)
    with pytest.raises(RuntimeError, match="scenario 1 has"):
        eng.run()


def test_retry_queue_order_is_priority_then_arrival():
    """Two pods wait for the one cpu; the later one has the higher
    priority and gets it (kube's QueueSort), the earlier one the next."""
    cluster = Cluster(nodes=[Node("n0", {"cpu": 1})])
    pods = [
        Pod("a", requests={"cpu": 1}, arrival_time=0.0, duration=2.0),
        Pod("lo", requests={"cpu": 1}, arrival_time=0.5, duration=2.0,
            priority=0),
        Pod("hi", requests={"cpu": 1}, arrival_time=0.6, duration=2.0,
            priority=100),
    ] + [Pod(f"f{i}", requests={}, arrival_time=3.0 + 2.5 * i) for i in range(4)]
    ec, ep = encode(cluster, pods)
    cfg = FrameworkConfig(plugins=[{"name": "NodeResourcesFit"}])
    anchor = greedy_replay(
        ec, ep, cfg, wave_width=1, completions_chunk_waves=1, retry_buffer=2
    )
    assert 0 <= anchor.bind_boundary[2] < anchor.bind_boundary[1]
    res = WhatIfEngine(
        ec, ep, [Scenario()], cfg, wave_width=1, chunk_waves=1,
        retry_buffer=2, collect_assignments=True,
    ).run()
    np.testing.assert_array_equal(res.bind_boundary[0], anchor.bind_boundary)


def test_retry_more_outstanding_binds_than_the_buffer_release_all():
    """More re-tried binds outstanding than ``retry_buffer`` holds: every
    one is released when it is due (until PR 41 the pending list was capped
    at the buffer and the rest held their nodes to the end). The anchor's
    final usage is the device's, and ``release_leaked`` reads 0."""
    cluster = Cluster(nodes=[Node("n0", {"cpu": 8})])
    # Eight residents hold the 8 cpus and leave in pairs at t = 2, 4, 6, 8.
    # Pairs of 1-cpu pods arrive just before each pair leaves, wait in the
    # buffer of 2 and bind at the next boundary for 20 s each: from t = 9
    # all eight are outstanding, 4x the buffer. Tickers of no request make
    # a boundary a second; by t = 30 every bind is due and the node empty.
    pods = [Pod(f"r{i}", requests={"cpu": 1}, arrival_time=0.0,
                duration=2.0 + 2.0 * (i // 2)) for i in range(8)]
    pods += [Pod(f"w{i}", requests={"cpu": 1},
                 arrival_time=1.0 + 2.0 * (i // 2) + 0.1 * (i % 2),
                 duration=20.0) for i in range(8)]
    pods += [Pod(f"t{i}", requests={}, arrival_time=0.5 + i, duration=0.25)
             for i in range(45)]
    ec, ep = encode(cluster, pods)
    cfg = FrameworkConfig(plugins=[{"name": "NodeResourcesFit"}])
    kw = dict(wave_width=1, retry_buffer=2)
    anchor = greedy_replay(ec, ep, cfg, completions_chunk_waves=1, **kw)
    res = WhatIfEngine(
        ec, ep, [Scenario()], cfg, chunk_waves=1, collect_assignments=True,
        **kw,
    ).run()
    np.testing.assert_array_equal(res.assignments[0], anchor.assignments)
    np.testing.assert_array_equal(res.bind_boundary[0], anchor.bind_boundary)
    retry = res.fleet_telemetry.summary()["retry"]
    assert retry["release_leaked"] == {"mean": 0.0, "max": 0}
    bound_late = anchor.bind_boundary[8:16]
    assert (bound_late >= 0).all() and retry["scenario0"]["retry_placed"] == 8
    assert res.utilization_cpu[0] == anchor.utilization["cpu"] == 0.0


def test_retry_perturbed_scenarios_hand_back_their_own_queues():
    """Scenario 0 is the anchor; a scenario with half its cpu queues more
    and, with a small buffer, drops."""
    from kubernetes_simulator_tpu.sim.whatif import Perturbation

    ec, ep = _contended(priorities=(0, 100, 200))
    scen = [Scenario(), Scenario([Perturbation(
        "scale_capacity", nodes=np.arange(3), resource="cpu", factor=0.5)])]
    _, res, anchor = _device_and_anchor(ec, ep, RB=8, scenarios=scen)
    np.testing.assert_array_equal(res.assignments[0], anchor.assignments)
    np.testing.assert_array_equal(res.bind_boundary[0], anchor.bind_boundary)
    assert (res.bind_boundary[1] != res.bind_boundary[0]).any()
    retry = res.fleet_telemetry.summary()["retry"]
    assert retry["depth_max"]["max"] >= retry["scenario0"]["depth_max"]
    for s in range(2):
        none = res.assignments[s] < 0
        assert (res.bind_boundary[s][none] <= -2).all()
        assert int((res.bind_boundary[s] == -3).sum()) == int(res.retry_dropped[s])


def test_retry_pass_selects_its_class_rows_and_hands_back_what_the_gather_does(
    monkeypatch,
):
    """The pass walks each scenario's own queue, so its slots' class ids
    differ by scenario: it reads a slot's toleration row as a select among
    the plane's two rows (``class_row_reads`` "select"), the arrival scan by
    the dynamic index. On a tainted cluster with two toleration classes,
    perturbed per scenario (half the cpu; a taint nobody tolerates; both),
    every task's node and bind boundary are what the parent's form, the
    gather, hands back, and scenario 0 is the anchor."""
    from kubernetes_simulator_tpu.models.core import Toleration
    from kubernetes_simulator_tpu.ops import tpu3 as V3
    from kubernetes_simulator_tpu.sim.whatif import Perturbation

    cluster = make_cluster(6, seed=11, taint_fraction=0.5)
    pods, _ = make_workload(
        220, seed=11, arrival_rate=60.0, duration_mean=1.5, with_spread=True,
    )
    rng = np.random.default_rng(11)
    for p in pods:  # three in five tolerate, so that both classes queue
        p.priority = int(rng.choice((0, 100, 200)))
        if rng.random() < 0.6:
            p.tolerations.append(
                Toleration(key="dedicated", operator="Equal", value="batch"))
    ec, ep = encode(cluster, pods)
    assert ec.taint_key.size and (ec.taint_key >= 0).any()  # tainted nodes
    half = Perturbation("scale_capacity", nodes=np.arange(6), resource="cpu",
                        factor=0.5)
    cordon = lambda nodes: Perturbation(
        "add_taint", nodes=np.asarray(nodes), key="whatif", value="cordon")
    scen = [Scenario(), Scenario([half]), Scenario([cordon([0, 3])]),
            Scenario([cordon([1]), half])]
    eng, res, anchor = _device_and_anchor(ec, ep, RB=16, scenarios=scen)
    assert res.fleet_telemetry.summary()["class_row_reads"] == {
        "arrival": "slice", "retry": "select", "tol_classes": 2,
        "na_classes": 0,
    }
    np.testing.assert_array_equal(res.assignments[0], anchor.assignments)
    np.testing.assert_array_equal(res.bind_boundary[0], anchor.bind_boundary)
    tol_class = eng.static3.tol_class
    tainted = np.nonzero((ec.taint_key >= 0).any(axis=1))[0]
    on_tainted = 0
    for s in range(len(scen)):
        retried = res.bind_boundary[s] >= 0
        # both toleration classes among the binds every scenario's pass made
        assert set(tol_class[retried]) == {0, 1}
        on_tainted += int(np.isin(res.assignments[s][retried], tainted).sum())
        assert s == 0 or (res.bind_boundary[s] != res.bind_boundary[0]).any()
    assert on_tainted > 0  # the row that was read decided placements
    # the parent's form, through the function that names it
    monkeypatch.setattr(V3, "class_row_reads", lambda *a, **k: "slice")
    gather, by_gather, _ = _device_and_anchor(ec, ep, RB=16, scenarios=scen)
    assert by_gather.fleet_telemetry.summary()["class_row_reads"]["retry"] == "slice"
    assert gather is not eng
    np.testing.assert_array_equal(res.assignments, by_gather.assignments)
    np.testing.assert_array_equal(res.bind_boundary, by_gather.bind_boundary)
    np.testing.assert_array_equal(res.placed, by_gather.placed)
    np.testing.assert_array_equal(res.retry_dropped, by_gather.retry_dropped)


def _sha(a):
    import hashlib

    return hashlib.sha256(np.ascontiguousarray(a).tobytes()).hexdigest()[:16]


def test_a_boundary_with_a_retry_buffer_is_two_programs(tmp_path, monkeypatch):
    """The retry pass and the arrival scan are a device program each (PR 47:
    held in one, their two state-carrying loops push the step's node planes
    out of the chip's on-chip memory): every boundary dispatches
    ``jit_per_scenario_retry`` and then ``jit_per_scenario_arrivals``, both
    under names the benchmark's chunk readers find, and an armed run files
    the instructions of both: the pass and its record under ``ksim.retry``
    in the first, the wave step's stages and the queue's upkeep in the
    second."""
    import re

    from kubernetes_simulator_tpu.utils import profiling

    ec, ep = _contended(priorities=(0, 100, 200))
    eng = WhatIfEngine(
        ec, ep, _perturbed(ec.num_nodes), FrameworkConfig(), wave_width=4,
        chunk_waves=4, retry_buffer=16, collect_assignments=True,
    )
    order, lowered = [], {}
    for attr in ("_retry_fn", "_chunk_fn"):
        real = getattr(eng, attr)

        def spy(*args, _attr=attr, _real=real):
            order.append(_attr)
            if _attr not in lowered:  # before the call: it donates its buffers
                lowered[_attr] = _real.lower(*args).as_text()
            return _real(*args)

        spy.__name__, spy.lower = real.__name__, real.lower
        setattr(eng, attr, spy)
    profiling._PROGRAMS.clear()
    monkeypatch.setenv("KSIM_PROFILE_DIR", str(tmp_path))
    res = eng.run()
    monkeypatch.delenv("KSIM_PROFILE_DIR")
    boundaries = res.fleet_telemetry.summary()["retry"]["passes"]
    assert boundaries == 9 and order == ["_retry_fn", "_chunk_fn"] * boundaries
    # what the benchmark's readers look for (layer_metrics/chunk_ms_per_wave.py)
    chunk_program = re.compile(r"^jit_(per_scenario\w*|chunk_fn\w*)\(")
    modules = {attr: re.search(r"module @(\w+)", text).group(1)
               for attr, text in lowered.items()}
    assert modules == {"_retry_fn": "jit_per_scenario_retry",
                       "_chunk_fn": "jit_per_scenario_arrivals"}
    assert all(chunk_program.match(name + "(7)") for name in modules.values())
    tables = profiling.stage_tables()
    profiling._PROGRAMS.clear()
    assert set(modules.values()) <= set(tables)
    first = set(tables["jit_per_scenario_retry"].values())
    second = set(tables["jit_per_scenario_arrivals"].values())
    # the pass: releases of re-tried binds, then the step under the pass
    assert {"ksim.release", "ksim.retry", "ksim.retry/ksim.select",
            "ksim.retry/ksim.commit"} <= first
    assert not any(p.startswith("ksim.") and not p.startswith(
        ("ksim.retry", "ksim.release", "ksim.derive")) for p in first)
    # the arrival scan's step under no pass, the upkeep, the fold
    assert {"ksim.select", "ksim.commit", "ksim.filter_score/NodeResourcesFit",
            "ksim.retry", "ksim.release"} <= second
    assert not any(p.startswith("ksim.retry/") for p in second)


class _Lowered(Exception):
    pass


@pytest.mark.parametrize("kind", ("backlog", "drain", "budget"))
def test_the_pass_reads_a_queued_tasks_rows_in_one_gather(kind):
    """The pass program reads everything it reads of a queued task BY ITS ID
    in ONE gather (PR 53; a column at a time it was 86 ms of a boundary at
    ``retry_buffer`` 8,192, a gather costing by the index on the chip): in its
    lowered text the instructions under ``ksim.retry/Gather`` hold exactly
    one, of the packed table's rows at the queues' ids, and none under
    ``ksim.retry/Record`` reads a per-task table. The table the engine staged
    is its own columns side by side."""
    import re

    import jax
    import jax.numpy as jnp

    if kind == "backlog":
        ec, ep = _contended(priorities=(0, 100, 200))
        eng = WhatIfEngine(
            ec, ep, _perturbed(ec.num_nodes), FrameworkConfig(), wave_width=4,
            chunk_waves=4, retry_buffer=16, collect_assignments=True,
        )
    else:
        import test_evict_search as searched

        eng = searched.small_engine(kind)
    real, kept = eng._retry_fn, {}

    def lower_the_first_call(*args):
        kept["text"] = real.lower(*args).as_text(debug_info=True)
        raise _Lowered

    lower_the_first_call.__name__ = real.__name__
    eng._retry_fn = lower_the_first_call
    with pytest.raises(_Lowered):
        eng.run()
    stg, (src, xsrc) = eng._dev_rel_stage, eng._slot_srcs
    rows, n_tasks = stg["rows"], eng.pods.num_pods
    rec = {"mg": stg["mgt"]}
    if kind != "backlog":
        rec["resd"] = stg["resd"]
    if kind == "budget":
        rec["app"] = eng._stage_events()["app_t"]
    want = (src, xsrc, rec)
    back = rows.take(jnp.arange(n_tasks))
    assert jax.tree.structure(back) == jax.tree.structure(want)
    for got, col in zip(jax.tree.leaves(back), jax.tree.leaves(want)):
        assert got.dtype == col.dtype and got.shape == col.shape
        np.testing.assert_array_equal(np.asarray(got), np.asarray(col))
    C = rows.table.shape[1]
    assert C == sum(int(np.prod(a.shape[1:])) for a in jax.tree.leaves(want))
    # the lowered text: every gather with the scope it was traced under
    text = kept["text"]
    names = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', text, re.M))
    gathers = {"ksim.retry/Gather": [], "ksim.retry/Record": []}
    for types, loc in re.findall(
            r'"stablehlo\.gather"\(.*?: \((.*?)\) -> .*? loc\((#loc\d+)\)', text):
        for scope in gathers:
            if f"/{scope}/" in names.get(loc, ""):
                gathers[scope].append(types)
    RB = eng.retry_buffer
    assert gathers["ksim.retry/Gather"] == [
        f"tensor<{n_tasks}x{C}xi32>, tensor<{eng.S}x{RB}x1xi32>"]
    assert not any(f"tensor<{n_tasks}x" in types or f"tensor<{n_tasks}>" in types
                   for types in gathers["ksim.retry/Record"])
    assert f"tensor<{n_tasks}x" not in text.replace(
        f"tensor<{n_tasks}x{C}xi32>", "")  # no other per-task table comes in


def test_the_two_programs_answer_what_the_one_program_answered():
    """Value-exact: on the contended trace with one deep scenario among four,
    both hand-back arrays and ``summary()["retry"]`` are what the tree before
    PR 47 (d4a9348: pass, arrival scan and upkeep in one program) gave."""
    ec, ep = _contended(priorities=(0, 100, 200))
    _, res, _ = _device_and_anchor(
        ec, ep, W=4, C=4, RB=16, scenarios=_perturbed(ec.num_nodes))
    assert _sha(res.assignments) == "0af030a33c914dbf"
    assert _sha(res.bind_boundary) == "b82416b669c0ea00"
    assert res.fleet_telemetry.summary()["retry"] == {
        "buffer": 16, "passes": 9,
        "depth_at_end": {"max": 16, "mean": 7.0},
        "depth_max": {"max": 16, "mean": 6.25},
        "handback_merged": {"max": 35, "mean": 11.5},
        "pass_waves": {"max": 22, "mean": 22.0},
        "release_leaked": {"max": 0, "mean": 0.0},
        "retry_dropped": {"max": 41, "mean": 10.25},
        "retry_placed": {"max": 35, "mean": 11.5},
        "scenario0": {"depth_at_end": 12, "depth_max": 8, "handback_merged": 10,
                      "pass_waves": 22, "release_leaked": 0, "retry_dropped": 0,
                      "retry_placed": 10},
    }
