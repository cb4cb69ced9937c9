"""Device-path completions: pods with finite duration free their resources
and count contributions at chunk boundaries (SURVEY.md §2 L4 — "binding
updates state used by subsequent pods"; completions are the other half of
that contract). Anchor = greedy_replay(completions_chunk_waves=...)."""

import numpy as np
import pytest

from kubernetes_simulator_tpu.framework.framework import FrameworkConfig
from kubernetes_simulator_tpu.models.core import (
    Cluster,
    LabelSelector,
    Node,
    Pod,
    PodAffinitySpec,
    PodAffinityTerm,
)
from kubernetes_simulator_tpu.models.encode import PAD, encode
from kubernetes_simulator_tpu.sim.greedy import greedy_replay
from kubernetes_simulator_tpu.sim.jax_runtime import JaxReplayEngine
from kubernetes_simulator_tpu.sim.synthetic import make_cluster, make_workload


def test_completion_frees_capacity_changes_placement():
    # a holds the only cpu until t=5; b arrives at t=10 — it fits only if
    # the release actually happened. Releases run ONE CHUNK BEHIND
    # placements (the round-3 pipelining slack: boundary b sees chunks
    # ≤ b−2), so a zero-request filler chunk sits between them.
    cluster = Cluster(nodes=[Node("n0", {"cpu": 1})])
    pods = [
        Pod("a", requests={"cpu": 1}, arrival_time=0.0, duration=5.0),
        Pod("f", requests={}, arrival_time=6.0),
        Pod("b", requests={"cpu": 1}, arrival_time=10.0),
    ]
    ec, ep = encode(cluster, pods)
    cfg = FrameworkConfig(plugins=[{"name": "NodeResourcesFit"}])
    res = JaxReplayEngine(ec, ep, cfg, wave_width=1, chunk_waves=1).replay()
    assert res.assignments[0] == 0 and res.assignments[2] == 0
    assert res.placed == 3
    off = JaxReplayEngine(
        ec, ep, cfg, wave_width=1, chunk_waves=1, completions=False
    ).replay()
    assert off.assignments[2] == PAD  # without completions b never fits
    anchor = greedy_replay(ec, ep, cfg, wave_width=1, completions_chunk_waves=1)
    np.testing.assert_array_equal(res.assignments, anchor.assignments)


def test_completion_decrements_count_planes():
    # a (app=x) blocks b's required anti-affinity until it completes: the
    # release must decrement the match-count planes, not just resources.
    cluster = Cluster(nodes=[Node("n0", {"cpu": 4})])
    anti = PodAffinitySpec(
        required=(
            PodAffinityTerm(
                LabelSelector.make({"app": "x"}), "kubernetes.io/hostname"
            ),
        )
    )
    pods = [
        Pod("a", labels={"app": "x"}, requests={"cpu": 1}, arrival_time=0.0,
            duration=3.0),
        Pod("f", requests={}, arrival_time=5.0),  # slack chunk
        Pod("b", requests={"cpu": 1}, arrival_time=10.0, pod_anti_affinity=anti),
    ]
    ec, ep = encode(cluster, pods)
    cfg = FrameworkConfig()
    res = JaxReplayEngine(ec, ep, cfg, wave_width=1, chunk_waves=1).replay()
    assert res.assignments[0] == 0 and res.assignments[2] == 0
    off = JaxReplayEngine(
        ec, ep, cfg, wave_width=1, chunk_waves=1, completions=False
    ).replay()
    assert off.assignments[2] == PAD
    anchor = greedy_replay(ec, ep, cfg, wave_width=1, completions_chunk_waves=1)
    np.testing.assert_array_equal(res.assignments, anchor.assignments)


def test_completions_parity_random():
    cluster = make_cluster(12, seed=3, taint_fraction=0.2)
    pods, _ = make_workload(
        80, seed=3, arrival_rate=10.0, duration_mean=2.0,
        with_affinity=True, with_spread=True, with_tolerations=True,
    )
    ec, ep = encode(cluster, pods)
    cfg = FrameworkConfig()
    anchor = greedy_replay(ec, ep, cfg, wave_width=4, completions_chunk_waves=4)
    dev = JaxReplayEngine(ec, ep, cfg, wave_width=4, chunk_waves=4).replay()
    np.testing.assert_array_equal(dev.assignments, anchor.assignments)
    # Releases must actually matter on this trace, or the test is vacuous.
    off = greedy_replay(ec, ep, cfg, wave_width=4)
    assert (anchor.assignments != off.assignments).any()


def test_completions_checkpoint_resume_identical(tmp_path):
    cluster = make_cluster(10, seed=5)
    pods, _ = make_workload(120, seed=5, arrival_rate=20.0, duration_mean=1.5)
    ec, ep = encode(cluster, pods)
    cfg = FrameworkConfig(plugins=[{"name": "NodeResourcesFit"}])
    full = JaxReplayEngine(ec, ep, cfg, wave_width=4, chunk_waves=4).replay()
    ck = str(tmp_path / "ck.npz")
    JaxReplayEngine(ec, ep, cfg, wave_width=4, chunk_waves=4).replay(
        checkpoint_path=ck, checkpoint_every=2
    )
    resumed = JaxReplayEngine(ec, ep, cfg, wave_width=4, chunk_waves=4).replay(
        checkpoint_path=ck, resume=True
    )
    np.testing.assert_array_equal(full.assignments, resumed.assignments)
    assert full.placed == resumed.placed


def test_gang_member_completions_release_individually():
    # Both gang members commit at t=0; each releases at its own finish time,
    # freeing capacity for later singles.
    cluster = Cluster(nodes=[Node("n0", {"cpu": 2})])
    pods = [
        Pod("g0", requests={"cpu": 1}, arrival_time=0.0, duration=2.0,
            pod_group="gang"),
        Pod("g1", requests={"cpu": 1}, arrival_time=0.0, duration=8.0,
            pod_group="gang"),
        Pod("f1", requests={}, arrival_time=12.0),  # slack chunk (W=2)
        Pod("f2", requests={}, arrival_time=13.0),
        Pod("s", requests={"cpu": 2}, arrival_time=20.0),
    ]
    ec, ep = encode(cluster, pods)
    cfg = FrameworkConfig(plugins=[{"name": "NodeResourcesFit"}])
    res = JaxReplayEngine(ec, ep, cfg, wave_width=2, chunk_waves=1).replay()
    assert res.assignments[0] == 0 and res.assignments[1] == 0
    assert res.assignments[4] == 0  # both released by t=20
    anchor = greedy_replay(ec, ep, cfg, wave_width=2, completions_chunk_waves=1)
    np.testing.assert_array_equal(res.assignments, anchor.assignments)


def test_completions_resume_with_prebound(tmp_path):
    # Pre-bound pods never appear in waves; the resume reconstruction must
    # still know their releases were already applied (chunk −2), or it
    # subtracts them a second time and the planes go negative.
    cluster = Cluster(nodes=[Node("n0", {"cpu": 2}), Node("n1", {"cpu": 2})])
    pods = [
        Pod("pre", requests={"cpu": 1}, arrival_time=0.0, duration=1.0,
            node_name="n0"),
    ] + [
        Pod(f"p{i}", requests={"cpu": 1}, arrival_time=2.0 + i, duration=1.5)
        for i in range(8)
    ]
    ec, ep = encode(cluster, pods)
    cfg = FrameworkConfig(plugins=[{"name": "NodeResourcesFit"}])
    full = JaxReplayEngine(ec, ep, cfg, wave_width=1, chunk_waves=2).replay()
    ck = str(tmp_path / "ck.npz")
    JaxReplayEngine(ec, ep, cfg, wave_width=1, chunk_waves=2).replay(
        checkpoint_path=ck, checkpoint_every=1
    )
    resumed = JaxReplayEngine(ec, ep, cfg, wave_width=1, chunk_waves=2).replay(
        checkpoint_path=ck, resume=True
    )
    np.testing.assert_array_equal(full.assignments, resumed.assignments)


def test_whatif_completions_scenario0_matches_single_replay():
    # What-if scenarios now release completed pods per scenario: the
    # unperturbed scenario must equal the single-chip replay (which has
    # completions), and a capacity-perturbed scenario must diverge the
    # usual way without breaking.
    from kubernetes_simulator_tpu.sim.whatif import (
        Perturbation,
        Scenario,
        WhatIfEngine,
    )

    cluster = make_cluster(10, seed=7)
    pods, _ = make_workload(150, seed=7, arrival_rate=15.0, duration_mean=2.0,
                            with_spread=True, with_tolerations=True)
    ec, ep = encode(cluster, pods)
    cfg = FrameworkConfig()
    scen = [
        Scenario(),
        Scenario([
            Perturbation("scale_capacity", nodes=np.arange(5),
                         resource="cpu", factor=0.5)
        ]),
    ]
    eng = WhatIfEngine(ec, ep, scen, cfg, wave_width=4, chunk_waves=4,
                       collect_assignments=True, completions=True)
    assert eng.completions_on
    res = eng.run()
    single = JaxReplayEngine(ec, ep, cfg, wave_width=4, chunk_waves=4).replay()
    np.testing.assert_array_equal(res.assignments[0], single.assignments)
    # completions must change the outcome on this trace (non-vacuous);
    # the default is ON since round 3, so force them off explicitly.
    off = WhatIfEngine(ec, ep, scen, cfg, wave_width=4, chunk_waves=4,
                       collect_assignments=True, completions=False).run()
    assert (off.assignments[0] != res.assignments[0]).any()


def test_whatif_device_release_path_matches_host_path(fork_at_start):
    """The device-side release path (no per-chunk D2H; round 3) must agree
    with the host pending-fold path: same per-scenario placed counts and
    utilization. A fork checkpoint taken before the first chunk keeps the
    second batch on the host path (collect_assignments picks no path)."""
    from kubernetes_simulator_tpu.sim.whatif import WhatIfEngine, uniform_scenarios

    cluster = make_cluster(12, seed=3, taint_fraction=0.2)
    pods, _ = make_workload(
        120, seed=3, arrival_rate=12.0, duration_mean=2.0,
        with_spread=True, with_tolerations=True,
    )
    ec, ep = encode(cluster, pods)
    cfg = FrameworkConfig()
    scen = uniform_scenarios(ec, 4, seed=3)
    dev = WhatIfEngine(ec, ep, scen, cfg, chunk_waves=4)
    assert dev._completions_dev and not dev._need_choices
    r1 = dev.run()
    host = WhatIfEngine(ec, ep, scen, cfg, chunk_waves=4, collect_assignments=True,
                        fork_checkpoint=fork_at_start(ec, ep))
    assert not host._completions_dev and host.completions_on
    r2 = host.run()
    np.testing.assert_array_equal(r1.placed, r2.placed)
    np.testing.assert_allclose(r1.utilization_cpu, r2.utilization_cpu, atol=1e-6)
    # Non-vacuous: completions change this trace's outcome.
    off = WhatIfEngine(
        ec, ep, scen, cfg, chunk_waves=4, completions=False
    ).run()
    assert (off.placed != r1.placed).any() or (
        np.abs(off.utilization_cpu - r1.utilization_cpu) > 1e-4
    ).any()


@pytest.mark.slow
def test_whatif_device_release_full_plugin_envelope(fork_at_start):
    """Round 4: the device-release path covers anti/pref count planes,
    multi-topology traces and singleton host-scale rows (the bench /
    config-3 workload shape). Device vs host pending-fold vs greedy
    anchor, plus the JaxReplayEngine twin, all value-identical."""
    from kubernetes_simulator_tpu.sim.whatif import (
        Scenario,
        WhatIfEngine,
        uniform_scenarios,
    )

    cluster = make_cluster(12, seed=5, taint_fraction=0.2)
    pods, _ = make_workload(
        140, seed=5, arrival_rate=14.0, duration_mean=2.0,
        with_affinity=True, with_spread=True, with_tolerations=True,
        gang_fraction=0.05, gang_size=2,
    )
    ec, ep = encode(cluster, pods)
    cfg = FrameworkConfig()
    scen = uniform_scenarios(ec, 4, seed=5)
    dev = WhatIfEngine(ec, ep, scen, cfg, chunk_waves=4)
    # The point of this test: affinity terms force the planes the
    # round-3 gate excluded — the path must still be the device one.
    assert dev.static3.maintain_anti or dev.static3.maintain_pref
    assert dev.static3.has_host_rows or not dev.static3.single_topo
    assert dev._completions_dev
    r1 = dev.run()
    host = WhatIfEngine(
        ec, ep, scen, cfg, chunk_waves=4, collect_assignments=True,
        fork_checkpoint=fork_at_start(ec, ep),
    )
    assert not host._completions_dev
    r2 = host.run()
    np.testing.assert_array_equal(r1.placed, r2.placed)
    np.testing.assert_allclose(
        r1.utilization_cpu, r2.utilization_cpu, atol=1e-6
    )
    # Scenario 0 == the single-replay engine == the greedy anchor.
    single = JaxReplayEngine(ec, ep, cfg, chunk_waves=4).replay()
    anchor = greedy_replay(ec, ep, cfg, completions_chunk_waves=4)
    np.testing.assert_array_equal(single.assignments, anchor.assignments)
    assert int(r1.placed[0]) == int(
        (anchor.assignments[ep.bound_node == PAD] >= 0).sum()
    )
    # Non-vacuous: releases must matter on this trace.
    off = WhatIfEngine(
        ec, ep, scen, cfg, chunk_waves=4, completions=False
    ).run()
    assert (off.placed != r1.placed).any()


def test_whatif_prebound_release_device_path():
    """Pre-bound pods live in vassign's static tail: their completion
    releases at the eligibility boundary through the device path, freeing
    capacity for later arrivals — pinned against the anchor."""
    from kubernetes_simulator_tpu.sim.whatif import Scenario, WhatIfEngine

    cluster = Cluster(nodes=[Node("n0", {"cpu": 1})])
    pods = [
        Pod("pre", requests={"cpu": 1}, arrival_time=0.0, duration=1.0,
            node_name="n0"),
        Pod("f1", requests={}, arrival_time=2.0),
        Pod("f2", requests={}, arrival_time=3.0),
        Pod("b", requests={"cpu": 1}, arrival_time=5.0),
    ]
    ec, ep = encode(cluster, pods)
    cfg = FrameworkConfig(plugins=[{"name": "NodeResourcesFit"}])
    eng = WhatIfEngine(ec, ep, [Scenario()], cfg, wave_width=1, chunk_waves=1)
    assert eng._completions_dev
    res = eng.run()
    anchor = greedy_replay(ec, ep, cfg, wave_width=1, completions_chunk_waves=1)
    assert anchor.assignments[3] == 0  # b fits once pre released
    assert int(res.placed[0]) == anchor.placed == 3
