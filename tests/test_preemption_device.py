"""Tier preemption on the greedy engines: the host anchor (sim.greedy
preemption=True) and the v3 device path must agree exactly; kube's
minimal-victims PostFilter stays in the CPU event engine
(tests/test_replay_cpu.py)."""

import numpy as np
import pytest

from kubernetes_simulator_tpu.framework.framework import FrameworkConfig
from kubernetes_simulator_tpu.models.core import Cluster, Node, Pod
from kubernetes_simulator_tpu.models.encode import PAD, encode
from kubernetes_simulator_tpu.sim.greedy import greedy_replay
from kubernetes_simulator_tpu.sim.jax_runtime import JaxReplayEngine
from kubernetes_simulator_tpu.sim.synthetic import make_cluster, make_workload


def _tight_case(seed, n_nodes=30, n_pods=220, **wl):
    """Over-committed cluster so preemption actually fires."""
    cluster = make_cluster(n_nodes, seed=seed, taint_fraction=0.2)
    pods, _ = make_workload(n_pods, seed=seed, with_tolerations=True, **wl)
    return encode(cluster, pods)


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_device_matches_anchor(seed):
    ec, ep = _tight_case(seed, with_spread=True)
    cfg = FrameworkConfig()
    a = greedy_replay(ec, ep, cfg, preemption=True)
    d = JaxReplayEngine(ec, ep, cfg, preemption=True).replay()
    np.testing.assert_array_equal(a.assignments, d.assignments)
    assert a.placed == d.placed
    assert a.preemptions == d.preemptions


def test_device_matches_anchor_with_gangs():
    ec, ep = _tight_case(7, gang_fraction=0.15, gang_size=3)
    cfg = FrameworkConfig()
    a = greedy_replay(ec, ep, cfg, preemption=True)
    d = JaxReplayEngine(ec, ep, cfg, preemption=True).replay()
    np.testing.assert_array_equal(a.assignments, d.assignments)
    assert a.preemptions == d.preemptions


def test_preemption_places_high_priority():
    nodes = [Node(f"n{i}", capacity={"cpu": 4.0, "memory": 8 * 2**30, "pods": 10})
             for i in range(4)]
    pods = [Pod(f"lo{i}", labels={"app": "lo"}, requests={"cpu": 1.0},
                priority=0, arrival_time=float(i)) for i in range(16)]
    pods += [Pod(f"hi{i}", labels={"app": "hi"}, requests={"cpu": 2.0},
                 priority=100, arrival_time=100.0 + i) for i in range(4)]
    ec, ep = encode(Cluster(nodes=nodes), pods)
    off = JaxReplayEngine(ec, ep, FrameworkConfig()).replay()
    on = JaxReplayEngine(ec, ep, FrameworkConfig(), preemption=True).replay()
    hi = np.arange(16, 20)
    assert (off.assignments[hi] >= 0).sum() == 0
    assert (on.assignments[hi] >= 0).sum() >= 2  # once-per-wave cap
    assert on.preemptions > 0
    # Usage stays consistent: evicted pods freed their resources.
    used = on.state.used[:, ec.vocab._r["cpu"]]
    assert (used <= 4.0 + 1e-5).all()


@pytest.mark.slow
def test_whatif_preemption_matches_single_replay():
    from kubernetes_simulator_tpu.sim.whatif import Scenario, WhatIfEngine

    ec, ep = _tight_case(5, n_nodes=20, n_pods=160, with_spread=True)
    cfg = FrameworkConfig()
    eng = WhatIfEngine(
        ec, ep, [Scenario(), Scenario()], cfg,
        collect_assignments=True, preemption=True,
    )
    res = eng.run()
    single = JaxReplayEngine(ec, ep, cfg, preemption=True).replay()
    np.testing.assert_array_equal(res.assignments[0], single.assignments)
    assert int(res.placed[0]) == single.placed
    # Tally path (no assignment collection) agrees.
    eng2 = WhatIfEngine(ec, ep, [Scenario(), Scenario()], cfg, preemption=True)
    res2 = eng2.run()
    np.testing.assert_array_equal(res2.placed, res.placed)


def test_preemption_guards():
    ec, ep = _tight_case(0)
    with pytest.raises(ValueError):
        JaxReplayEngine(ec, ep, FrameworkConfig(), preemption=True).replay(
            checkpoint_path="/tmp/x.npz", checkpoint_every=1
        )
    # Host-plane rows (hostname anti terms at scale) are rejected.
    cluster = make_cluster(150, seed=1)
    pods, _ = make_workload(50, seed=1, with_affinity=True)
    ec2, ep2 = encode(cluster, pods)
    from kubernetes_simulator_tpu.ops import tpu3 as V3
    from kubernetes_simulator_tpu.sim.jax_runtime import StepSpec

    spec = StepSpec.from_config(ec2, FrameworkConfig(), ep2)
    if V3.V3Static.build(ec2, ep2, spec).has_host_rows:
        with pytest.raises(ValueError):
            JaxReplayEngine(ec2, ep2, FrameworkConfig(), preemption=True)


def test_prebound_pods_preempted_single_replay():
    """Pre-bound low-priority pods occupy the cluster; the replay engine's
    tier planes must see them (reviewer repro: what-if once silently
    ignored pre-bound usage)."""
    nodes = [Node(f"n{i}", capacity={"cpu": 2.0, "memory": 4 * 2**30, "pods": 5})
             for i in range(2)]
    pods = [Pod(f"pre{i}", labels={"app": "lo"}, requests={"cpu": 2.0},
                priority=0, arrival_time=0.0, node_name=f"n{i}")
            for i in range(2)]
    pods += [Pod(f"hi{i}", labels={"app": "hi"}, requests={"cpu": 2.0},
                 priority=100, arrival_time=10.0 + i) for i in range(2)]
    ec, ep = encode(Cluster(nodes=nodes), pods)
    a = greedy_replay(ec, ep, FrameworkConfig(), preemption=True)
    d = JaxReplayEngine(ec, ep, FrameworkConfig(), preemption=True).replay()
    np.testing.assert_array_equal(a.assignments, d.assignments)
    assert d.preemptions >= 1
    assert (d.assignments[2:] >= 0).any()  # a hi pod got in
    assert (d.assignments[:2] == PAD).any()  # a pre-bound pod was evicted


def test_whatif_preemption_rejects_prebound():
    from kubernetes_simulator_tpu.sim.whatif import Scenario, WhatIfEngine

    nodes = [Node("n0", capacity={"cpu": 2.0, "memory": 4 * 2**30, "pods": 5})]
    pods = [Pod("pre", labels={}, requests={"cpu": 1.0}, priority=0,
                arrival_time=0.0, node_name="n0"),
            Pod("hi", labels={}, requests={"cpu": 2.0}, priority=10,
                arrival_time=1.0)]
    ec, ep = encode(Cluster(nodes=nodes), pods)
    with pytest.raises(ValueError):
        WhatIfEngine(ec, ep, [Scenario()], FrameworkConfig(), preemption=True)


def test_preemption_with_completions_tiny():
    """Round 4: preemption × completions is a supported device config.
    lo's completion (not an eviction) frees the node; hi then fits
    WITHOUT preempting mid. Releases drop the tier planes, so a later
    eviction check sees the freed capacity."""
    cluster = Cluster(nodes=[Node("n0", {"cpu": 2})])
    pods = [
        Pod("lo", requests={"cpu": 2}, arrival_time=0.0, duration=3.0,
            priority=0),
        Pod("f1", requests={}, arrival_time=5.0),
        Pod("f2", requests={}, arrival_time=6.0),
        Pod("hi", requests={"cpu": 2}, arrival_time=10.0, priority=100),
    ]
    ec, ep = encode(cluster, pods)
    cfg = FrameworkConfig(plugins=[{"name": "NodeResourcesFit"}])
    a = greedy_replay(
        ec, ep, cfg, wave_width=1, preemption=True,
        completions_chunk_waves=1,
    )
    assert a.assignments[0] == 0 and a.assignments[3] == 0
    assert a.preemptions == 0  # completion freed it, no eviction needed
    d = JaxReplayEngine(
        ec, ep, cfg, wave_width=1, chunk_waves=1, preemption=True,
    ).replay()
    np.testing.assert_array_equal(a.assignments, d.assignments)
    assert d.preemptions == 0 and d.placed == a.placed


def test_preemption_evicts_then_victim_never_releases():
    """An evicted pod must NOT release resources at its old completion
    time (it no longer holds them) — the planes would go negative and
    later placements would over-fit. hi evicts lo; at lo's would-be
    completion nothing is released; a second 2-cpu pod must NOT fit
    while hi is running."""
    cluster = Cluster(nodes=[Node("n0", {"cpu": 2})])
    pods = [
        Pod("lo", requests={"cpu": 2}, arrival_time=0.0, duration=6.0,
            priority=0),
        Pod("f1", requests={}, arrival_time=1.0, priority=200),
        Pod("f2", requests={}, arrival_time=2.0, priority=200),
        Pod("hi", requests={"cpu": 2}, arrival_time=3.0, duration=100.0,
            priority=100),
        Pod("f3", requests={}, arrival_time=7.0, priority=200),
        Pod("f4", requests={}, arrival_time=8.0, priority=200),
        # lo's arrival+duration (6.0) has passed; if its phantom release
        # fired, probe would fit. It must not.
        Pod("probe", requests={"cpu": 2}, arrival_time=9.0, priority=0),
    ]
    ec, ep = encode(cluster, pods)
    cfg = FrameworkConfig(plugins=[{"name": "NodeResourcesFit"}])
    a = greedy_replay(
        ec, ep, cfg, wave_width=1, preemption=True,
        completions_chunk_waves=1,
    )
    assert a.assignments[0] == PAD  # evicted
    assert a.assignments[3] == 0
    assert a.assignments[6] == PAD  # no phantom release
    assert a.preemptions == 1
    d = JaxReplayEngine(
        ec, ep, cfg, wave_width=1, chunk_waves=1, preemption=True,
    ).replay()
    np.testing.assert_array_equal(a.assignments, d.assignments)
    assert d.preemptions == 1


def test_completed_pod_not_evicted():
    """A completed pod keeps its assignment (it ran to completion) and
    must not appear as an eviction victim; its capacity is already free
    so hi fits without any preemption."""
    cluster = Cluster(nodes=[Node("n0", {"cpu": 2})])
    pods = [
        Pod("lo", requests={"cpu": 2}, arrival_time=0.0, duration=1.0,
            priority=0),
        Pod("f1", requests={}, arrival_time=2.0),
        Pod("f2", requests={}, arrival_time=3.0),
        Pod("hi", requests={"cpu": 2}, arrival_time=5.0, priority=100),
    ]
    ec, ep = encode(cluster, pods)
    cfg = FrameworkConfig(plugins=[{"name": "NodeResourcesFit"}])
    a = greedy_replay(
        ec, ep, cfg, wave_width=1, preemption=True,
        completions_chunk_waves=1,
    )
    assert a.assignments[0] == 0  # completed, assignment kept
    assert a.assignments[3] == 0
    assert a.preemptions == 0
    d = JaxReplayEngine(
        ec, ep, cfg, wave_width=1, chunk_waves=1, preemption=True,
    ).replay()
    np.testing.assert_array_equal(a.assignments, d.assignments)
    assert d.preemptions == 0


@pytest.mark.parametrize(
    "seed", [pytest.param(2, marks=pytest.mark.slow), 3])
def test_preemption_completions_parity_random(seed):
    """Random over-committed workload WITH durations: device preemption ×
    completions must match the anchor exactly. Shape tuned so BOTH
    mechanisms fire (evictions occur AND completions change placements)."""
    ec, ep = _tight_case(
        seed, n_nodes=8, n_pods=400, with_spread=True,
        duration_mean=20.0, arrival_rate=12.0,
    )
    cfg = FrameworkConfig()
    a = greedy_replay(
        ec, ep, cfg, preemption=True, completions_chunk_waves=4
    )
    d = JaxReplayEngine(
        ec, ep, cfg, preemption=True, chunk_waves=4
    ).replay()
    np.testing.assert_array_equal(a.assignments, d.assignments)
    assert a.placed == d.placed
    assert a.preemptions == d.preemptions
    # Non-vacuous: both mechanisms fire on this trace.
    assert a.preemptions > 0
    off = greedy_replay(ec, ep, cfg, preemption=True)
    assert (off.assignments != a.assignments).any()


def _replay_with_fusion(ec, ep, cfg, fused, **kw):
    """Build + replay inside a FUSED_PREEMPT patch window — the flag is
    read at trace time, so the program variant is picked here."""
    from kubernetes_simulator_tpu.ops import tpu3 as V3

    old = V3.FUSED_PREEMPT
    V3.FUSED_PREEMPT = fused
    try:
        return JaxReplayEngine(ec, ep, cfg, preemption=True, **kw).replay()
    finally:
        V3.FUSED_PREEMPT = old


# Tier mixes for the fused-program parity sweep (round 10): tier count
# drives the packed-prefix width AND the batched-commit einsum shapes, so
# sweep sparse/dense/skewed priority populations.
TIER_MIXES = [
    (0, 100),
    (0, 50, 100),
    (0, 10, 100, 1000),
    (0, 0, 0, 1000),  # skewed: one hot tier over a deep low-tier pool
]


@pytest.mark.parametrize("tiers", TIER_MIXES, ids=lambda t: "x".join(map(str, t)))
def test_fused_tier_mix_parity(tiers):
    """Fused preempt-select (ops.tpu3.FUSED_PREEMPT) vs the retained
    pre-fusion program vs the CPU anchor: bit-identical assignments,
    placement counts, eviction counts, and usage planes across tier
    mixes. Priorities ramp upward over arrival time so later tiers
    actually preempt earlier ones (non-vacuous: asserts evictions)."""
    n_pods = 72
    nodes = [
        Node(f"n{i}", capacity={"cpu": 4.0, "memory": 8 * 2**30, "pods": 12})
        for i in range(6)
    ]
    pods = [
        Pod(
            f"p{i}", labels={"app": f"a{i % 3}"},
            requests={"cpu": [0.5, 1.0, 2.0][i % 3]},
            priority=tiers[min(len(tiers) - 1, (i * len(tiers)) // n_pods)],
            arrival_time=float(i),
        )
        for i in range(n_pods)
    ]
    ec, ep = encode(Cluster(nodes=nodes), pods)
    cfg = FrameworkConfig()
    a = greedy_replay(ec, ep, cfg, preemption=True)
    fused = _replay_with_fusion(ec, ep, cfg, True)
    pre = _replay_with_fusion(ec, ep, cfg, False)
    np.testing.assert_array_equal(fused.assignments, a.assignments)
    np.testing.assert_array_equal(fused.assignments, pre.assignments)
    assert fused.placed == a.placed == pre.placed
    assert fused.preemptions == a.preemptions == pre.preemptions
    assert fused.preemptions > 0  # the mix must actually exercise eviction
    np.testing.assert_array_equal(
        np.asarray(fused.state.used), np.asarray(pre.state.used)
    )


@pytest.mark.parametrize("seed", [0])
@pytest.mark.slow
def test_fused_matches_prefusion_random(seed):
    """Randomized over-committed traces (gangs, spread, tolerations):
    the fused and pre-fusion device programs must be BIT-identical —
    assignments and f32 usage planes. One seed here (tier-1 budget);
    the fuzz_quick slice flips the flag on every preempt trial."""
    ec, ep = _tight_case(seed, with_spread=True, gang_fraction=0.1,
                         gang_size=3)
    cfg = FrameworkConfig()
    fused = _replay_with_fusion(ec, ep, cfg, True)
    pre = _replay_with_fusion(ec, ep, cfg, False)
    np.testing.assert_array_equal(fused.assignments, pre.assignments)
    assert fused.placed == pre.placed
    assert fused.preemptions == pre.preemptions
    np.testing.assert_array_equal(
        np.asarray(fused.state.used), np.asarray(pre.state.used)
    )


def test_masked_argmin_matches_reference():
    """The fused victim-select helper must pick exactly what the
    argmax(where(mask, -score, -inf)) + any(mask) pair picked — including
    lowest-index tie-breaks and the all-masked-out case."""
    import jax.numpy as jnp

    from kubernetes_simulator_tpu.ops import tpu as T

    rng = np.random.default_rng(0)
    for _ in range(25):
        s = rng.integers(0, 5, 32).astype(np.float32)  # dense ties
        m = rng.random(32) < 0.4
        choice, ok = T.masked_argmin(jnp.asarray(s), jnp.asarray(m))
        if m.any():
            assert bool(ok)
            assert int(choice) == int(np.argmax(np.where(m, -s, -np.inf)))
        else:
            assert not bool(ok)
            assert int(choice) == PAD


def test_gang_completion_does_not_corrupt_tier_planes():
    """A completed GANG pod must not be subtracted from the tier planes
    (which never accumulate gang pods — gangs are not evictable): the
    corruption under-counted evictable usage and skipped required
    evictions (round-4 review repro)."""
    cluster = Cluster(nodes=[Node("n0", {"cpu": 2})])
    pods = [
        Pod("g0", requests={"cpu": 1}, arrival_time=0.0, duration=2.0,
            pod_group="g", priority=0),
        Pod("g1", requests={"cpu": 1}, arrival_time=0.0, duration=2.0,
            pod_group="g", priority=0),
        Pod("f1", requests={}, arrival_time=3.0, priority=200),
        Pod("f2", requests={}, arrival_time=4.0, priority=200),
        # lo refills the node after the gang completes...
        Pod("lo", requests={"cpu": 2}, arrival_time=5.0, duration=100.0,
            priority=0),
        Pod("f3", requests={}, arrival_time=6.0, priority=200),
        Pod("f4", requests={}, arrival_time=7.0, priority=200),
        # ...and hi must evict lo — negative tier planes would hide it.
        Pod("hi", requests={"cpu": 2}, arrival_time=8.0, priority=100),
    ]
    ec, ep = encode(cluster, pods)
    cfg = FrameworkConfig(plugins=[{"name": "NodeResourcesFit"}])
    a = greedy_replay(
        ec, ep, cfg, wave_width=2, preemption=True,
        completions_chunk_waves=1,
    )
    assert a.assignments[7] == 0 and a.preemptions == 1
    d = JaxReplayEngine(
        ec, ep, cfg, wave_width=2, chunk_waves=1, preemption=True,
    ).replay()
    np.testing.assert_array_equal(a.assignments, d.assignments)
    assert d.preemptions == a.preemptions
