"""CPU↔JAX parity — the load-bearing suite (SURVEY.md §4.2).

The numpy greedy wave replay and the jitted lax.scan replay implement the
same algorithm independently; placements must agree exactly on randomized
workloads covering every plugin, gangs included.
"""

import numpy as np
import pytest

from kubernetes_simulator_tpu.framework.framework import FrameworkConfig
from kubernetes_simulator_tpu.framework.registry import get_strategy
from kubernetes_simulator_tpu.models.encode import PAD, encode
from kubernetes_simulator_tpu.sim.greedy import greedy_replay
from kubernetes_simulator_tpu.sim.jax_runtime import JaxReplayEngine
from kubernetes_simulator_tpu.sim.synthetic import config1, make_cluster, make_workload


def assert_parity(cluster, pods, plugins=None, wave_width=8, **jax_kw):
    ec, ep = encode(cluster, pods)
    cfg = FrameworkConfig(plugins=plugins)
    cpu_res = greedy_replay(ec, ep, FrameworkConfig(plugins=plugins), wave_width=wave_width)
    jax_res = JaxReplayEngine(ec, ep, cfg, wave_width=wave_width, **jax_kw).replay()
    mismatch = np.nonzero(cpu_res.assignments != jax_res.assignments)[0]
    assert mismatch.size == 0, (
        f"{mismatch.size} mismatches, first at pod {mismatch[:5]}: "
        f"cpu={cpu_res.assignments[mismatch[:5]]} jax={jax_res.assignments[mismatch[:5]]}"
    )
    assert cpu_res.placed == jax_res.placed
    np.testing.assert_allclose(cpu_res.state.used, jax_res.state.used, atol=1e-3)
    np.testing.assert_allclose(
        cpu_res.state.match_count, jax_res.state.match_count, atol=1e-5
    )
    return cpu_res, jax_res


def test_parity_fit_only():
    cluster, pods, plugins = config1(num_nodes=40, num_pods=300)
    assert_parity(cluster, pods, plugins)


@pytest.mark.parametrize("seed", range(3))
def test_parity_full_plugin_set(seed):
    cluster = make_cluster(25, seed=seed, taint_fraction=0.2)
    pods, _ = make_workload(
        120, seed=seed, with_affinity=True, with_spread=True, with_tolerations=True
    )
    assert_parity(cluster, pods)


def test_parity_with_gangs():
    cluster = make_cluster(15, seed=5)
    pods, meta = make_workload(80, seed=5, gang_fraction=0.2, gang_size=3)
    assert meta["num_gangs"] > 0
    assert_parity(cluster, pods)


def test_parity_gang_infeasible_rolls_back_identically():
    # Two tiny nodes: a 4-pod gang of 1 cpu each (4 total) can never fully
    # fit (capacity 3), so gang rollback is exercised on both paths.
    from kubernetes_simulator_tpu.models.core import Cluster, Node, Pod

    cluster = Cluster(nodes=[Node("n0", {"cpu": 2}), Node("n1", {"cpu": 1})])
    pods = []
    for g in range(3):
        for m in range(4):
            pods.append(
                Pod(
                    f"g{g}-m{m}",
                    requests={"cpu": 1},
                    arrival_time=float(g * 4 + m),
                    pod_group=f"gang-{g}",
                )
            )
    pods.append(Pod("single", requests={"cpu": 1}, arrival_time=100.0))
    # wave_width=4 → each gang gets its own wave, the singleton its own:
    # rollback happens at the gang's wave boundary, so the singleton sees a
    # clean cluster.
    cpu_res, jax_res = assert_parity(cluster, pods, wave_width=4)
    assert cpu_res.unschedulable == 12  # every gang rolled back
    assert cpu_res.assignments[-1] >= 0  # the singleton still fits


def test_parity_extended_resources_multitenant():
    cluster = make_cluster(20, seed=3, extended_resources={"google.com/tpu": (8, 0.3)})
    pods, _ = make_workload(
        100, seed=3, extended_resource=("google.com/tpu", 8, 0.3), gang_fraction=0.1, gang_size=4
    )
    assert_parity(cluster, pods)


def test_parity_chunked_equals_single_shot():
    cluster, pods, plugins = config1(num_nodes=20, num_pods=200)
    ec, ep = encode(cluster, pods)
    one = JaxReplayEngine(ec, ep, FrameworkConfig(plugins=plugins), chunk_waves=10_000).replay()
    many = JaxReplayEngine(ec, ep, FrameworkConfig(plugins=plugins), chunk_waves=4).replay()
    assert (one.assignments == many.assignments).all()


def test_registry_selects_jax():
    cluster, pods, plugins = config1(num_nodes=10, num_pods=40)
    ec, ep = encode(cluster, pods)
    eng = get_strategy("jax")(ec, ep, FrameworkConfig(plugins=plugins))
    res = eng.replay()
    assert res.placed == 40


def test_jax_determinism():
    cluster, pods, _ = config1(num_nodes=15, num_pods=100)
    ec, ep = encode(cluster, pods)
    r1 = JaxReplayEngine(ec, ep, FrameworkConfig(plugins=None)).replay()
    r2 = JaxReplayEngine(ec, ep, FrameworkConfig(plugins=None)).replay()
    assert (r1.assignments == r2.assignments).all()


def test_parity_bootstrap_on_domainless_node():
    """A pod placed via the bootstrap exception on a node WITHOUT the
    topology label must not count toward the group total — a later pod with
    the same required term still gets the bootstrap (regression: device
    match_total once counted domainless binds; ops/cpu.py total is
    match_count.sum which never sees them)."""
    from kubernetes_simulator_tpu.models.core import (
        Cluster, LabelSelector, Node, Pod, PodAffinitySpec, PodAffinityTerm,
    )

    zone = "topology.kubernetes.io/zone"
    nodes = [
        # Has the zone label but too small for any pod below.
        Node("n-zoned", capacity={"cpu": 0.5, "memory": 1, "pods": 10},
             labels={zone: "a"}),
        # Fits everything but has NO zone label → no domain under `zone`.
        Node("n-bare", capacity={"cpu": 8, "memory": 32, "pods": 10}),
    ]
    aff = PodAffinitySpec(
        required=(PodAffinityTerm(LabelSelector.make({"app": "x"}), zone),)
    )
    pods = [
        Pod("a", labels={"app": "x"}, requests={"cpu": 1}, arrival_time=0.0,
            pod_affinity=aff),
        Pod("b", labels={"app": "x"}, requests={"cpu": 1}, arrival_time=1.0,
            pod_affinity=aff),
    ]
    cpu_res, jax_res = assert_parity(Cluster(nodes=nodes), pods)
    # Both pods bootstrap onto the bare node; neither may be unschedulable.
    assert cpu_res.placed == 2
