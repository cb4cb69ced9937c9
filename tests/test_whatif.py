"""What-if scenario engine: vmap correctness vs looped evaluation, mesh
sharding on the 8-device CPU mesh, perturbation semantics (SURVEY.md §4.4-5)."""

import numpy as np
import pytest

import jax

from kubernetes_simulator_tpu.framework.framework import FrameworkConfig
from kubernetes_simulator_tpu.models.encode import PAD, encode
from kubernetes_simulator_tpu.parallel.mesh import make_mesh
from kubernetes_simulator_tpu.sim.jax_runtime import JaxReplayEngine
from kubernetes_simulator_tpu.sim.synthetic import config1, make_cluster, make_workload
from kubernetes_simulator_tpu.sim.whatif import (
    Perturbation,
    Scenario,
    WhatIfEngine,
    uniform_scenarios,
)


def small_case(seed=0, n=15, p=80):
    cluster = make_cluster(n, seed=seed, taint_fraction=0.1)
    pods, _ = make_workload(p, seed=seed, with_affinity=True, with_spread=True,
                            with_tolerations=True)
    return encode(cluster, pods)


def test_base_scenario_matches_single_replay():
    """Scenario 0 (unperturbed) must equal the plain jax engine exactly."""
    ec, ep = small_case()
    cfg = FrameworkConfig()
    single = JaxReplayEngine(ec, ep, cfg).replay()
    eng = WhatIfEngine(ec, ep, [Scenario(), Scenario()], cfg, collect_assignments=True)
    res = eng.run()
    assert (res.assignments[0] == single.assignments).all()
    assert res.placed[0] == single.placed


def test_vmap_matches_looped_perturbed_scenarios():
    """Each perturbed scenario must equal a from-scratch single replay on
    the equivalently perturbed cluster (SURVEY.md §4.5)."""
    from kubernetes_simulator_tpu.models.core import Taint

    cluster = make_cluster(12, seed=3)
    pods, _ = make_workload(60, seed=3, with_tolerations=True)
    ec, ep = encode(cluster, pods)

    down = np.array([0, 1])
    scen = [
        Scenario(),
        Scenario([Perturbation("node_down", nodes=down)]),
        Scenario([Perturbation("scale_capacity", nodes=np.arange(6), resource="cpu", factor=0.5)]),
        Scenario([Perturbation("add_taint", nodes=np.arange(4), key="k", value="v",
                               effect="NoSchedule")]),
    ]
    res = WhatIfEngine(ec, ep, scen, FrameworkConfig(), collect_assignments=True).run()

    # Reference replays with the perturbation applied to the object model.
    cluster_down = make_cluster(12, seed=3)
    for i in down:
        cluster_down.nodes[i].allocatable = {k: 0.0 for k in cluster_down.nodes[i].allocatable}
    ec2, ep2 = encode(cluster_down, pods)
    ref = JaxReplayEngine(ec2, ep2, FrameworkConfig()).replay()
    assert (res.assignments[1] == ref.assignments).all()

    cluster_half = make_cluster(12, seed=3)
    for i in range(6):
        cluster_half.nodes[i].allocatable = {
            k: (v * 0.5 if k == "cpu" else v) for k, v in cluster_half.nodes[i].allocatable.items()
        }
    ec3, ep3 = encode(cluster_half, pods)
    ref3 = JaxReplayEngine(ec3, ep3, FrameworkConfig()).replay()
    assert (res.assignments[2] == ref3.assignments).all()

    cluster_taint = make_cluster(12, seed=3)
    for i in range(4):
        cluster_taint.nodes[i].taints.append(Taint("k", "v", "NoSchedule"))
    ec4, ep4 = encode(cluster_taint, pods)
    ref4 = JaxReplayEngine(ec4, ep4, FrameworkConfig()).replay()
    assert (res.assignments[3] == ref4.assignments).all()


def test_mesh_sharded_matches_unsharded():
    """shard_map-equivalent sharded run over 8 virtual devices must equal
    the single-device vmap bit-for-bit."""
    assert len(jax.devices()) == 8
    ec, ep = small_case(seed=7)
    scen = uniform_scenarios(ec, 16, seed=7)
    cfg = FrameworkConfig()
    plain = WhatIfEngine(ec, ep, scen, cfg, collect_assignments=True).run()
    mesh = make_mesh()
    sharded = WhatIfEngine(ec, ep, scen, cfg, mesh=mesh, collect_assignments=True).run()
    assert (plain.assignments == sharded.assignments).all()
    assert (plain.placed == sharded.placed).all()


@pytest.mark.slow
def test_node_down_reduces_capacity():
    ec, ep = small_case(seed=1, n=6, p=60)
    scen = [Scenario(), Scenario([Perturbation("node_down", nodes=np.arange(3))])]
    res = WhatIfEngine(ec, ep, scen, FrameworkConfig()).run()
    assert res.placed[1] <= res.placed[0]


def test_set_label_rederives_domains():
    """Moving nodes between zones must change spread domain counts."""
    from kubernetes_simulator_tpu.models.core import (
        Cluster, LabelSelector, Node, Pod, TopologySpreadConstraint,
    )

    nodes = [Node(f"n{i}", {"cpu": 100}, labels={"zone": "za" if i < 3 else "zb"})
             for i in range(4)]
    sel = LabelSelector.make({"app": "w"})
    pods = [
        Pod(f"p{i}", labels={"app": "w"},
            topology_spread=[TopologySpreadConstraint(1, "zone", "DoNotSchedule", sel)],
            arrival_time=float(i), requests={"cpu": 1})
        for i in range(8)
    ]
    ec, ep = encode(Cluster(nodes=nodes), pods)
    # Scenario 1 moves n3 into za → single domain → skew constraint trivial.
    scen = [
        Scenario(),
        Scenario([Perturbation("set_label", nodes=np.array([3]), key="zone", value="za")]),
    ]
    res = WhatIfEngine(ec, ep, scen, FrameworkConfig(), collect_assignments=True).run()
    assert res.placed[0] == 8 and res.placed[1] == 8
    # In the base, placements must spread between za and zb nodes.
    a0 = res.assignments[0]
    assert (a0 < 3).any() and (a0 >= 3).any()


def test_scenario_count_must_divide_devices():
    ec, ep = small_case(seed=2, n=5, p=10)
    with pytest.raises(ValueError):
        WhatIfEngine(ec, ep, [Scenario()] * 3, mesh=make_mesh())


def test_injected_prefer_taint_reenables_score_row():
    """The taint score row is statically dropped when the base cluster has
    no PreferNoSchedule taints; a what-if scenario that injects one must
    re-enable it (scores change where the taint lands)."""
    from kubernetes_simulator_tpu.models.core import Taint

    cluster = make_cluster(12, seed=9)  # no taints in the base cluster
    pods, _ = make_workload(80, seed=9)
    ec, ep = encode(cluster, pods)
    from kubernetes_simulator_tpu.sim.jax_runtime import StepSpec

    assert not StepSpec.from_config(ec, FrameworkConfig(), ep).taint_score
    scen = [
        Scenario(),
        Scenario([Perturbation("add_taint", nodes=np.arange(6), key="soft",
                               value="x", effect="PreferNoSchedule")]),
    ]
    eng = WhatIfEngine(ec, ep, scen, FrameworkConfig(), collect_assignments=True)
    assert eng.spec.taint_score  # re-enabled by the injection
    res = eng.run()

    # Reference: from-scratch replay on the equivalently tainted cluster.
    cluster_t = make_cluster(12, seed=9)
    for n in cluster_t.nodes[:6]:
        n.taints.append(Taint("soft", "x", "PreferNoSchedule"))
    ec_t, ep_t = encode(cluster_t, pods)
    ref = JaxReplayEngine(ec_t, ep_t, FrameworkConfig()).replay()
    np.testing.assert_array_equal(res.assignments[1], ref.assignments)


ZONE = "topology.kubernetes.io/zone"


def _relabel(nodes, key=ZONE, value="zone-1"):
    return Scenario([Perturbation(
        "set_label", nodes=np.asarray(nodes, np.int64), key=key, value=value)])


def _envelope_case(reason, tmp_path):
    """(ec, ep, scenarios, engine keywords) of a small ``set_label`` batch
    outside the DynTables envelope for ``reason`` alone."""
    kw = {}
    cluster = make_cluster(40, seed=3)
    pods, _ = make_workload(30, seed=3, with_spread=True)
    scen = [Scenario(), _relabel([0, 4])]
    if reason == "host-scale topology change":
        # 150 hostnames: a host-scale topology (ops.tpu3.DMAX_COARSE = 128)
        # that the affinity terms name.
        cluster = make_cluster(150, seed=1)
        pods, _ = make_workload(50, seed=1, with_affinity=True)
        scen = [Scenario(), _relabel([0], "kubernetes.io/hostname", "elsewhere")]
    elif reason == ">32 perturbed nodes/scenario (K=33)":
        scen = [Scenario(), _relabel(np.arange(33))]
    elif reason == "fork checkpoint":
        ec, ep = encode(cluster, pods)
        kw["fork_checkpoint"] = str(tmp_path / "ck.npz")
        JaxReplayEngine(ec, ep, FrameworkConfig(), chunk_waves=2).replay(
            checkpoint_path=kw["fork_checkpoint"], checkpoint_every=1)
    elif reason == "pre-bound pods":
        pods[0].node_name = cluster.nodes[5].name
    elif reason == "no DynTables":
        scen = [Scenario(), _relabel([])]
    elif reason == "preemption":
        kw["preemption"] = True
    return (*encode(cluster, pods), scen, kw)


@pytest.mark.parametrize("reason", [
    "host-scale topology change",
    ">32 perturbed nodes/scenario (K=33)",
    "fork checkpoint",
    "pre-bound pods",
    "no DynTables",
    "preemption",
])
def test_set_label_outside_the_envelope_is_refused(reason, tmp_path):
    """A ``set_label`` batch the per-scenario domain tables cannot carry is
    refused when the engine is built, with the reason and the way that
    still runs it; the same batch without the relabel builds."""
    ec, ep, scen, kw = _envelope_case(reason, tmp_path)
    with pytest.raises(ValueError, match="outside the DynTables envelope") as exc:
        WhatIfEngine(ec, ep, scen, FrameworkConfig(), **kw)
    assert f"({reason})" in str(exc.value)
    assert "one single replay per scenario" in str(exc.value)
    WhatIfEngine(ec, ep, [Scenario(), Scenario()], FrameworkConfig(), **kw)


def test_the_envelope_refusal_names_every_reason():
    ec, ep, scen, _ = _envelope_case(">32 perturbed nodes/scenario (K=33)", None)
    ep.bound_node[0] = 5
    with pytest.raises(
        ValueError,
        match=r"\(>32 perturbed nodes/scenario \(K=33\), pre-bound pods\)",
    ):
        WhatIfEngine(ec, ep, scen, FrameworkConfig())


@pytest.mark.slow
def test_labels_dirty_runs_v3_and_matches_scratch():
    """Round-3 DynTables: label-perturbation batches run on per-scenario
    domain tables and must match a from-scratch replay of each explicitly
    perturbed cluster. Cases: move to an existing value,
    a NEW value (appended domain id), emptying a domain (its last node
    moves out — the spread min must exclude it), a node GAINING the key,
    and mixed taint/capacity perturbations in the same batch."""
    import copy

    from kubernetes_simulator_tpu.sim.jax_runtime import JaxReplayEngine
    from kubernetes_simulator_tpu.sim.synthetic import make_cluster, make_workload

    cluster = make_cluster(18, seed=5, taint_fraction=0.1)
    zkey = "topology.kubernetes.io/zone"
    # Give one zone exactly one node (emptying case) and strip the key
    # from one node (gaining case).
    cluster.nodes[7].labels[zkey] = "zonly"
    del cluster.nodes[11].labels[zkey]
    pods, _ = make_workload(
        70, seed=5, with_affinity=True, with_spread=True, with_tolerations=True
    )
    ec, ep = encode(cluster, pods)
    cfg = FrameworkConfig()
    scen = [
        Scenario(),
        Scenario([  # existing value + capacity in one scenario
            Perturbation("set_label", nodes=np.array([0, 4]), key=zkey, value="zone-1"),
            Perturbation("scale_capacity", nodes=np.array([2]), resource="cpu", factor=0.5),
        ]),
        Scenario([  # NEW value → appended domain id
            Perturbation("set_label", nodes=np.array([1, 9]), key=zkey, value="zz-fresh"),
        ]),
        Scenario([  # empty the singleton zone
            Perturbation("set_label", nodes=np.array([7]), key=zkey, value="zone-0"),
        ]),
        Scenario([  # unlabeled node gains the key
            Perturbation("set_label", nodes=np.array([11]), key=zkey, value="zone-2"),
        ]),
        Scenario([  # taint-only scenario sharing the dirty batch
            Perturbation("add_taint", nodes=np.array([5]), key="wi", value="x", effect="NoSchedule"),
        ]),
    ]
    eng = WhatIfEngine(ec, ep, scen, cfg, chunk_waves=4, collect_assignments=True)
    assert eng.engine == "v3" and eng._dyn is not None
    res = eng.run()

    # From-scratch replay of each perturbed cluster (label/taint/capacity
    # applied to a copy, re-encoded) — chunk sizes aligned.
    for si, sc in enumerate(scen):
        c2 = copy.deepcopy(cluster)
        for pt in sc.perturbations:
            for n in np.asarray(pt.nodes).tolist():
                if pt.op == "set_label":
                    c2.nodes[n].labels[pt.key] = pt.value
                elif pt.op == "scale_capacity":
                    c2.nodes[n].allocatable = {
                        k: (v * pt.factor if k == "cpu" else v)
                        for k, v in c2.nodes[n].allocatable.items()
                    }
                elif pt.op == "add_taint":
                    from kubernetes_simulator_tpu.models.core import Taint

                    c2.nodes[n].taints.append(
                        Taint(pt.key, pt.value, pt.effect)
                    )
        ec2, ep2 = encode(c2, pods)
        single = JaxReplayEngine(ec2, ep2, cfg, chunk_waves=4).replay()
        np.testing.assert_array_equal(
            res.assignments[si], single.assignments,
            err_msg=f"scenario {si} diverged from from-scratch replay",
        )


@pytest.mark.slow
def test_labels_dirty_mesh_matches_unsharded():
    """DynTables shard over the scenario axis like every other per-scenario
    tensor: the 8-device mesh run must equal the unsharded batch."""
    ec, ep = small_case(seed=11, n=16, p=64)
    zkey = "topology.kubernetes.io/zone"
    rng = np.random.default_rng(11)
    scen = [Scenario()] + [
        Scenario([
            Perturbation(
                "set_label", nodes=rng.choice(16, 2, replace=False),
                key=zkey, value=f"zone-{rng.integers(0, 8)}",
            )
        ])
        for _ in range(7)
    ]
    cfg = FrameworkConfig()
    plain = WhatIfEngine(ec, ep, scen, cfg, chunk_waves=4, collect_assignments=True)
    assert plain.engine == "v3" and plain._dyn is not None
    res = plain.run()
    sharded = WhatIfEngine(
        ec, ep, scen, cfg, chunk_waves=4, collect_assignments=True,
        mesh=make_mesh(),
    )
    assert sharded.engine == "v3" and sharded._dyn is not None
    res2 = sharded.run()
    np.testing.assert_array_equal(res.assignments, res2.assignments)


@pytest.mark.slow
def test_config5_scale_1024_scenarios_mesh():
    """[BASELINE] config #5 at its STATED scenario count: 1024 scenarios
    mesh-sharded over the 8 virtual devices (tiny nodes/pods so the smoke
    stays cheap — the point is exercising S=1024 end-to-end, 128
    scenarios per device, not just divisibility)."""
    assert len(jax.devices()) == 8
    ec, ep = small_case(seed=9, n=12, p=48)
    scen = uniform_scenarios(ec, 1024, seed=9)
    cfg = FrameworkConfig()
    mesh = make_mesh()
    res = WhatIfEngine(
        ec, ep, scen, cfg, chunk_waves=4, mesh=mesh
    ).run()
    assert res.placed.shape == (1024,)
    assert int(res.placed[0]) > 0
    # Scenario 0 (unperturbed) equals the single-replay anchor.
    single = JaxReplayEngine(ec, ep, cfg, chunk_waves=4).replay()
    assert int(res.placed[0]) == int(
        (single.assignments[ep.bound_node == -1] >= 0).sum()
    )


def test_labels_dirty_with_completions_device_path():
    """Round 4: labels_dirty × completions — supported by the DEVICE
    release path (per-scenario domain corrections ride the commit
    blocks). Each perturbed scenario's placed count must equal a
    from-scratch replay of the explicitly perturbed cluster with
    completions on; the un-dirty twin batch confirms completions stay
    on (completions_on=True) rather than silently dropping."""
    import copy

    from kubernetes_simulator_tpu.sim.jax_runtime import JaxReplayEngine
    from kubernetes_simulator_tpu.sim.synthetic import make_cluster, make_workload

    cluster = make_cluster(6, seed=17, taint_fraction=0.1)
    zkey = "topology.kubernetes.io/zone"
    del cluster.nodes[5].labels[zkey]  # gaining case
    pods, _ = make_workload(
        400, seed=17, arrival_rate=40.0, duration_mean=1.5,
        with_spread=True, with_tolerations=True,
    )
    ec, ep = encode(cluster, pods)
    cfg = FrameworkConfig()
    scen = [
        Scenario(),
        Scenario([  # existing value move
            Perturbation("set_label", nodes=np.array([0, 3]), key=zkey,
                         value="zone-1"),
        ]),
        Scenario([  # NEW value → appended domain id
            Perturbation("set_label", nodes=np.array([2]), key=zkey,
                         value="zz-new"),
        ]),
        Scenario([  # unlabeled node gains the key
            Perturbation("set_label", nodes=np.array([5]), key=zkey,
                         value="zone-0"),
        ]),
    ]
    eng = WhatIfEngine(ec, ep, scen, cfg, chunk_waves=4)
    assert eng.engine == "v3" and eng._dyn is not None
    assert eng.completions_on and eng._completions_dev
    res = eng.run()
    assert res.completions_on

    for si, sc in enumerate(scen):
        c2 = copy.deepcopy(cluster)
        for pt in sc.perturbations:
            for n in np.asarray(pt.nodes).tolist():
                c2.nodes[n].labels[pt.key] = pt.value
        ec2, ep2 = encode(c2, pods)
        single = JaxReplayEngine(ec2, ep2, cfg, chunk_waves=4).replay()
        assert int(res.placed[si]) == single.placed, (
            f"scenario {si}: whatif {int(res.placed[si])} vs "
            f"from-scratch {single.placed}"
        )

    # Non-vacuous: completions change the outcome on this trace.
    off = WhatIfEngine(
        ec, ep, scen, cfg, chunk_waves=4, completions=False
    ).run()
    assert (off.placed != res.placed).any()
