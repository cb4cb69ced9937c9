"""v3 engine (domain-space state, wave-deferred commits) must match the
v2 node-space engine and the CPU greedy oracle EXACTLY — including with
the host-plane path forced on (tiny dmax_coarse) and with the class-mask
fallback disabled/enabled."""

import numpy as np
import pytest

from kubernetes_simulator_tpu.framework.framework import FrameworkConfig
from kubernetes_simulator_tpu.models.encode import encode
from kubernetes_simulator_tpu.sim.greedy import greedy_replay
from kubernetes_simulator_tpu.sim.jax_runtime import JaxReplayEngine
from kubernetes_simulator_tpu.sim.synthetic import make_cluster, make_workload


def _case(seed, n_nodes=60, n_pods=240):
    cluster = make_cluster(n_nodes, seed=seed, taint_fraction=0.3)
    pods, _ = make_workload(
        n_pods, seed=seed, with_affinity=True, with_spread=True,
        with_tolerations=True, gang_fraction=0.1, gang_size=3,
    )
    return encode(cluster, pods)


def _assert_same(ec, ep, **kw):
    cfg = FrameworkConfig()
    cpu = greedy_replay(ec, ep, cfg)
    v2 = JaxReplayEngine(ec, ep, cfg, engine="v2").replay()
    v3 = JaxReplayEngine(ec, ep, cfg, engine="v3", **kw).replay()
    np.testing.assert_array_equal(cpu.assignments, v2.assignments)
    np.testing.assert_array_equal(cpu.assignments, v3.assignments)
    np.testing.assert_allclose(v2.state.used, v3.state.used, atol=1e-3)
    np.testing.assert_allclose(v2.state.match_count, v3.state.match_count, atol=1e-5)
    np.testing.assert_allclose(v2.state.anti_active, v3.state.anti_active, atol=1e-5)
    return v3


@pytest.mark.parametrize(
    "seed", [0, pytest.param(1, marks=pytest.mark.slow),
             pytest.param(2, marks=pytest.mark.slow)]
)
def test_v3_matches_v2_and_cpu(seed):
    ec, ep = _case(seed)
    _assert_same(ec, ep)


@pytest.mark.slow
def test_v3_host_planes_forced():
    """dmax_coarse=4 pushes zone/rack groups onto the host-plane path —
    results must not change."""
    ec, ep = _case(3)
    _assert_same(ec, ep, dmax_coarse=4)


@pytest.mark.slow
def test_v3_class_fallback(monkeypatch):
    """Force the per-wave vmap fallback (as if every pod were distinct)."""
    from kubernetes_simulator_tpu.ops import tpu3 as V3

    monkeypatch.setattr(V3.V3Static, "MAX_CLASSES", 0)
    ec, ep = _case(4)
    _assert_same(ec, ep)


def test_v3_host_singleton_partial_labels():
    """Singleton host topology where some nodes LACK the label: binds onto
    label-less nodes must not credit the host planes (regression: the
    singleton commit fast path skipped v2's node_has_dom gate, making the
    symmetric-anti check wrongly block label-less nodes)."""
    from kubernetes_simulator_tpu.models.core import (
        Cluster, LabelSelector, Node, Pod, PodAffinitySpec, PodAffinityTerm,
    )

    key = "custom/slot"
    nodes = [
        Node(
            f"n{i}",
            capacity={"cpu": 4.0, "memory": 8 * 2**30, "pods": 20},
            labels=({key: f"s{i}"} if i % 3 != 0 else {}),  # every 3rd bare
        )
        for i in range(12)
    ]
    anti = PodAffinitySpec(
        required=(PodAffinityTerm(LabelSelector.make({"app": "a"}), key),)
    )
    pods = [
        Pod(f"p{i}", labels={"app": "a"}, requests={"cpu": 1.0},
            arrival_time=float(i), pod_anti_affinity=anti)
        for i in range(20)
    ]
    ec, ep = encode(Cluster(nodes=nodes), pods)
    # dmax_coarse=0 forces every topology onto the host-plane path; the
    # custom key's domains are singletons.
    _assert_same(ec, ep, dmax_coarse=0)


@pytest.mark.slow
def test_v3_mesh_with_host_planes():
    """Mesh-sharded what-if on a trace whose anti terms ride a hostname
    topology (>128 domains → real host planes). Regression: the sharding
    proto state used width-1 planes and crashed in from_host."""
    import jax

    from kubernetes_simulator_tpu.parallel.mesh import make_mesh
    from kubernetes_simulator_tpu.sim.whatif import Scenario, WhatIfEngine

    cluster = make_cluster(150, seed=7)
    pods, _ = make_workload(200, seed=7, with_affinity=True)
    ec, ep = encode(cluster, pods)
    mesh = make_mesh(2)
    eng = WhatIfEngine(
        ec, ep, [Scenario(), Scenario()], FrameworkConfig(),
        mesh=mesh, collect_assignments=True,
    )
    assert eng.engine == "v3" and eng.static3.has_host_rows
    res = eng.run()
    single = JaxReplayEngine(ec, ep, FrameworkConfig()).replay()
    np.testing.assert_array_equal(res.assignments[0], single.assignments)


@pytest.mark.slow
def test_v3_checkpoint_resume_identical(tmp_path):
    ec, ep = _case(5, n_pods=400)
    cfg = FrameworkConfig()
    full = JaxReplayEngine(ec, ep, cfg, chunk_waves=8).replay()
    path = str(tmp_path / "v3.ck.npz")
    eng = JaxReplayEngine(ec, ep, cfg, chunk_waves=8)
    eng.replay(checkpoint_path=path, checkpoint_every=2)
    resumed = JaxReplayEngine(ec, ep, cfg, chunk_waves=8).replay(
        checkpoint_path=path, resume=True
    )
    np.testing.assert_array_equal(full.assignments, resumed.assignments)


def test_bf16_host_planes_disabled_under_capacity_events():
    """capacity_scale node events can push per-node pod counts past the
    bf16 exactness bound — the engine must rebuild without bf16 planes."""
    from kubernetes_simulator_tpu.sim.runtime import NodeEvent

    cluster = make_cluster(150, seed=7)
    pods, _ = make_workload(300, seed=7, with_affinity=True)
    ec, ep = encode(cluster, pods)
    eng = JaxReplayEngine(ec, ep, FrameworkConfig())
    if not (eng.static3.mc_h_bf16 or eng.static3.anti_h_bf16):
        pytest.skip("trace has no bf16 host planes")
    ev = [NodeEvent(time=1.0, kind="capacity_scale", node=0, scale=3.0)]
    res = eng.replay(node_events=ev)
    assert not (eng.static3.mc_h_bf16 or eng.static3.anti_h_bf16)
    assert res.placed > 0


# --- in-wave usage corrections: the running plane (ops.tpu3) -------------
# One wave of 8 slots on two nodes, built so that a slot's choice depends
# on what the earlier slots of ITS wave used. Requests are cpu only; with
# LeastAllocated the big node wins until it is full.

def _mini(node_cpus, pod_cpus, groups=None, gangs=None):
    from kubernetes_simulator_tpu.models.core import Cluster, Node, Pod, PodGroup

    nodes = [
        Node(f"n{i}", capacity={"cpu": c, "memory": 8 * 2**30, "pods": 110})
        for i, c in enumerate(node_cpus)
    ]
    pods = [
        Pod(f"p{i}", requests={"cpu": c}, arrival_time=float(i),
            pod_group=(groups or {}).get(i))
        for i, c in enumerate(pod_cpus)
    ]
    pod_groups = {g: PodGroup(g, m) for g, m in (gangs or {}).items()}
    return encode(Cluster(nodes=nodes, pod_groups=pod_groups), pods)


_BIG = 10 ** 6  # cpus: fits nowhere, so the slot's choice is PAD
# name -> (node cpus, pod cpus, pod -> gang, gang -> min members, placements)
_WAVE_TRAPS = {
    # two / three slots bind to n1, the next no longer fits there
    "same_node_x2": ([3.0, 7.0], [3.0] * 4, None, None, [1, 1, 0, -1]),
    "same_node_x3": ([3.0, 10.0], [3.0] * 5, None, None, [1, 1, 1, 0, -1]),
    # an unplaced slot ahead of a placed one whose only node is the
    # FIRST (a clamped point update lands there) / the LAST (a wrapped
    # one) and has room for exactly that pod
    "pad_then_node0": ([1.0, 0.5], [_BIG, 1.0, 1.0], None, None, [-1, 0, -1]),
    "pad_then_last": ([0.5, 0.5, 1.0], [_BIG, 1.0, 1.0], None, None, [-1, 2, -1]),
    # a gang whose first two members fill n0 for the slots behind them
    # and whose third fits nowhere: rolled back at wave end, so the
    # next wave finds n0 empty
    "gang_rollback": (
        [7.0, 3.0], [3.0, 3.0, _BIG] + [3.0] * 8,
        {0: "g", 1: "g", 2: "g"}, {"g": 3},
        [-1, -1, -1, 1, -1, -1, -1, -1, 0, 0, -1],
    ),
}


@pytest.mark.parametrize("trap", sorted(_WAVE_TRAPS))
def test_v3_inwave_usage_traps(trap):
    node_cpus, pod_cpus, groups, gangs, want = _WAVE_TRAPS[trap]
    ec, ep = _mini(node_cpus, pod_cpus, groups, gangs)
    v3 = _assert_same(ec, ep)
    np.testing.assert_array_equal(v3.assignments, want)
    assert v3.telemetry.summary()["inwave_corrections"] == "plane"


def _nondyadic(seed, n_nodes=5, n_pods=96):
    """Contended, requests that no f32 holds exactly (0.1, 0.3, 0.7 ...):
    a sum's last bit depends on the order it was added in."""
    rng = np.random.default_rng(seed)
    cpus = rng.choice([0.1, 0.3, 0.7, 1.1, 1.3], size=n_pods)
    return _mini(list(rng.choice([3.3, 4.7, 6.1], size=n_nodes)), list(cpus))


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_v3_plane_equals_terms_bit_for_bit(seed, monkeypatch):
    """The plane accumulates from zero in slot order and is added to
    ``used`` before the slot's own request: the k-term form's association.
    Built both ways on one trace, the placements and the final ``used``
    plane have to be equal to the bit."""
    from kubernetes_simulator_tpu.ops import tpu3 as V3

    ec, ep = _nondyadic(seed)
    cfg = FrameworkConfig()
    plane = JaxReplayEngine(ec, ep, cfg, engine="v3").replay()
    monkeypatch.setattr(V3, "inwave_corrections", lambda *a, **k: "terms")
    terms = JaxReplayEngine(ec, ep, cfg, engine="v3").replay()
    assert plane.telemetry.summary()["inwave_corrections"] == "plane"
    assert terms.telemetry.summary()["inwave_corrections"] == "terms"
    assert plane.unschedulable > 0  # contended: fit edges are met
    np.testing.assert_array_equal(plane.assignments, terms.assignments)
    np.testing.assert_array_equal(plane.state.used, terms.state.used)


def _node_wide_compares(ec, ep, wave_width):
    """How many [N]-wide integer equality tests (node iota against a chosen
    node) one traced chunk program of width ``wave_width`` holds: the jaxpr
    walked through every nested jaxpr (pjit, scan, closed_call)."""
    import jax
    import jax.numpy as jnp

    eng = JaxReplayEngine(
        ec, ep, FrameworkConfig(), engine="v3", wave_width=wave_width
    )
    N = ec.num_nodes
    args = (eng.dc, eng._init_dev_state(), eng._slot_src, eng._extra_src,
            jnp.asarray(eng.waves.idx[:2]))

    def count(jaxpr):
        n = 0
        for eqn in jaxpr.eqns:
            if (eqn.primitive.name == "eq"
                    and eqn.outvars[0].aval.shape == (N,)
                    and jnp.issubdtype(eqn.invars[0].aval.dtype, jnp.integer)):
                n += 1
            for v in eqn.params.values():
                for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        n += count(sub)
        return n

    return count(jax.make_jaxpr(eng.chunk_fn)(*args).jaxpr)


def test_v3_node_wide_compares_grow_linearly_with_wave_width(monkeypatch):
    """The plane form tests the node iota against a chosen node only in the
    wave-end ``used`` update (W x R times); the k-term form adds one test
    for every earlier slot of every slot, W(W-1)/2. Keeps a refactor from
    fusing the terms back in."""
    from kubernetes_simulator_tpu.ops import tpu3 as V3

    ec, ep = _mini([4.0] * 37, [1.0] * 64)
    plane = {w: _node_wide_compares(ec, ep, w) for w in (2, 4, 8)}
    assert plane[2] > 0
    assert plane[8] - plane[4] == 2 * (plane[4] - plane[2]), plane
    monkeypatch.setattr(V3, "inwave_corrections", lambda *a, **k: "terms")
    terms = {w: _node_wide_compares(ec, ep, w) for w in (2, 4, 8)}
    assert {w: terms[w] - plane[w] for w in terms} == {2: 1, 4: 6, 8: 28}


@pytest.mark.parametrize(
    "preemption, scenario_axis, form",
    [(False, False, "plane"), (True, False, "terms"), (False, True, "terms")],
    ids=["replay", "tier-preemption", "scenario-axis"],
)
def test_inwave_corrections_form_follows_how_the_step_is_built(
    preemption, scenario_axis, form
):
    from kubernetes_simulator_tpu.ops import tpu3 as V3
    from kubernetes_simulator_tpu.sim.jax_runtime import StepSpec

    ec, ep = _mini([4.0, 4.0], [1.0] * 4)
    st = V3.V3Static.build(
        ec, ep, StepSpec.from_config(ec, FrameworkConfig(), ep),
        preemption=preemption,
    )
    assert V3.inwave_corrections(st, scenario_axis) == form
