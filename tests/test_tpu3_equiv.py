"""The device engine (domain-space state, wave-deferred commits) must match
the CPU greedy oracle EXACTLY — including with the host-plane path forced
on (tiny dmax_coarse) and with the class-mask fallback disabled/enabled."""

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
    v3 = JaxReplayEngine(ec, ep, cfg, **kw).replay()
    np.testing.assert_array_equal(cpu.assignments, v3.assignments)
    np.testing.assert_allclose(cpu.state.used, v3.state.used, atol=1e-3)
    np.testing.assert_allclose(cpu.state.match_count, v3.state.match_count, atol=1e-5)
    np.testing.assert_allclose(cpu.state.anti_active, v3.state.anti_active, atol=1e-5)
    return v3


@pytest.mark.parametrize(
    "seed", [0, pytest.param(1, marks=pytest.mark.slow),
             pytest.param(2, marks=pytest.mark.slow)]
)
def test_v3_matches_cpu(seed):
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
    ec, ep = _partial_label_slots()
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
    plane = JaxReplayEngine(ec, ep, cfg).replay()
    monkeypatch.setattr(V3, "inwave_corrections", lambda *a, **k: "terms")
    terms = JaxReplayEngine(ec, ep, cfg).replay()
    assert plane.telemetry.summary()["inwave_corrections"] == "plane"
    assert terms.telemetry.summary()["inwave_corrections"] == "terms"
    assert plane.unschedulable > 0  # contended: fit edges are met
    np.testing.assert_array_equal(plane.assignments, terms.assignments)
    np.testing.assert_array_equal(plane.state.used, terms.state.used)


def _scoped_eqns(jaxpr, outer=""):
    """(equation, its whole name stack) over a jaxpr and the jaxprs nested
    in it: a nested jaxpr's name stacks are relative to its call."""
    for eqn in jaxpr.eqns:
        scope = f"{outer}/{eqn.source_info.name_stack}"
        yield eqn, scope
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _scoped_eqns(sub, scope)


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs nested in it."""
    return (eqn for eqn, _ in _scoped_eqns(jaxpr))


def _node_wide_compares(ec, ep, wave_width):
    """How many [N]-wide integer equality tests (node iota against a chosen
    node) one traced chunk program of width ``wave_width`` holds, nested
    jaxprs (pjit, scan, closed_call) included."""
    import jax
    import jax.numpy as jnp

    eng = JaxReplayEngine(
        ec, ep, FrameworkConfig(), wave_width=wave_width
    )
    N = ec.num_nodes
    args = (eng.dc, eng._init_dev_state(), eng._slot_src, eng._extra_src,
            jnp.asarray(eng.waves.idx[:2]))

    return sum(
        eqn.primitive.name == "eq"
        and eqn.outvars[0].aval.shape == (N,)
        and jnp.issubdtype(eqn.invars[0].aval.dtype, jnp.integer)
        for eqn in _eqns(jax.make_jaxpr(eng.chunk_fn)(*args).jaxpr)
    )


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
    [(False, False, "plane"), (True, False, "terms"),
     (False, True, "resolved_terms"), (True, True, "terms")],
    ids=["replay", "tier-preemption", "scenario-axis",
         "tier-preemption-on-a-scenario-axis"],
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


# --- in-wave usage corrections resolved among scalars (ops.tpu3) ----------
# A step mapped over a scenario axis keeps the k chosen-node compares of the
# k-term form, but each picks R scalars that already hold the node's sum
# (`resolve_usage`). Held to the plane and to the summed terms in the single
# replay (the form forced on it), and to the summed terms under `vmap`.


def _pileup(seed=0, n_pods=96):
    """One node far larger than the other: LeastAllocated sends all 8 slots
    of every wave to it until it is full, with requests no f32 holds."""
    rng = np.random.default_rng(seed)
    return _mini([50.3, 0.9], list(rng.choice([0.1, 0.3, 0.7, 1.1, 1.3], size=n_pods)))


def _extended_resource_case(seed=0, n_nodes=12, n_pods=120):
    """R = 4: every fourth node holds 8 `example.com/dev`, the others 0;
    a third of the pods ask for 1 or 2 of them, all for a cpu request no
    float32 holds exactly. The extended resource runs out first."""
    from kubernetes_simulator_tpu.models.core import Cluster, Node, Pod

    rng = np.random.default_rng(seed)
    nodes = [
        Node(f"n{i}", capacity={
            "cpu": float(c), "memory": 8 * 2**30, "pods": 110,
            **({"example.com/dev": 8} if i % 4 == 0 else {}),
        })
        for i, c in enumerate(rng.choice([3.3, 4.7, 6.1], size=n_nodes))
    ]
    pods = [
        Pod(f"p{i}", arrival_time=float(i), requests={
            "cpu": float(rng.choice([0.1, 0.3, 0.7, 1.1])),
            **({"example.com/dev": int(rng.choice([1, 2]))} if i % 3 == 0 else {}),
        })
        for i in range(n_pods)
    ]
    return encode(Cluster(nodes=nodes), pods)


# name -> (trace, the most slots of one wave that have to meet on one node)
_COLLIDING = {
    "extended-resource": (_extended_resource_case, 2),
    "nondyadic-0": (lambda: _nondyadic(0), 2),
    "nondyadic-1": (lambda: _nondyadic(1), 2),
    "nondyadic-2": (lambda: _nondyadic(2), 2),
    "pileup": (_pileup, 8),
}


def _most_slots_on_one_node(assignments, wave_width=8):
    waves = assignments[: len(assignments) // wave_width * wave_width]
    return max(
        np.bincount(w[w >= 0]).max()
        for w in waves.reshape(-1, wave_width) if (w >= 0).any()
    )


def _forced(monkeypatch, form):
    from kubernetes_simulator_tpu.ops import tpu3 as V3

    monkeypatch.setattr(V3, "inwave_corrections", lambda *a, **k: form)


def _whatif_arrivals(ec, ep, scenarios, chunk_waves=4):
    """(result, the final device state, the engine's static facts) of an
    arrivals-only what-if that hands every pod's node back."""
    from kubernetes_simulator_tpu.sim.whatif import WhatIfEngine

    eng = WhatIfEngine(ec, ep, scenarios, FrameworkConfig(), wave_width=8,
                       chunk_waves=chunk_waves, collect_assignments=True)
    assert eng.engine == "v3"
    last, chunk_fn = {}, eng._chunk_fn

    def spy(*args):
        out = chunk_fn(*args)
        last["state"] = out[0]
        return out

    eng._chunk_fn = spy
    return eng.run(), last["state"], eng.static3


def _whatif_used(ec, ep, scenarios):
    """(result, final ``used`` [S, R, N]) of an arrivals-only what-if."""
    res, state, _ = _whatif_arrivals(ec, ep, scenarios)
    return res, np.asarray(state.used)


@pytest.mark.parametrize("case", sorted(_COLLIDING))
def test_v3_resolved_terms_equal_plane_and_terms_bit_for_bit(case, monkeypatch):
    """96 pods on 5 nodes, or piled onto one, or 120 of which a third ask for
    an extended resource (R = 4): 2 to 8 slots of a wave collide on one
    node, with requests no float32 holds exactly. The single replay
    built in all three forms gives the same placements and the same final
    ``used``, to the bit."""
    make, collide = _COLLIDING[case]
    ec, ep = make()
    cfg = FrameworkConfig()
    runs = {"plane": JaxReplayEngine(ec, ep, cfg).replay()}
    for form in ("resolved_terms", "terms"):
        _forced(monkeypatch, form)
        runs[form] = JaxReplayEngine(ec, ep, cfg).replay()
    for form, run in runs.items():
        assert run.telemetry.summary()["inwave_corrections"] == form
    got = runs["resolved_terms"]
    assert got.unschedulable > 0  # contended: fit edges are met
    assert _most_slots_on_one_node(got.assignments) >= collide
    for form in ("plane", "terms"):
        np.testing.assert_array_equal(got.assignments, runs[form].assignments)
        np.testing.assert_array_equal(got.state.used, runs[form].state.used)


@pytest.mark.parametrize("case", sorted(_COLLIDING))
def test_whatif_resolved_terms_equal_summed_terms_bit_for_bit(case, monkeypatch):
    """The same traces under a scenario axis of 4 (`vmap`): every scenario's
    placements and final ``used`` equal the summed terms', and scenario 0
    the single replay's plane."""
    make, collide = _COLLIDING[case]
    ec, ep = make()
    scen = _perturbed(ec.num_nodes, 4)
    res, used = _whatif_used(ec, ep, scen)
    assert res.fleet_telemetry.summary()["inwave_corrections"] == "resolved_terms"
    single = JaxReplayEngine(ec, ep, FrameworkConfig()).replay()
    np.testing.assert_array_equal(res.assignments[0], single.assignments)
    np.testing.assert_array_equal(used[0], single.state.used.T)
    assert _most_slots_on_one_node(res.assignments[0]) >= collide
    assert (res.assignments[1:] != res.assignments[0]).any()
    if case == "extended-resource":  # R = 4, and the fourth row decides
        assert (ec.allocatable[:, 3] == 0).sum() == 9
        assert (used[:, 3] > 0).any() and (res.unschedulable > 0).all()
    _forced(monkeypatch, "terms")
    parent, parent_used = _whatif_used(ec, ep, scen)
    assert parent.fleet_telemetry.summary()["inwave_corrections"] == "terms"
    np.testing.assert_array_equal(res.assignments, parent.assignments)
    np.testing.assert_array_equal(used, parent_used)


@pytest.mark.parametrize("trap", sorted(_WAVE_TRAPS))
def test_whatif_resolved_terms_on_the_wave_traps(trap, monkeypatch):
    """The wave traps under a scenario axis of 2: both copies of the
    cluster place as the trap says, and as the summed terms do."""
    from kubernetes_simulator_tpu.sim.whatif import Scenario

    node_cpus, pod_cpus, groups, gangs, want = _WAVE_TRAPS[trap]
    ec, ep = _mini(node_cpus, pod_cpus, groups, gangs)
    res, used = _whatif_used(ec, ep, [Scenario(), Scenario()])
    assert res.fleet_telemetry.summary()["inwave_corrections"] == "resolved_terms"
    np.testing.assert_array_equal(res.assignments, [want, want])
    _forced(monkeypatch, "terms")
    parent, parent_used = _whatif_used(ec, ep, [Scenario(), Scenario()])
    np.testing.assert_array_equal(res.assignments, parent.assignments)
    np.testing.assert_array_equal(used, parent_used)


def _vmapped_step_eqns(ec, ep, scenarios=2):
    """Every equation of the what-if chunk program's jaxpr (the step under
    `vmap`), with its name stack."""
    import jax

    from kubernetes_simulator_tpu.sim.whatif import Scenario, WhatIfEngine

    eng = WhatIfEngine(ec, ep, [Scenario()] * scenarios, FrameworkConfig(),
                       wave_width=8, chunk_waves=2, collect_assignments=True)
    assert eng.engine == "v3"
    seen, chunk_fn = {}, eng._chunk_fn

    def spy(*args):
        seen.setdefault("args", args)
        return chunk_fn(*args)

    eng._chunk_fn = spy
    eng.run()
    return list(_scoped_eqns(jax.make_jaxpr(chunk_fn)(*seen["args"]).jaxpr))


def test_whatif_usage_terms_are_a_compare_and_selects_on_the_node_axis():
    """In the vmapped program the node-wide chosen-node compares of
    ``ksim.corrections`` stay W(W-1)/2 = 28, and nothing node-wide in that
    stage is float arithmetic: a term is a compare and R selects. Keeps a
    refactor from fusing the multiply-adds back in."""
    import jax.numpy as jnp

    S, N = 2, 37
    ec, ep = _mini([4.0] * N, [1.0] * 64)
    node_wide = [
        eqn for eqn, scope in _vmapped_step_eqns(ec, ep, S)
        if "ksim.corrections" in scope
        and any(v.aval.shape[-1:] == (N,) for v in eqn.outvars)
    ]
    compares = [
        eqn for eqn in node_wide
        if eqn.primitive.name == "eq"
        and jnp.issubdtype(eqn.invars[0].aval.dtype, jnp.integer)
    ]
    assert len(compares) == 28
    arithmetic = {"add", "sub", "mul", "div", "dot_general", "convert_element_type"}
    assert not [e.primitive.name for e in node_wide
                if e.primitive.name in arithmetic]
    selects = [e for e in node_wide if e.primitive.name == "select_n"]
    assert len(selects) == 28 * ec.allocatable.shape[1]


# --- one node-wide reduce a slot: the zone-packed select (ops.tpu3) -------
# The traps above on a cluster with stride zones and a scored zone spread
# (the Borg shape: the fit score and ONE zone row), so that the step's
# gate is on. Held to the CPU oracle, to v2 and to the step built in the
# two-pass form on the same trace, task for task.

_ZONE = "topology.kubernetes.io/zone"


def _zoned(node_cpus, pod_cpus, groups=None, gangs=None, zones=2, duration=None):
    from kubernetes_simulator_tpu.models.core import (
        Cluster, LabelSelector, Node, Pod, PodGroup, TopologySpreadConstraint,
    )

    nodes = [
        Node(f"n{i}", capacity={"cpu": c, "memory": 8 * 2**30, "pods": 110},
             labels={_ZONE: f"z{i % zones}"})
        for i, c in enumerate(node_cpus)
    ]
    spread = TopologySpreadConstraint(
        1, _ZONE, "ScheduleAnyway", LabelSelector.make({"app": "a"})
    )
    pods = [
        Pod(f"p{i}", labels={"app": "a"}, requests={"cpu": c},
            arrival_time=float(i), duration=duration,
            pod_group=(groups or {}).get(i), topology_spread=[spread])
        for i, c in enumerate(pod_cpus)
    ]
    pod_groups = {g: PodGroup(g, m) for g, m in (gangs or {}).items()}
    return encode(Cluster(nodes=nodes, pod_groups=pod_groups), pods)


# name -> (node cpus, pod cpus, pod -> gang, gang -> min members)
_ZONE_TRAPS = {name: trap[:4] for name, trap in _WAVE_TRAPS.items()}
# four nodes, so that two zones tile them
_ZONE_TRAPS["pad_then_last"] = ([0.5, 0.5, 0.5, 1.0], [_BIG, 1.0, 1.0], None, None)
# one wave whose slots collide on the best node of BOTH zones: n0 (zone 0)
# and n1 (zone 1) take two pods each, in turn as the spread score moves,
# then the small nodes one each, and two pods fit nowhere
_ZONE_TRAPS["collide_on_the_best_node_of_two_zones"] = (
    [4.0, 4.0, 2.0, 2.0], [2.0] * 8, None, None,
)


def _contended_zones(seed, n_nodes=16, n_pods=160, zones=8, duration=90.0):
    """Requests no f32 holds exactly on 8 stride zones, arrivals faster
    than the durations free the nodes: fit edges and releases decide."""
    rng = np.random.default_rng(seed)
    return _zoned(
        list(rng.choice([3.3, 4.7, 6.1], size=n_nodes)),
        list(rng.choice([0.3, 0.7, 1.1, 1.3], size=n_pods)),
        zones=zones, duration=duration,
    )


def _two_pass(monkeypatch):
    from kubernetes_simulator_tpu.ops import tpu3 as V3

    monkeypatch.setattr(V3, "select_form", lambda *a, **k: "two_pass")


@pytest.mark.parametrize("trap", sorted(_ZONE_TRAPS))
def test_v3_zone_packed_select_on_the_wave_traps(trap, monkeypatch):
    ec, ep = _zoned(*_ZONE_TRAPS[trap])
    v3 = _assert_same(ec, ep)
    tel = v3.telemetry.summary()
    assert tel["select_form"] == "zone_packed"
    assert tel["inwave_corrections"] == "plane"
    _two_pass(monkeypatch)
    parent = JaxReplayEngine(ec, ep, FrameworkConfig()).replay()
    assert parent.telemetry.summary()["select_form"] == "two_pass"
    np.testing.assert_array_equal(v3.assignments, parent.assignments)
    np.testing.assert_array_equal(v3.state.used, parent.state.used)
    if trap == "collide_on_the_best_node_of_two_zones":
        np.testing.assert_array_equal(
            np.sort(v3.assignments), [-1, -1, 0, 0, 1, 1, 2, 3]
        )


def _whatif(ec, ep, **kw):
    from kubernetes_simulator_tpu.sim.whatif import WhatIfEngine

    scen = _perturbed(ec.num_nodes, 4)
    eng = WhatIfEngine(ec, ep, scen, FrameworkConfig(), wave_width=8,
                       chunk_waves=2, completions=True,
                       collect_assignments=True, **kw)
    assert eng.engine == "v3" and eng._completions_dev
    return eng.run()


@pytest.mark.parametrize(
    "case", sorted(_ZONE_TRAPS) + ["contended-0", "contended-1"]
)
def test_whatif_zone_packed_select_equals_two_pass(case, monkeypatch):
    """A 4-scenario what-if (the k-term corrections, completions on the
    device-release path): every task's node in every scenario equals the
    two-pass form's, and scenario 0 the single replay's."""
    if case in _ZONE_TRAPS:
        ec, ep = _zoned(*_ZONE_TRAPS[case], duration=20.0)
    else:
        ec, ep = _contended_zones(int(case[-1]))
    res = _whatif(ec, ep)
    assert res.fleet_telemetry.summary()["select_form"] == "zone_packed"
    single = JaxReplayEngine(
        ec, ep, FrameworkConfig(), wave_width=8, chunk_waves=2
    ).replay()
    np.testing.assert_array_equal(res.assignments[0], single.assignments)
    _two_pass(monkeypatch)
    parent = _whatif(ec, ep)
    assert parent.fleet_telemetry.summary()["select_form"] == "two_pass"
    np.testing.assert_array_equal(res.assignments, parent.assignments)
    np.testing.assert_array_equal(res.placed, parent.placed)
    if case not in _ZONE_TRAPS:
        assert (res.unschedulable > 0).all()  # contended in every scenario
        assert (res.assignments[1:] != res.assignments[0]).any()


def _lowered_sha(ec, ep, cfg):
    import hashlib

    import jax.numpy as jnp

    eng = JaxReplayEngine(ec, ep, cfg, wave_width=4, chunk_waves=4)
    args = (eng.dc, eng._init_dev_state(), eng._slot_src, eng._extra_src,
            jnp.asarray(eng.waves.idx[:4]))
    text = eng.chunk_fn.lower(*args).as_text()
    return eng, hashlib.sha256(text.encode()).hexdigest()


# sha256 of `Lowered.as_text()` of the chunk program (jax 0.9.0): profiles
# outside the zone-packed gate keep their program to the byte. Taken on the
# parent of PR 30 (083b5a2) and pinned again in PR 31, on purpose: a
# max-normalised score row (TaintToleration, NodeAffinity) now divides
# through `ops.tpu.floor_div_f32`, because the chip's float32 division
# reads floor(6100 / 61) as 99 (94f14475... and 8442c1fb... before), and
# the toleration and node-affinity classes are numbered by their rows, not
# by their first pods, so that another arrival order finds the same program:
# the node-affinity profile's two class rows changed places in two constants
# and nothing else did (2d3895a1... with the first pod's class first). A PR
# that changes the step for these profiles on purpose re-pins them; one
# that meant to leave them alone has found a leak.
_PARENT_PROGRAMS = {
    "taint-score-row": "941161e7208d8dc2cba70398fcdc7fbd88351032cc1ddd8aed2185237877eb2a",
    "node-affinity-row": "ae170876ca19279d9c6ce01ff614d7fe43d0f69b566719d783f7e10b06b6a99e",
}


def _profile_with_a_node_space_row(profile):
    """The spread case of the gate tests plus ONE node-space score row: a
    PreferNoSchedule taint on every fifth node, or a preferred node
    affinity on every third pod."""
    from kubernetes_simulator_tpu.models.core import (
        MatchExpression, NodeAffinitySpec, NodeSelectorTerm,
        PreferredSchedulingTerm, Taint,
    )

    cluster = make_cluster(16, seed=5)
    pods, _ = make_workload(48, seed=5, with_spread=True)
    if profile == "taint-score-row":
        for node in cluster.nodes[::5]:
            node.taints.append(Taint("soft", "x", "PreferNoSchedule"))
    else:
        for i, node in enumerate(cluster.nodes):
            node.labels["tier"] = "hot" if i % 4 == 0 else "cold"
        prefer_hot = NodeAffinitySpec(preferred=(PreferredSchedulingTerm(
            10, NodeSelectorTerm((MatchExpression.make("tier", "In", ["hot"]),))
        ),))
        for pod in pods[::3]:
            pod.node_affinity = prefer_hot
    return encode(cluster, pods)


@pytest.mark.parametrize("profile", sorted(_PARENT_PROGRAMS))
def test_profiles_outside_the_gate_keep_the_parents_program(profile):
    """A PreferNoSchedule taint or a preferred node affinity puts a
    node-space score row into the total: two reduces, and the lowered text
    of the chunk program is the parent's."""
    ec, ep = _profile_with_a_node_space_row(profile)
    eng, sha = _lowered_sha(ec, ep, FrameworkConfig())
    assert eng.static3.seg_mode == "stride"
    assert eng.replay().telemetry.summary()["select_form"] == "two_pass"
    assert sha == _PARENT_PROGRAMS[profile]


# --- a slot's host-scale count rows, read by row index (ops.tpu3) ---------
# Where the step is mapped over a scenario axis a slot reads the ONE row of
# a host plane a term names (`host_row_reads` "rows", `host_rows_at`: a
# dynamic slice by a scenario-shared index, and only at the positions of the
# term axis that can name a host-scale group); the single replay keeps the
# wave-start contraction of all W x KT one-hots with the whole planes. The
# row read is held to that contraction in both mappings, and to the plain
# form below: each position's one-hot over its kind's H rows against the
# whole [H, N] plane, slot by slot and at EVERY position.


def _host_rows_by_contraction(st, carry, row_h_k, positions):
    import jax
    import jax.numpy as jnp

    o = st.sections
    rows = {}
    for lo, hi, plane in ((o[0], o[4], carry.mc_host),
                          (o[4], o[5], carry.anti_host),
                          (o[5], o[6], carry.pref_host)):
        if plane.shape[0]:
            for r in range(lo, hi):
                oh = (row_h_k[r] == jnp.arange(plane.shape[0])).astype(plane.dtype)
                rows[r] = jnp.einsum(
                    "h,hn->n", oh, plane, precision=jax.lax.Precision.HIGHEST,
                    preferred_element_type=jnp.float32,
                )
    return rows


_SLOT = "custom/slot"


def _slot_nodes(cpu=4.0):
    """12 nodes; every third lacks the singleton topology `custom/slot`."""
    from kubernetes_simulator_tpu.models.core import Node

    return [
        Node(f"n{i}", capacity={"cpu": cpu, "memory": 8 * 2**30, "pods": 20},
             labels=({_SLOT: f"s{i}"} if i % 3 != 0 else {}))
        for i in range(12)
    ]


def _anti_on_slot(**selector):
    from kubernetes_simulator_tpu.models.core import (
        LabelSelector, PodAffinityTerm,
    )

    return PodAffinityTerm(LabelSelector.make(selector), _SLOT)


def _partial_label_slots():
    """A singleton host topology (`custom/slot`) that every third node
    lacks, 20 pods with a required anti-affinity to their own app on it."""
    from kubernetes_simulator_tpu.models.core import (
        Cluster, Pod, PodAffinitySpec,
    )

    nodes = _slot_nodes()
    anti = PodAffinitySpec(required=(_anti_on_slot(app="a"),))
    pods = [
        Pod(f"p{i}", labels={"app": "a"}, requests={"cpu": 1.0},
            arrival_time=float(i), pod_anti_affinity=anti)
        for i in range(20)
    ]
    return encode(Cluster(nodes=nodes), pods)


def _default_plugins_136():
    """The 136-node default-plugins trace of
    tests/test_default_plugins_reference.py: hostname is a host-scale
    topology at the program's own threshold, bf16 planes."""
    from test_default_plugins_reference import case

    return case(136, 512, 5)[2:]


# name -> (trace, the threshold that forces groups onto host planes or None)
_HOST_ROW_TRACES = {
    "zone-rack-forced": (lambda: _case(3), 4),
    "singleton-partial-labels": (_partial_label_slots, 0),
    "default-plugins-136": (_default_plugins_136, None),
}


def _perturbed(N, count):
    """The base cluster and up to four perturbed copies of it."""
    from kubernetes_simulator_tpu.sim.whatif import Perturbation, Scenario

    return [
        Scenario(),
        Scenario([Perturbation("node_down", nodes=np.array([0]))]),
        Scenario([Perturbation("scale_capacity", nodes=np.arange(0, N, 2),
                               resource="cpu", factor=0.5)]),
        Scenario([Perturbation("add_taint", nodes=np.array([N - 1]),
                               key="whatif/injected", value="true",
                               effect="NoSchedule")]),
        Scenario([Perturbation("node_down", nodes=np.array([2, 3]))]),
    ][:count]


def _run_with_host_planes(ec, ep, mapping):
    """(every pod's node, the final mc_host and anti_host planes, the static
    facts) of a single replay or of a 4-scenario arrivals-only what-if."""
    nodes, planes, st = _run_with_count_planes(ec, ep, mapping)
    mc, anti = (("match_count", "anti_active") if mapping == "replay"
                else ("mc_host", "anti_host"))
    return nodes, planes[mc], planes[anti], st


@pytest.mark.parametrize("mapping", ["replay", "whatif"])
@pytest.mark.parametrize("trace", sorted(_HOST_ROW_TRACES))
def test_v3_host_row_read_equals_the_one_hot_contraction(
    trace, mapping, monkeypatch
):
    """Every pod's node and the final ``mc_host`` / ``anti_host`` planes of
    the step that reads rows by index equal, bit for bit, those of the step
    that contracts at wave start (the program's other form) and those of
    the plain form."""
    from kubernetes_simulator_tpu.ops import tpu3 as V3

    make, dmax = _HOST_ROW_TRACES[trace]
    ec, ep = make()
    if dmax is not None:
        _force_host_planes(monkeypatch, dmax)
    monkeypatch.setattr(V3, "host_row_reads", lambda *a, **k: "rows")
    nodes, mc, anti, st = _run_with_host_planes(ec, ep, mapping)
    assert st.has_host_rows and 0 < st.host_pos.sum() <= st.KT
    assert (nodes >= 0).any() and (mc.any() or anti.any())
    monkeypatch.setattr(V3, "host_row_reads", lambda *a, **k: "contraction")
    others = [_run_with_host_planes(ec, ep, mapping)]
    if mapping == "whatif":
        monkeypatch.setattr(V3, "host_row_reads", lambda *a, **k: "rows")
        monkeypatch.setattr(V3, "host_rows_at", _host_rows_by_contraction)
        others.append(_run_with_host_planes(ec, ep, mapping))
        others.append(_run_with_host_planes(ec, ep, "replay"))
        np.testing.assert_array_equal(nodes[0], others.pop()[0])
    for other in others:
        np.testing.assert_array_equal(nodes, other[0])
        np.testing.assert_array_equal(mc, other[1])
        np.testing.assert_array_equal(anti, other[2])


@pytest.mark.parametrize(
    "scenario_axis, form", [(False, "contraction"), (True, "rows")],
    ids=["replay", "scenario-axis"],
)
def test_host_row_reads_form_follows_how_the_step_is_built(scenario_axis, form):
    from kubernetes_simulator_tpu.ops import tpu3 as V3

    assert V3.host_row_reads(scenario_axis) == form


def test_whatif_host_row_reads_build_no_wave_wide_tensor_and_gather_nothing(
    monkeypatch,
):
    """The arrivals-only what-if chunk program (``jit_per_scenario_src``) on
    the 136-node default-plugins trace, 5 scenarios: no value shaped
    [S, W, KT, N] or [W, KT, S, N] (the wave-start expansion of the host
    rows, and its dots' outputs), and no gather whose indices carry the
    scenario axis in the wave scan: every scenario reads the same row, so
    ``vmap`` leaves a gather of ONE slice, [S, 1, N] at one start index,
    which XLA lowers to a dynamic slice. (The plain form contracts a
    slot's one-hot with the whole plane and slices nothing.)"""
    import jax

    from kubernetes_simulator_tpu.ops import tpu3 as V3
    from kubernetes_simulator_tpu.sim.whatif import WhatIfEngine

    ec, ep = _default_plugins_136()
    S, W, N = 5, 8, ec.num_nodes

    def traced(plain):
        if plain:
            monkeypatch.setattr(V3, "host_rows_at", _host_rows_by_contraction)
        eng = WhatIfEngine(ec, ep, _perturbed(N, S), FrameworkConfig(),
                           wave_width=W, chunk_waves=4,
                           collect_assignments=True)
        KT, seen, chunk_fn = eng.static3.KT, {}, eng._chunk_fn

        class Traced(Exception):
            pass

        def spy(*args):
            seen["jaxpr"] = jax.make_jaxpr(chunk_fn)(*args).jaxpr
            raise Traced

        eng._chunk_fn = spy
        with pytest.raises(Traced):
            eng.run()
        wide, batched_gathers, slices = set(), 0, 0
        planes = {(S, len(ids), N) for ids in (
            eng.static3.mc_h_ids, eng.static3.anti_h_ids) if len(ids)}
        (scan,) = [e for e in _eqns(seen["jaxpr"]) if e.primitive.name == "scan"]
        for eqn in _eqns(scan.params["jaxpr"].jaxpr):
            for v in eqn.outvars:
                if tuple(v.aval.shape) in ((S, W, KT, N), (W, KT, S, N)):
                    wide.add(tuple(v.aval.shape))
            if eqn.primitive.name == "gather":
                batched_gathers += S in eqn.invars[1].aval.shape
                # a host-plane row read: one row of [S, H, N], one index
                slices += (tuple(eqn.invars[0].aval.shape) in planes
                           and tuple(eqn.outvars[0].aval.shape) == (S, 1, N))
        return eng.static3, wide, batched_gathers, slices

    st, wide, batched_gathers, slices = traced(plain=False)
    assert st.has_host_rows
    assert not wide and batched_gathers == 0
    # one read a slot at each position that can name a host-scale group
    assert slices == W * int(st.host_pos.sum()) == 16
    _, wide, _, slices = traced(plain=True)
    assert slices == 0 and not wide  # a slot's contraction: [S, H, N] whole


@pytest.mark.parametrize(
    "cell, nodes, host_pos, coarse_pos",
    [("k8s5k-whatif256", 136, (False, True, False, True),
      (True, False, True, False)),
     ("borg10k-whatif128", 64, (False,), (True,)),
     ("borg10k-replay1", 64, (False,), (True,))],
)
def test_host_read_positions_of_the_cells_traces(cell, nodes, host_pos, coarse_pos):
    """``count_planes()["host_read_positions"]``: B (hostname anti-affinity,
    match counts) and MA (its symmetric check, holders) of the default-plugins
    trace's four positions A, B, SP, MA; none on the Borg trace, whose one
    position is a zone spread. ``"expand_positions"``: A (zone affinity) and
    SP (zone spread) there, the mirror half; 1 on the Borg trace, where the
    counter reads what the pods' terms can name and not what the step does:
    one ``ScheduleAnyway`` zone spread is scored in domain space and that
    step expands nothing at all. The same for twelve deals of one pod
    multiset: facts of the pods, so every seed finds one program."""
    from test_default_plugins_reference import bench

    from kubernetes_simulator_tpu.ops import tpu3 as V3
    from kubernetes_simulator_tpu.sim.jax_runtime import StepSpec

    _, _, config, _ = bench.load_cell(cell)
    gen = bench.load_part("generators", config["generator"])
    got = set()
    for seed in range(12):
        ec, ep = gen.to_program(gen.generate(config, nodes, 256, seed), config)
        st = V3.V3Static.build(
            ec, ep, StepSpec.from_config(ec, FrameworkConfig(), ep))
        planes = V3.count_planes(st)
        got.add((planes["host_read_positions"], tuple(st.host_pos),
                 st.has_host_rows, planes["expand_positions"],
                 tuple(st.coarse_pos), planes["term_rows"]))
    assert got == {(sum(host_pos), host_pos, any(host_pos), sum(coarse_pos),
                    coarse_pos, len(coarse_pos))}


# --- a slot's node-space count values, position by position (ops.tpu3) ----
# Where the step reads its host rows one by one (`host_row_reads` "rows": the
# what-if's own form, forced on the single replay here) it expands a slot's
# domain rows to node space at the positions of the term axis that can name a
# domain-scale group (`V3Static.coarse_pos`) and reads host rows and builds
# their in-wave terms at those that can name a host-scale one (`host_pos`;
# `value_positions`). The form of before PR 38, every position in both lists,
# is reached through the static facts alone: the same builder handed
# `coarse_pos` and `host_pos` all True. The single replay's own form (the
# wave-start contraction, one [KT, N] array over every position) is held to
# both.

_ZONE = "topology.kubernetes.io/zone"


def _one_position_two_scales():
    """12 nodes in 4 zones, two thirds of them with the singleton topology
    `custom/slot`; pods whose ONE required anti-affinity term names a
    hostname-scale group (app a against app d, on the slot), pods whose one
    term names a zone group (app b against app e, on the zone), the plain
    pods d and e they avoid, and pods c with a zone spread: the first
    position of the anti section, and of its symmetric check, holds a group
    of either scale. No term is against the pod's own app, so the term and
    its symmetric check each decide placements the other does not; under
    this deal of the 36 pods, leaving any ONE position out of either list
    moves pods in the single replay."""
    from kubernetes_simulator_tpu.models.core import (
        Cluster, LabelSelector, Pod, PodAffinitySpec, PodAffinityTerm,
        TopologySpreadConstraint,
    )

    nodes = _slot_nodes()
    for i, node in enumerate(nodes):
        node.labels[_ZONE] = f"z{i % 4}"
    anti = {
        "a": PodAffinitySpec(required=(_anti_on_slot(app="d"),)),
        "b": PodAffinitySpec(required=(
            PodAffinityTerm(LabelSelector.make({"app": "e"}), _ZONE),)),
    }
    spread = [TopologySpreadConstraint(
        1, _ZONE, "DoNotSchedule", LabelSelector.make({"app": "c"}))]
    pods = []
    for i, kind in enumerate(np.random.default_rng(14).choice(
            list("abcde"), 36, p=[0.3, 0.15, 0.1, 0.3, 0.15])):
        pods.append(Pod(
            f"p{i}", labels={"app": str(kind)}, requests={"cpu": 1.5},
            arrival_time=float(i),
            pod_anti_affinity=anti.get(str(kind), PodAffinitySpec()),
            topology_spread=spread if kind == "c" else []))
    return encode(Cluster(nodes=nodes), pods)


_POSITION_TRACES = {
    **_HOST_ROW_TRACES, "one-position-two-scales": (_one_position_two_scales, 4),
}


def _every_position(monkeypatch):
    """Every later static names every position as one of either scale."""
    import dataclasses

    from kubernetes_simulator_tpu.ops import tpu3 as V3

    build = V3.V3Static.build

    def every(*args, **kw):
        st = build(*args, **kw)
        return dataclasses.replace(st, coarse_pos=np.ones(st.KT, bool),
                                   host_pos=np.ones(st.KT, bool))

    monkeypatch.setattr(V3.V3Static, "build", every)


def _run_with_count_planes(ec, ep, mapping):
    """(every pod's node, the final count planes by name, the static facts)
    of a single replay or of a 4-scenario arrivals-only what-if."""
    if mapping == "replay":
        eng = JaxReplayEngine(ec, ep, FrameworkConfig(),
                              wave_width=8, chunk_waves=4)
        res = eng.replay()
        return (res.assignments, {"match_count": res.state.match_count,
                                  "anti_active": res.state.anti_active},
                eng.static3)
    res, st, static3 = _whatif_arrivals(ec, ep, _perturbed(ec.num_nodes, 4))
    return (res.assignments,
            {k: np.asarray(getattr(st, k), np.float32)
             for k in ("mc_dom", "anti_dom", "mc_host", "anti_host")},
            static3)


@pytest.mark.parametrize("mapping", ["replay", "whatif"])
@pytest.mark.parametrize("trace", sorted(_POSITION_TRACES))
def test_v3_position_split_equals_all_positions(trace, mapping, monkeypatch):
    """Every pod's node and the final ``mc_dom`` / ``anti_dom`` / ``mc_host``
    / ``anti_host`` planes of the step that builds a slot's node values at
    the positions that can hold them equal, bit for bit, those of the same
    step built with every position in both lists (the form of before PR 38)
    and those of the single replay in its own form, whose nodes scenario 0 of
    the what-if has too."""
    from kubernetes_simulator_tpu.ops import tpu3 as V3

    make, dmax = _POSITION_TRACES[trace]
    ec, ep = make()
    if dmax is not None:
        _force_host_planes(monkeypatch, dmax)
    own = _run_with_count_planes(ec, ep, "replay")  # the contraction
    monkeypatch.setattr(V3, "host_row_reads", lambda *a, **k: "rows")
    nodes, planes, st = _run_with_count_planes(ec, ep, mapping)
    assert st.has_host_rows and (nodes >= 0).any()
    assert any(p.any() for p in planes.values())
    assert (st.coarse_pos | st.host_pos).all()
    both = st.coarse_pos & st.host_pos
    if trace == "one-position-two-scales":
        o = st.sections
        assert both[o[1]] and both[o[4]] and not both[o[2]]
    # some position is left out of one list, or the split changes nothing
    assert not both.all()
    np.testing.assert_array_equal(nodes[0] if mapping == "whatif" else nodes,
                                  own[0])
    if mapping == "replay":
        for name, plane in planes.items():
            np.testing.assert_array_equal(plane, own[1][name], err_msg=name)
    _every_position(monkeypatch)
    nodes_all, planes_all, st_all = _run_with_count_planes(ec, ep, mapping)
    assert st_all.coarse_pos.all() and st_all.host_pos.all()
    np.testing.assert_array_equal(nodes, nodes_all)
    assert sorted(planes) == sorted(planes_all)
    for name, plane in planes.items():
        np.testing.assert_array_equal(plane, planes_all[name], err_msg=name)


@pytest.mark.parametrize("which, drop", [
    ("expansion", 0), ("expansion", 1), ("expansion", 2), ("host", 0), ("host", 1),
])
def test_every_position_of_the_two_scale_trace_decides_placements(
    which, drop, monkeypatch
):
    """The trace above holds the split to something: a step that leaves ONE
    of its positions out of the expansion's list (B, SP, MA) or out of the
    host rows' (B, MA) places pods elsewhere in the single replay (built
    in the what-if's form)."""
    from kubernetes_simulator_tpu.ops import tpu3 as V3

    ec, ep = _one_position_two_scales()
    _force_host_planes(monkeypatch, 4)
    monkeypatch.setattr(V3, "host_row_reads", lambda *a, **k: "rows")
    nodes, _, st = _run_with_count_planes(ec, ep, "replay")
    lists = V3.value_positions(st)
    assert [len(p) for p in lists] == [3, 2]

    def one_left_out(st, all_positions=False):
        kept = [list(p) for p in lists]
        del kept[which == "host"][drop]
        return tuple(kept)

    monkeypatch.setattr(V3, "value_positions", one_left_out)
    assert (_run_with_count_planes(ec, ep, "replay")[0] != nodes).any()


def test_whatif_slot_builds_no_count_value_over_all_positions(monkeypatch):
    """The arrivals-only what-if chunk program (``jit_per_scenario_src``) on
    the 136-node default-plugins trace, 5 scenarios: the wave scan holds no
    float32 value shaped [S, KT, N] or [KT, S, N] (KT = 4: a slot's node
    values over every position), and each slot's expansion, the one
    contraction whose result carries the scenario and the node axis, has
    ``coarse_pos.sum()`` = 2 rows. Built with every position in both lists
    the same program holds both: what the test looks for is there to see."""
    import jax

    from kubernetes_simulator_tpu.sim.whatif import WhatIfEngine

    ec, ep = _default_plugins_136()
    S, W, N = 5, 8, ec.num_nodes

    def traced():
        eng = WhatIfEngine(ec, ep, _perturbed(N, S), FrameworkConfig(),
                           wave_width=W, chunk_waves=4,
                           collect_assignments=True)
        st, seen, chunk_fn = eng.static3, {}, eng._chunk_fn

        class Traced(Exception):
            pass

        def spy(*args):
            seen["jaxpr"] = jax.make_jaxpr(chunk_fn)(*args).jaxpr
            raise Traced

        eng._chunk_fn = spy
        with pytest.raises(Traced):
            eng.run()
        over_all, expansion_rows = 0, []
        (scan,) = [e for e in _eqns(seen["jaxpr"]) if e.primitive.name == "scan"]
        for eqn in _eqns(scan.params["jaxpr"].jaxpr):
            for v in eqn.outvars:
                shape = tuple(getattr(v.aval, "shape", ()))
                if v.aval.dtype == np.float32 and shape in (
                        (S, st.KT, N), (st.KT, S, N)):
                    over_all += 1
                if (eqn.primitive.name == "dot_general" and len(shape) == 3
                        and S in shape and N in shape):
                    (rows,) = [d for d in shape if d not in (S, N)]
                    expansion_rows.append(rows)
        return st, over_all, expansion_rows

    st, over_all, expansion_rows = traced()
    assert st.KT == 4 and tuple(st.coarse_pos) == (True, False, True, False)
    assert over_all == 0
    assert expansion_rows == [int(st.coarse_pos.sum())] * W == [2] * W
    _every_position(monkeypatch)
    st, over_all, expansion_rows = traced()
    assert over_all > 0 and expansion_rows == [st.KT] * W


# --- the wave-end commit of the host-scale count rows (ops.tpu3) ----------
# The whole-number planes (`mc_host`, `anti_host`) of a singleton-domain
# topology take a wave's binds at the chosen nodes with no matrix product
# (`host_commit_form`): the step mapped over a scenario axis row by row where
# no pod names two rows of a plane (`host_named_row_add`, "rows"), every other
# step over the whole plane (`host_rows_add`, "elementwise"). The parent's
# form, the [W, H] x [W, N] dot with the wave's node one-hots, is kept HERE as
# the plain form both are held to.


def _host_rows_add_by_dot(plane, coef, choice):
    import jax
    import jax.numpy as jnp

    oh_all = (
        (jnp.arange(plane.shape[1])[None, :] == choice[:, None])
        & (choice[:, None] >= 0)
    ).astype(jnp.bfloat16)  # [W, N]
    delta = jnp.einsum(
        "wh,wn->hn", coef, oh_all, precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )
    return (plane.astype(jnp.float32) + delta).astype(plane.dtype)


def _commit_by_dot(monkeypatch, calls=None):
    """Both no-dot forms of the program replaced by the plain form."""
    from kubernetes_simulator_tpu.ops import tpu3 as V3

    def by_dot(plane, coef, choice, named=None):
        if calls is not None:
            calls.append(named is not None)
        return _host_rows_add_by_dot(plane, coef, choice)

    monkeypatch.setattr(V3, "host_rows_add", by_dot)
    monkeypatch.setattr(V3, "host_named_row_add", by_dot)


def _assert_the_dots_answers(monkeypatch, ec, ep, mapping, got, calls=None):
    """(nodes, mc, anti) of the step as built equal those of the step that
    commits through the plain form."""
    _commit_by_dot(monkeypatch, calls)
    other = _run_with_host_planes(ec, ep, mapping)
    for ours, theirs in zip(got, other[:3]):
        np.testing.assert_array_equal(ours, theirs)


def _force_host_planes(monkeypatch, dmax):
    from kubernetes_simulator_tpu.ops import tpu3 as V3

    build = V3.V3Static.build
    monkeypatch.setattr(
        V3.V3Static, "build",
        lambda ec, ep, spec, dmax_coarse=None, **kw: build(ec, ep, spec, dmax, **kw),
    )


@pytest.mark.parametrize("mapping", ["replay", "whatif"])
@pytest.mark.parametrize("trace", sorted(_HOST_ROW_TRACES))
def test_v3_host_commit_without_a_dot_equals_the_dot(trace, mapping, monkeypatch):
    """Every pod's node and the final ``mc_host`` / ``anti_host`` planes of
    the step that adds a wave's binds with no matrix product (row by row
    under the scenario axis, over the whole plane in the single replay)
    equal, bit for bit, those of the step that commits them through the
    parent's dot."""
    from kubernetes_simulator_tpu.ops import tpu3 as V3

    make, dmax = _HOST_ROW_TRACES[trace]
    ec, ep = make()
    if dmax is not None:
        _force_host_planes(monkeypatch, dmax)
    nodes, mc, anti, st = _run_with_host_planes(ec, ep, mapping)
    assert st.has_host_rows and (nodes >= 0).any() and (mc.any() or anti.any())
    form = V3.host_commit_form(st, scenario_axis=mapping == "whatif")
    assert form["dot"] == 0
    assert sum(form.values()) == V3.count_planes(st)["host_rows"]
    calls = []
    _assert_the_dots_answers(monkeypatch, ec, ep, mapping, (nodes, mc, anti), calls)
    # a plane of singleton domains goes through an add, row by row under
    # the scenario axis alone; one whose groups credit a whole zone or rack
    # keeps the general branch in both runs
    assert bool(calls) == any(
        len(ids) and st.single_g[ids].all()
        for ids in (st.mc_h_ids, st.anti_h_ids))
    assert set(calls) <= {mapping == "whatif"}
    assert (form["rows"] > 0) == (mapping == "whatif" and bool(calls))


def _gangs_on_partial_label_slots():
    """The partial-label cluster of ``_partial_label_slots`` at 2 cpu a
    node, and gangs of pods of 1 cpu with a required anti-affinity to their
    own app on the singleton topology: a labelled node takes one pod, a
    label-less one two, 16 places. Three gangs of 4 take 12; the gang of 6
    finds 4 places and not 6 and is rolled back in its wave; a gang of 2
    after it takes two of the places it gave back."""
    from kubernetes_simulator_tpu.models.core import (
        Cluster, Pod, PodAffinitySpec, PodGroup,
    )

    anti = PodAffinitySpec(required=(_anti_on_slot(app="a"),))
    sizes = [4, 4, 4, 6, 2, 2]
    pods, groups = [], {}
    for g, size in enumerate(sizes):
        groups[f"g{g}"] = PodGroup(f"g{g}", size)
        for _ in range(size):
            i = len(pods)
            pods.append(Pod(
                f"p{i}", labels={"app": "a"}, requests={"cpu": 1.0},
                arrival_time=float(i), pod_anti_affinity=anti,
                pod_group=f"g{g}"))
    return encode(Cluster(nodes=_slot_nodes(2.0), pod_groups=groups), pods), sizes


@pytest.mark.parametrize("mapping", ["replay", "whatif"])
def test_v3_host_commit_leaves_out_a_rolled_back_gang_and_label_less_nodes(
    mapping, monkeypatch
):
    """A gang with a hostname-scale anti-affinity term that is rolled back in
    its wave (the ``wv`` mask) on a topology some nodes lack (the
    ``has_dom_h`` gate): the planes hold the binds that stand, on labelled
    nodes only: an integer count made in numpy from the answers, and the
    dot's planes."""
    from kubernetes_simulator_tpu.ops import tpu3 as V3

    (ec, ep), sizes = _gangs_on_partial_label_slots()
    _force_host_planes(monkeypatch, 0)
    nodes, mc, anti, st = _run_with_host_planes(ec, ep, mapping)
    assert st.has_gangs and st.single_g[st.mc_h_ids].all()
    base = nodes[0] if mapping == "whatif" else nodes
    starts = np.cumsum([0] + sizes)
    gangs = [base[a:b] for a, b in zip(starts[:-1], starts[1:])]
    assert all((g >= 0).all() or (g < 0).all() for g in gangs)  # never split
    rolled = [i for i, g in enumerate(gangs) if (g < 0).all()]
    later = [i for i, g in enumerate(gangs) if (g >= 0).all()]
    # a gang was rolled back though places were left: a later gang took them
    assert rolled and max(later) > min(rolled)
    labelled = np.array([i % 3 != 0 for i in range(ec.num_nodes)])
    # ONE row in each plane (every pod matches and holds the one term); the
    # single replay hands its state back by domain (the labelled nodes in
    # node order), the what-if step's planes are [S, H, N].
    for s, placed in enumerate(nodes if mapping == "whatif" else nodes[None]):
        count = np.bincount(placed[placed >= 0], minlength=ec.num_nodes)
        assert count[~labelled].sum() > 0  # binds the planes must not hold
        for plane in (mc, anti):
            if mapping == "whatif":
                np.testing.assert_array_equal(plane[s], [count * labelled])
            else:
                np.testing.assert_array_equal(plane, [count[labelled]])
    _assert_the_dots_answers(monkeypatch, ec, ep, mapping, (nodes, mc, anti))


def _one_node_filled_to_the_bf16_bound():
    """Three nodes with hostname labels; only n0 has room, for exactly 256
    pods (``pods`` 256, the bound of a bfloat16 count). 260 pods of app
    ``a`` and 4 of app ``c`` with a required hostname anti-affinity to ``a``
    (so the ``(a, hostname)`` match counts are carried): 256 land on n0."""
    from kubernetes_simulator_tpu.models.core import (
        Cluster, LabelSelector, Node, Pod, PodAffinitySpec, PodAffinityTerm,
    )

    key = "kubernetes.io/hostname"
    nodes = [
        Node(f"n{i}", labels={key: f"n{i}"},
             capacity={"cpu": 1000.0 if i == 0 else 0.05,
                       "memory": 64 * 2**30, "pods": 256})
        for i in range(3)
    ]
    anti = PodAffinitySpec(
        required=(PodAffinityTerm(LabelSelector.make({"app": "a"}), key),)
    )
    pods = [
        Pod(f"p{i}", labels={"app": "a"}, requests={"cpu": 0.1},
            arrival_time=float(i))
        for i in range(260)
    ] + [
        Pod(f"c{i}", labels={"app": "c"}, requests={"cpu": 0.1},
            arrival_time=260.0 + i, pod_anti_affinity=anti)
        for i in range(4)
    ]
    return encode(Cluster(nodes=nodes), pods)


@pytest.mark.parametrize("mapping", ["replay", "whatif"])
def test_v3_host_commit_fills_a_bf16_plane_to_its_bound(mapping, monkeypatch):
    """A bfloat16 ``mc_host`` row committed up to 256, the largest count the
    plane's dtype holds exactly (``_host_plane``'s bound): equal to an
    integer count made in numpy from the answers, and to the dot's plane."""
    from kubernetes_simulator_tpu.ops import tpu3 as V3

    ec, ep = _one_node_filled_to_the_bf16_bound()
    _force_host_planes(monkeypatch, 0)
    nodes, mc, anti, st = _run_with_host_planes(ec, ep, mapping)
    assert st.mc_h_bf16 and len(st.mc_h_ids) == 1
    for s, placed in enumerate(nodes if mapping == "whatif" else nodes[None]):
        count = np.bincount(placed[:260][placed[:260] >= 0], minlength=3)
        assert (placed[260:] < 0).all()
        # scenario 1 of `_perturbed` has n0 down and places nothing
        assert count[0] == (0 if s == 1 else 256) and count[1:].sum() == 0
        np.testing.assert_array_equal(
            mc[s] if mapping == "whatif" else mc, [count])
    _assert_the_dots_answers(monkeypatch, ec, ep, mapping, (nodes, mc, anti))


def test_whatif_host_commit_builds_no_dot_and_no_wave_one_hot(monkeypatch):
    """The arrivals-only what-if chunk program (``jit_per_scenario_src``) on
    the 136-node default-plugins trace, 5 scenarios: inside the wave scan no
    ``dot_general`` whose result has a host plane's shape ([S, H, N] in any
    order) and, with neither tier preemption nor a ``pref_host`` plane, no
    bfloat16 one-hot of the wave's nodes ([S, W, N]); each slot adds ONE
    [S, N] row to each plane, at an index without the scenario axis. The
    plain form (the parent's dot) builds the dots and the one-hot."""
    import jax
    import jax.numpy as jnp

    from kubernetes_simulator_tpu.ops import tpu3 as V3
    from kubernetes_simulator_tpu.sim.whatif import WhatIfEngine

    ec, ep = _default_plugins_136()
    S, W, N = 5, 8, ec.num_nodes

    def traced(plain):
        if plain:
            _commit_by_dot(monkeypatch)
        eng = WhatIfEngine(ec, ep, _perturbed(N, S), FrameworkConfig(),
                           wave_width=W, chunk_waves=4,
                           collect_assignments=True)
        st, seen, chunk_fn = eng.static3, {}, eng._chunk_fn

        class Traced(Exception):
            pass

        def spy(*args):
            seen["jaxpr"] = jax.make_jaxpr(chunk_fn)(*args).jaxpr
            raise Traced

        eng._chunk_fn = spy
        with pytest.raises(Traced):
            eng.run()
        planes = {tuple(sorted((S, len(ids), N)))
                  for ids in (st.mc_h_ids, st.anti_h_ids) if len(ids)}
        dots, one_hots, row_adds = 0, 0, 0
        (scan,) = [e for e in _eqns(seen["jaxpr"]) if e.primitive.name == "scan"]
        for eqn in _eqns(scan.params["jaxpr"].jaxpr):
            for v in eqn.outvars:
                shape = tuple(sorted(v.aval.shape))
                dots += eqn.primitive.name == "dot_general" and shape in planes
                one_hots += (v.aval.dtype == jnp.bfloat16
                             and shape == tuple(sorted((S, W, N))))
            if eqn.primitive.name == "scatter-add":
                plane, index, row = (tuple(x.aval.shape) for x in eqn.invars)
                # ONE row of [S, H, N], at an index every scenario shares
                row_adds += (tuple(sorted(plane)) in planes
                             and S not in index and row == (S, N))
        return st, dots, one_hots, row_adds

    st, dots, one_hots, row_adds = traced(plain=False)
    assert st.has_host_rows and not st.preemption and not len(st.pref_h_ids)
    assert st.mc_h_one_row and st.anti_h_one_row
    assert V3.host_commit_form(st, scenario_axis=True) == {
        "rows": len(st.mc_h_ids) + len(st.anti_h_ids), "elementwise": 0,
        "dot": 0}
    # a row a slot in each of the two planes, and nothing else
    assert (dots, one_hots, row_adds) == (0, 0, 2 * W)
    _, dots, one_hots, row_adds = traced(plain=True)
    # one dot a plane, over one one-hot
    assert dots == 2 and one_hots >= 1 and row_adds == 0


@pytest.mark.parametrize("scenario_axis", [False, True],
                         ids=["replay", "scenario-axis"])
@pytest.mark.parametrize(
    "cell, nodes, host_rows",
    [("k8s5k-whatif256", 136, True), ("borg10k-whatif128", 64, False),
     ("borg10k-replay1", 64, False), ("multitenant-mesh4", 64, False)],
)
def test_host_commit_form_of_the_cells_traces(cell, nodes, host_rows,
                                              scenario_axis):
    """``count_planes()["host_commit"]``: every host row of the
    default-plugins trace (hostname anti-affinity: match counts and holders,
    whole numbers, no pod naming two rows of a plane) is committed row by
    row where the step is mapped over a scenario axis and over the whole
    plane in the single replay, none through a dot; the Borg and the
    multi-tenant traces carry no host row: 0, 0 and 0."""
    from test_default_plugins_reference import bench

    from kubernetes_simulator_tpu.ops import tpu3 as V3
    from kubernetes_simulator_tpu.sim.jax_runtime import StepSpec

    _, _, config, _ = bench.load_cell(cell)
    gen = bench.load_part("generators", config["generator"])
    ec, ep = gen.to_program(gen.generate(config, nodes, 256, 3), config)
    st = V3.V3Static.build(
        ec, ep, StepSpec.from_config(ec, FrameworkConfig(), ep))
    planes = V3.count_planes(st, scenario_axis)
    rows = len(st.mc_h_ids) + len(st.anti_h_ids)
    assert planes["host_commit"] == V3.host_commit_form(st, scenario_axis) == {
        "rows": rows if scenario_axis else 0,
        "elementwise": 0 if scenario_axis else rows, "dot": 0}
    assert planes["host_rows"] == rows and (rows > 0) == host_rows
    assert st.mc_h_one_row and st.anti_h_one_row


def _two_rows_a_pod():
    """The partial-label cluster with pods of TWO apps at once, each with a
    required anti-affinity to both on the singleton topology: a bind adds
    to two rows of ``mc_host`` and to two of ``anti_host``."""
    from kubernetes_simulator_tpu.models.core import (
        Cluster, Pod, PodAffinitySpec,
    )

    anti = PodAffinitySpec(
        required=(_anti_on_slot(app="a"), _anti_on_slot(team="t")))
    pods = [
        Pod(f"p{i}", labels={"app": "a", "team": "t"}, requests={"cpu": 1.0},
            arrival_time=float(i), pod_anti_affinity=anti)
        for i in range(20)
    ]
    return encode(Cluster(nodes=_slot_nodes()), pods)


def test_a_pod_that_names_two_rows_keeps_the_whole_plane_add(monkeypatch):
    """``mc_h_one_row`` / ``anti_h_one_row``: where a pod's bind adds to two
    rows of a plane the step commits the plane whole under the scenario axis
    too, and its planes are the dot's."""
    from kubernetes_simulator_tpu.ops import tpu3 as V3

    ec, ep = _two_rows_a_pod()
    _force_host_planes(monkeypatch, 0)
    nodes, mc, anti, st = _run_with_host_planes(ec, ep, "whatif")
    assert len(st.mc_h_ids) == len(st.anti_h_ids) == 2
    assert not st.mc_h_one_row and not st.anti_h_one_row
    assert V3.host_commit_form(st, scenario_axis=True) == {
        "rows": 0, "elementwise": 4, "dot": 0}
    count = np.bincount(nodes[0][nodes[0] >= 0], minlength=ec.num_nodes)
    labelled = np.array([i % 3 != 0 for i in range(ec.num_nodes)])
    np.testing.assert_array_equal(mc[0], [count * labelled] * 2)
    np.testing.assert_array_equal(anti[0], [count * labelled] * 2)
    calls = []
    _assert_the_dots_answers(monkeypatch, ec, ep, "whatif", (nodes, mc, anti), calls)
    assert calls and not any(calls)  # the whole-plane add, never the row's


def test_a_preferred_hostname_term_keeps_the_dot_and_the_one_hot():
    """A preferred pod-affinity term on a host-scale singleton topology puts
    fractional weights into ``pref_host``: its rows keep the dot (the sum
    depends on the order of its additions) and ``host_commit_form`` says so;
    the whole-number rows beside them are elementwise."""
    from kubernetes_simulator_tpu.models.core import (
        LabelSelector, PodAffinitySpec, PodAffinityTerm, WeightedPodAffinityTerm,
    )
    from kubernetes_simulator_tpu.ops import tpu3 as V3

    cluster = make_cluster(150, seed=7)
    pods, _ = make_workload(200, seed=7, with_affinity=True)
    near = PodAffinitySpec(preferred=(WeightedPodAffinityTerm(
        7, PodAffinityTerm(LabelSelector.make({"app": pods[0].labels["app"]}),
                           "kubernetes.io/hostname")),))
    for pod in pods[::5]:
        pod.pod_affinity = near
    ec, ep = encode(cluster, pods)
    eng = JaxReplayEngine(ec, ep, FrameworkConfig(), wave_width=8,
                          chunk_waves=4)
    st = eng.static3
    assert len(st.pref_h_ids) and st.single_g[st.pref_h_ids].all()
    form = V3.host_commit_form(st)
    assert form["dot"] == len(st.pref_h_ids) and form["rows"] == 0
    assert form["elementwise"] == len(st.mc_h_ids) + len(st.anti_h_ids) > 0
    assert V3.host_commit_form(st, scenario_axis=True)["dot"] == form["dot"]
    cpu = greedy_replay(ec, ep, FrameworkConfig())
    v3 = eng.replay()
    np.testing.assert_array_equal(cpu.assignments, v3.assignments)
