"""Pod groups WIDER than the wave: the packers lay them out over consecutive
waves, the v3 step carries their transaction across waves and chunks
(``ops.tpu3.GangTxn``) and rolls a failed group back where it closes, the
hand-back returns it unplaced member for member, and the host twin
(``sim.greedy``) does the same. The anchor: a trace whose groups of 16 run
at ``wave_width`` 8 (two waves a group) gives the placements it gives at
``wave_width`` 16 (one wave a group, the wave-local mechanism)."""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubernetes_simulator_tpu import native
from kubernetes_simulator_tpu.framework.framework import FrameworkConfig
from kubernetes_simulator_tpu.models.core import Cluster, Node, Pod
from kubernetes_simulator_tpu.models.encode import PAD, encode
from kubernetes_simulator_tpu.ops import tpu3 as V3
from kubernetes_simulator_tpu.sim.greedy import greedy_replay
from kubernetes_simulator_tpu.sim.jax_runtime import JaxReplayEngine
from kubernetes_simulator_tpu.sim.synthetic import make_cluster, make_workload
from kubernetes_simulator_tpu.sim.waves import pack_waves, wide_gang_table
from kubernetes_simulator_tpu.sim.whatif import WhatIfEngine, uniform_scenarios

GPU = "nvidia.com/gpu"
GPU_JOBS = {"resource": GPU, "counts": {1: 0.5, 2: 0.3, 8: 0.2}, "wideFrom": 8,
            "smallJobFraction": 0.05, "wideJobFraction": 0.6}


def _case(sizes, pods=400, nodes=16, seed=0):
    cluster = make_cluster(nodes, seed=seed, extended_resources={GPU: (8, 0.45)})
    made, _ = make_workload(pods, seed=seed, gang_sizes=sizes,
                            job_extended_resource=GPU_JOBS)
    return encode(cluster, made)


@pytest.fixture(scope="module")
def sixteens():
    """400 pods on 16 nodes, 30% of the jobs groups of 16, 6 GPU nodes: some
    groups fit whole, some fail at a later member, some at their first."""
    return _case({1: 0.5, 4: 0.2, 16: 0.3})


def _whatif(case, width, chunk=7, scenarios=4, **kw):
    ec, ep = case
    eng = WhatIfEngine(ec, ep, uniform_scenarios(ec, scenarios, seed=0),
                       FrameworkConfig(), wave_width=width, chunk_waves=chunk,
                       collect_assignments=True, **kw)
    assert eng.engine == "v3"
    return eng, eng.run()


def _replay(case, width, chunk=7):
    ec, ep = case
    return JaxReplayEngine(ec, ep, FrameworkConfig(), wave_width=width,
                           chunk_waves=chunk).replay()


def _whole_or_not(ep, assign):
    g = ep.group_id
    members = np.bincount(g[g != PAD])
    bound = np.bincount(g[(g != PAD) & (assign >= 0)], minlength=len(members))
    return bool(((bound == 0) | (bound == members)).all())


@pytest.mark.parametrize("sizes", [(16,), (9, 23), (8, 16, 32, 64), (3, 64, 1, 65)])
def test_the_native_packer_is_the_python_packer_on_wide_groups(sizes, monkeypatch):
    """Groups wider than the wave fill consecutive waves from a wave's first
    slot, in both packers, waves and empty slots alike."""
    if not native.available():
        pytest.skip("native packers did not build")
    gid, g = [], 0
    for n in (1, 2) + sizes + (1, 5) + sizes:
        gid += [PAD] if n == 1 else [g] * n
        g += n > 1
    pods = [Pod(name=f"p{i}", requests={"cpu": 1.0}, arrival_time=float(i),
                pod_group=None if x == PAD else f"g{x}")
            for i, x in enumerate(gid)]
    _, ep = encode(Cluster(nodes=[Node(name="n", capacity={"cpu": 1.0})]), pods)
    fast = pack_waves(ep, 8).idx
    monkeypatch.setattr(native, "pack_waves_native", lambda *a: None)
    plain = pack_waves(ep, 8).idx
    np.testing.assert_array_equal(fast, plain)
    tab = wide_gang_table(ep, 8)
    assert (tab is not None) == any(n > 8 for n in sizes)
    if tab is None:
        return
    for row in plain:
        for slot, p in enumerate(row):
            if p >= 0 and tab[p, 0] >= 0:
                # member ``pos`` sits in slot ``pos % W``: a wide group starts
                # on a wave's first slot and its waves are consecutive
                assert tab[p, 0] % 8 == slot and tab[p, 1] > 8
    sizes_wide = sorted(n for n in sizes * 2 if n > 8)
    wide = tab[tab[:, 0] >= 0]
    got = sorted(int(wide[wide[:, 2] == o, 1][0]) for o in range(wide[:, 2].max() + 1))
    assert got == sizes_wide


@pytest.mark.parametrize("width", [8, 16])
def test_the_host_twin_is_the_device_replay(sixteens, width):
    ec, ep = sixteens
    dev = _replay(sixteens, width)
    host = greedy_replay(ec, ep, FrameworkConfig(), wave_width=width)
    np.testing.assert_array_equal(dev.assignments, host.assignments)
    assert dev.placed == host.placed and _whole_or_not(ep, dev.assignments)


def test_groups_of_16_at_width_8_place_as_at_width_16(sixteens):
    """Two waves a group through the carried transaction against one wave a
    group through the wave-local mask: the same placements in every scenario
    and in the single replay, some groups rolled back, none partly bound."""
    ec, ep = sixteens
    eng8, two = _whatif(sixteens, 8)
    eng16, one = _whatif(sixteens, 16)
    assert eng8.static3.has_wide_gangs and not eng16.static3.has_wide_gangs
    np.testing.assert_array_equal(two.assignments, one.assignments)
    np.testing.assert_array_equal(two.placed, one.placed)
    np.testing.assert_array_equal(two.placed, (two.assignments >= 0).sum(axis=1))
    single = _replay(sixteens, 8)
    np.testing.assert_array_equal(two.assignments[0], single.assignments)
    np.testing.assert_array_equal(single.assignments,
                                  _replay(sixteens, 16).assignments)
    gangs = two.fleet_telemetry.summary()["gangs"]
    assert gangs["wide_groups"] == 22 and gangs["max_group"] == 16
    assert gangs["max_waves_spanned"] == 2
    assert gangs["rollback_form"] == V3.rollback_form(eng8.static3) == "txn_plane"
    assert gangs["wide_rolled_back"] >= 4 * 5  # in every scenario
    assert gangs["pods_rolled_back"] >= 4 * 20  # rollbacks that undo binds
    assert all(_whole_or_not(ep, a) for a in two.assignments)
    assert "gangs" not in one.fleet_telemetry.summary()
    mine = single.telemetry.summary()["gangs"]
    assert mine["wide_rolled_back"] == 7 and mine["pods_rolled_back"] == 59
    rolled = np.asarray(
        [not (single.assignments[ep.group_id == g] >= 0).any()
         for g in np.unique(ep.group_id[ep.group_id != PAD])
         if (ep.group_id == g).sum() > 8])
    assert rolled.sum() == 7


def test_groups_of_64_at_width_8_place_as_the_host_twin_at_width_64():
    """Eight waves a group. (The wave-local DEVICE program at width 64 unrolls
    2,016 in-wave terms and compiles for minutes on the CPU: the host twin
    stands in for it, which the device equals at widths 8 and 16 above.)"""
    case = _case({1: 0.6, 8: 0.1, 64: 0.3}, pods=700, nodes=24, seed=3)
    ec, ep = case
    _, res = _whatif(case, 8, chunk=11, scenarios=2)
    host = greedy_replay(ec, ep, FrameworkConfig(), wave_width=64)
    np.testing.assert_array_equal(res.assignments[0], host.assignments)
    gangs = res.fleet_telemetry.summary()["gangs"]
    assert gangs["max_group"] == 64 and gangs["max_waves_spanned"] == 8
    assert gangs["wide_rolled_back"] > 0 and gangs["pods_rolled_back"] > 0
    assert all(_whole_or_not(ep, a) for a in res.assignments)


@pytest.mark.parametrize("chunk", [1, 3, 1000])
def test_a_group_may_cross_a_chunk_edge(sixteens, chunk):
    """The transaction is in the state the chunks hand on: chunks of one wave
    (every two-wave group crosses an edge), of three, and one chunk."""
    ec, ep = sixteens
    idx = pack_waves(ep, 8).idx
    tab = wide_gang_table(ep, 8)
    first = [w for w, row in enumerate(idx) if row[0] >= 0 and tab[row[0], 0] == 0]
    if chunk == 3:
        assert any(w % 3 == 2 for w in first)  # a group split over two chunks
    _, res = _whatif(sixteens, 8, chunk=chunk)
    np.testing.assert_array_equal(res.assignments, _whatif(sixteens, 8)[1].assignments)
    np.testing.assert_array_equal(_replay(sixteens, 8, chunk).assignments,
                                  res.assignments[0])


def test_what_a_rolled_back_group_took_is_back_before_the_next_pod():
    """Two nodes of 8 GPUs. One pod takes a GPU; a group of 16 x 1 GPU binds
    15 members and fails at its last, in its second wave; the pod right after
    it asks for 8 GPUs and fits only if the group's binds are gone."""
    nodes = [Node(name=f"n{i}", capacity={"cpu": 64.0, "memory": 2.0**38,
                                          "pods": 110, GPU: 8.0})
             for i in range(2)]
    ask = lambda n: {"cpu": 1.0, "memory": 2.0**30, GPU: float(n)}
    pods = [Pod(name="first", requests=ask(1), arrival_time=0.0)]
    pods += [Pod(name=f"m{i}", requests=ask(1), arrival_time=1.0 + i,
                 pod_group="job") for i in range(16)]
    pods += [Pod(name="next", requests=ask(8), arrival_time=20.0),
             Pod(name="last", requests=ask(8), arrival_time=21.0)]
    case = encode(Cluster(nodes=nodes), pods)
    res = _replay(case, 8)
    job = res.assignments[1:17]
    assert (job == PAD).all()
    assert res.assignments[17] >= 0 and res.assignments[17] != res.assignments[0]
    assert res.assignments[18] == PAD  # the other node still holds `first`
    assert res.telemetry.summary()["gangs"] == {
        "wide_groups": 1, "max_group": 16, "max_waves_spanned": 2,
        "rollback_form": "txn_plane", "wide_rolled_back": 1,
        "pods_rolled_back": 15}
    used = res.state.used[:, case[0].vocab._r[GPU]]
    assert sorted(used.tolist()) == [1.0, 8.0]
    _, batch = _whatif(case, 8, chunk=2, scenarios=2)
    np.testing.assert_array_equal(batch.assignments[0], res.assignments)
    host = greedy_replay(*case, FrameworkConfig(), wave_width=8)
    np.testing.assert_array_equal(host.assignments, res.assignments)


def test_the_count_only_batch_counts_a_rolled_back_group_out(sixteens):
    ec, ep = sixteens
    eng = WhatIfEngine(ec, ep, uniform_scenarios(ec, 4, seed=0),
                       FrameworkConfig(), wave_width=8, chunk_waves=7)
    _, asked = _whatif(sixteens, 8)
    np.testing.assert_array_equal(eng.run().placed, asked.placed)


# sha256 of `Lowered.as_text()` of the chunk programs of a trace whose pod
# groups all fit a wave (groups of 4, extended resource; jax 0.9.0), taken on
# the parent of PR 37 (faae657): without a group wider than the wave the
# state carries no transaction, the rows no ``txn`` column, and the program
# is the parent's to the byte.
_PARENT_PROGRAMS = {
    "replay": "52383c8af26ae04383d653d35173cdc93c1c30ec35e3e95660dcc0571536b100",
    "whatif": "12f804bd26a1dafc8dd2e364eb5607fb07f7f452d75c87b5cb852c7e606dd017",
}


@pytest.mark.parametrize("engine", sorted(_PARENT_PROGRAMS))
def test_without_a_wide_group_the_chunk_program_is_the_parents(engine):
    cluster = make_cluster(16, seed=5, extended_resources={GPU: (8, 0.5)})
    pods, _ = make_workload(96, seed=5, gang_fraction=0.2, gang_size=4,
                            extended_resource=(GPU, 8, 0.3))
    ec, ep = encode(cluster, pods)
    if engine == "replay":
        eng = JaxReplayEngine(ec, ep, FrameworkConfig(),
                              wave_width=8, chunk_waves=4)
        fn, state = eng.chunk_fn, eng._init_dev_state()
        args = (eng.dc, state, eng._slot_src, eng._extra_src)
    else:
        eng = WhatIfEngine(ec, ep, uniform_scenarios(ec, 4, seed=0),
                           FrameworkConfig(), wave_width=8, chunk_waves=4,
                           collect_assignments=True)
        fn, state = eng._chunk_fn, eng._init_states()
        args = (eng.sset.dc, state, *eng._slot_srcs)
    assert eng.static3.has_gangs and not eng.static3.has_wide_gangs
    assert state.txn is None and eng._slot_srcs[1].txn is None if engine == "whatif" \
        else state.txn is None and eng._extra_src.txn is None
    assert len(jax.tree.leaves(state)) == 10
    text = fn.lower(*args, jnp.asarray(eng.waves.idx[:4])).as_text()
    assert "gang_txn" not in text and "gang_rollback" not in text
    assert hashlib.sha256(text.encode()).hexdigest() == _PARENT_PROGRAMS[engine]


def test_a_wide_group_puts_both_scopes_into_the_chunk_program(sixteens):
    ec, ep = sixteens
    eng = JaxReplayEngine(ec, ep, FrameworkConfig(), wave_width=8, chunk_waves=4)
    state = eng._init_dev_state()
    assert state.txn.plane.shape == (4, 16) and state.txn.log.shape == (22,)
    text = eng.chunk_fn.lower(eng.dc, state, eng._slot_src, eng._extra_src,
                              jnp.asarray(eng.waves.idx[:4])).as_text(debug_info=True)
    assert "ksim.gang_txn" in text and "ksim.gang_rollback" in text


def test_what_an_open_transaction_cannot_be_combined_with_is_refused(sixteens):
    ec, ep = sixteens
    scen = uniform_scenarios(ec, 2, seed=0)
    timed = _case({1: 0.5, 16: 0.5}, pods=64)
    timed[1].duration[:] = 5.0
    with pytest.raises(ValueError, match="wider than the wave.*completions"):
        WhatIfEngine(*timed, scen, FrameworkConfig(), wave_width=8)
    with pytest.raises(ValueError, match="wider than the wave.*completions"):
        JaxReplayEngine(*timed, FrameworkConfig(), wave_width=8).replay()
    with pytest.raises(ValueError, match="wider than the wave.*retry"):
        JaxReplayEngine(ec, ep, FrameworkConfig(), wave_width=8, retry_buffer=8)
    with pytest.raises(ValueError, match="wider than the wave"):
        JaxReplayEngine(ec, ep, FrameworkConfig(), wave_width=8, preemption=True)
    with pytest.raises(ValueError, match="wider than the wave"):
        greedy_replay(ec, ep, FrameworkConfig(), wave_width=8, preemption=True)
    spread, _ = make_workload(64, seed=1, with_spread=True)
    for i, pod in enumerate(spread[:32]):
        pod.pod_group = "wide"
    with pytest.raises(ValueError, match="wider than the wave.*count planes"):
        JaxReplayEngine(*encode(make_cluster(8, seed=1), spread),
                        FrameworkConfig(), wave_width=8)
    # a step built from a static that was not told the wave width would judge
    # a wide group wave by wave: its builder refuses (every direct caller of
    # V3Static.build(ec, ep, spec) that packs at a narrower wave lands here)
    from kubernetes_simulator_tpu.ops import tpu as T
    from kubernetes_simulator_tpu.sim.jax_runtime import StepSpec

    spec = StepSpec.from_config(ec, FrameworkConfig(), ep)
    for width in (None, 16):
        st = V3.V3Static.build(ec, ep, spec, wave_width=width)
        assert st.max_gang == 16 and not st.has_wide_gangs
        dc = T.DevCluster.from_encoded(ec)
        with pytest.raises(ValueError, match="wider than the wave.*without the wave width"):
            V3.make_wave_step3(dc, T.Derived.build(dc), V3.Shared3.build(ec, st),
                               st, 8, spec)
    # the paged mode's guard stays: a gang must fit in one page
    with pytest.raises(ValueError, match="must fit in one page"):
        pack_waves(ep, 8, page_pods=8)
    # at a width that holds the widest group none of this is refused
    JaxReplayEngine(ec, ep, FrameworkConfig(), wave_width=16, retry_buffer=16)
