"""The what-if device-release path hands back every task's node
(``collect_assignments=True`` picks no path any more): held, task for task
and scenario by scenario, to the CPU plugin path's replay of that
scenario's own cluster and to the host pending-fold path; pre-bound tasks;
a resident engine's later ``run()`` calls compile nothing; the phases cover
the call; the programs a profiled run registers.

"The CPU engine" here is ``greedy_replay``: the CPU framework
(``framework/``, the plugins ``sim/runtime.py`` drives) run over the device
engines' waves and chunk-granular releases. The event engine of
``sim/runtime.py`` releases at event time and requeues, so it answers
another question (tests/test_divergence_pin.py)."""

import dataclasses
import time

import jax
import numpy as np
import pytest

from kubernetes_simulator_tpu.framework.framework import FrameworkConfig
from kubernetes_simulator_tpu.models.encode import PAD, encode
from kubernetes_simulator_tpu.sim.greedy import greedy_replay
from kubernetes_simulator_tpu.sim.synthetic import make_cluster, make_workload
from kubernetes_simulator_tpu.sim.telemetry import PHASE_NAMES
from kubernetes_simulator_tpu.sim.whatif import (
    Perturbation,
    Scenario,
    ScenarioSet,
    WhatIfEngine,
)
from kubernetes_simulator_tpu.utils import profiling

W, C = 4, 4


def scenarios(n_nodes: int):
    """Six scenarios, every perturbation kind alone and all three at once;
    scenario 0 is the base."""
    down = Perturbation("node_down", nodes=np.array([1, 5]))
    half = Perturbation("scale_capacity", nodes=np.arange(0, n_nodes, 2),
                        resource="cpu", factor=0.5)
    more = Perturbation("scale_capacity", nodes=np.arange(3), resource="cpu",
                        factor=1.5)
    taint = Perturbation("add_taint", nodes=np.arange(2, 6),
                         key="whatif/injected", value="true",
                         effect="NoSchedule")
    return [Scenario(), Scenario([down]), Scenario([half]), Scenario([taint]),
            Scenario([down, half, taint]), Scenario([more, taint])]


@pytest.fixture(scope="module")
def case():
    """A contended trace: gangs, a taint some tasks tolerate, a zone
    spread, and durations that keep the cluster full, so releases decide what
    fits (4 to 227 of the 400 tasks go unplaced, by scenario)."""
    cluster = make_cluster(8, seed=3, taint_fraction=0.2)
    pods, _ = make_workload(
        400, seed=3, arrival_rate=12.0, duration_mean=20.0, with_spread=True,
        with_tolerations=True, gang_fraction=0.1, gang_size=3,
    )
    ec, ep = encode(cluster, pods)
    return ec, ep, FrameworkConfig(), scenarios(ec.num_nodes)


def device_engine(ec, ep, cfg, scen, **kw):
    eng = WhatIfEngine(ec, ep, scen, cfg, wave_width=W, chunk_waves=C,
                       completions=True, collect_assignments=True, **kw)
    assert eng._completions_dev and not eng._need_choices
    return eng


def backend_compiles():
    """A list that every backend compile from now on appends its event to."""
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, _, **kw: compiles.append(name)
        if name == "/jax/core/compile/backend_compile_duration" else None)
    return compiles


@pytest.fixture(scope="module")
def answered(case):
    ec, ep, cfg, scen = case
    eng = device_engine(ec, ep, cfg, scen)
    return eng, eng.run()


@pytest.mark.parametrize("s", range(6))
def test_each_scenario_is_the_cpu_replay_of_its_own_cluster(case, answered, s):
    ec, ep, cfg, scen = case
    eng, res = answered
    own = ScenarioSet(ec, scen, keep_host_stacks=True).host_clusters(ec)[s]
    ref = greedy_replay(own, ep, cfg, wave_width=W,
                        completions_chunk_waves=eng.chunk_waves)
    np.testing.assert_array_equal(res.assignments[s], ref.assignments)
    assert int(res.placed[s]) == ref.placed
    assert int(res.unschedulable[s]) == ref.unschedulable
    # the device-side count and the placements handed back agree
    assert int((res.assignments[s] >= 0).sum()) == int(res.placed[s])


def test_perturbations_and_releases_decide_the_answers(case, answered):
    """Not vacuous: some tasks go unplaced, every perturbed scenario parts
    from the base, and without completions the base answers otherwise."""
    ec, ep, cfg, scen = case
    _, res = answered
    assert (res.unschedulable > 0).any()
    for s in range(1, len(scen)):
        assert (res.assignments[s] != res.assignments[0]).any(), s
    off = WhatIfEngine(ec, ep, scen, cfg, wave_width=W, chunk_waves=C,
                       completions=False, collect_assignments=True).run()
    assert (off.assignments[0] != res.assignments[0]).any()


def test_device_release_path_equals_host_pending_fold_path(case, answered,
                                                           fork_at_start):
    ec, ep, cfg, scen = case
    _, res = answered
    host = WhatIfEngine(
        ec, ep, scen, cfg, wave_width=W, chunk_waves=C, completions=True,
        collect_assignments=True,
        fork_checkpoint=fork_at_start(ec, ep))
    assert host.completions_on and not host._completions_dev
    r2 = host.run()
    np.testing.assert_array_equal(res.assignments, r2.assignments)
    np.testing.assert_array_equal(res.placed, r2.placed)
    np.testing.assert_array_equal(res.utilization_cpu, r2.utilization_cpu)


def test_counts_only_batch_fetches_no_placements(case, answered):
    """Without the flag the path answers as before: the same counts, no
    placements, and no hand-back phase or bytes."""
    ec, ep, cfg, scen = case
    _, res = answered
    eng = WhatIfEngine(ec, ep, scen, cfg, wave_width=W, chunk_waves=C,
                       completions=True)
    assert eng._completions_dev
    r = eng.run()
    assert r.assignments is None
    np.testing.assert_array_equal(r.placed, res.placed)
    np.testing.assert_array_equal(r.utilization_cpu, res.utilization_cpu)
    tel = r.fleet_telemetry
    assert "p0/handback" not in tel.phases
    assert tel.summary()["handback_bytes"] == 0
    got = res.fleet_telemetry.summary()
    assert got["handback_bytes"] == 4 * len(scen) * ep.num_pods
    assert got["scenarios"] == len(scen) and got["chunk_waves"] == C
    assert got["release_buckets"] == [256]


def test_prebound_tasks_come_back_from_the_tail_region(case):
    ec, ep, cfg, scen = case
    bound = np.full(ep.num_pods, PAD, np.int32)
    bound[[0, 7, 30]] = [2, 2, 7]
    ep2 = dataclasses.replace(ep, bound_node=bound)
    eng = device_engine(ec, ep2, cfg, scen[:3])
    res = eng.run()
    np.testing.assert_array_equal(res.assignments[:, [0, 7, 30]],
                                  np.tile([2, 2, 7], (3, 1)))
    ref = greedy_replay(ec, ep2, cfg, wave_width=W,
                        completions_chunk_waves=eng.chunk_waves)
    np.testing.assert_array_equal(res.assignments[0], ref.assignments)
    # pre-bound tasks are in the placements, never in the count
    assert int(res.placed[0]) == ref.placed
    assert int((res.assignments[0] >= 0).sum()) == ref.placed + 3


def test_later_runs_compile_nothing_and_answer_the_same(case, answered):
    ec, ep, cfg, scen = case
    eng, first = answered
    compiles = backend_compiles()
    for _ in range(2):
        again = eng.run()
        np.testing.assert_array_equal(again.assignments, first.assignments)
        np.testing.assert_array_equal(again.placed, first.placed)
        np.testing.assert_array_equal(again.utilization_cpu,
                                      first.utilization_cpu)
    assert compiles == []


def test_later_runs_compile_nothing_under_a_mesh(case):
    from kubernetes_simulator_tpu.parallel.mesh import make_mesh

    ec, ep, cfg, scen = case
    eng = device_engine(ec, ep, cfg, scen[:4], mesh=make_mesh(2))
    first = eng.run()
    compiles = backend_compiles()
    again = eng.run()
    assert compiles == []
    np.testing.assert_array_equal(again.assignments, first.assignments)
    plain = device_engine(ec, ep, cfg, scen[:4]).run()
    np.testing.assert_array_equal(first.assignments, plain.assignments)


@pytest.mark.parametrize("s", [0, 2, 4])
def test_device_retry_buffer_hands_back_placements_and_boundaries(case, s):
    """Until PR 41 the device ``retry_buffer`` refused ``collect_assignments``
    (re-tried placements were not kept per task). Now both arrays come
    back, and each scenario's are the CPU replay's of its own cluster:
    gangs (never queued), a tolerated taint, the perturbations."""
    ec, ep, cfg, scen = case
    eng = device_engine(ec, ep, cfg, scen, retry_buffer=16)
    res = eng.run()
    own = ScenarioSet(ec, scen, keep_host_stacks=True).host_clusters(ec)[s]
    ref = greedy_replay(own, ep, cfg, wave_width=W,
                        completions_chunk_waves=eng.chunk_waves,
                        retry_buffer=16)
    np.testing.assert_array_equal(res.assignments[s], ref.assignments)
    np.testing.assert_array_equal(res.bind_boundary[s], ref.bind_boundary)
    assert int(res.placed[s]) == ref.placed
    assert int(res.retry_dropped[s]) == ref.retry_dropped
    assert (res.bind_boundary[s] >= 0).any()
    gang = ep.group_id >= 0
    assert set(np.unique(res.bind_boundary[s][gang])) <= {-1, -4}


@pytest.fixture(scope="module")
def retrying(case):
    """The case's six scenarios with a pending queue of 16, and its first
    batch."""
    ec, ep, cfg, scen = case
    eng = device_engine(ec, ep, cfg, scen, retry_buffer=16)
    return eng, eng.run()


def host_merge(eng, vassign, t_id, t_node, ids):
    """The hand-back of the device retry path as the host wrote it until
    PR 43, kept as the oracle of the program that took its place: the
    arrival binds in task order and each task's default code, then the
    record's binds by fancy index, then the tasks still queued."""
    node = np.take(vassign, eng._dev_rel_stage["pos"], axis=1).astype(np.int32)
    gang = eng.pods.group_id >= 0
    code = np.where(node >= 0, -1, np.where(gang[None, :], -4, -3)).astype(np.int32)
    s, b, j = np.nonzero(t_id >= 0)
    tasks = t_id[s, b, j]
    node[s, tasks] = t_node[s, b, j]
    code[s, tasks] = b
    s, j = np.nonzero(ids >= 0)
    code[s, ids[s, j]] = -2
    return node, code


# name -> (S, B, RB) -> ({scenario: [(boundary, slot, nth free task)]},
#                        {scenario: how many free tasks are queued})
MERGE_RECORDS = {
    "empty_record": lambda S, B, RB: ({}, {}),
    "a_row_bound_full": lambda S, B, RB: (
        {1: [(2, j, j) for j in range(RB)]}, {}),
    "bound_by_the_last_boundary": lambda S, B, RB: (
        {s: [(B - 1, 3, 0)] for s in range(S)}, {}),
    "still_queued_at_the_end": lambda S, B, RB: (
        {}, {s: 1 + s for s in range(S)}),
    "dropped_and_gang_members_untouched": lambda S, B, RB: (
        {s: [(0, 0, 0), (1, 5, 1)] for s in range(S)}, {s: 2 for s in range(S)}),
    "pad_rows_between_valid_ones": lambda S, B, RB: (
        {0: [(b, j, nth) for nth, (b, j) in enumerate(
            (b, j) for b in range(0, B, 2) for j in range(0, RB, 2))]}, {}),
    "queues_differ_by_scenario": lambda S, B, RB: (
        {0: [(0, 1, 0), (3, 0, 1), (3, 1, 2)], 3: [(1, RB - 1, 0)]},
        {0: RB, 3: 4}),
}


@pytest.mark.parametrize("record", sorted(MERGE_RECORDS))
def test_retry_handback_program_is_the_host_merge(retrying, record):
    """The hand-back program of the device retry path against the host
    merge it replaced (PR 43), over made-up wave-order buffers and records:
    both arrays equal entry for entry, the count of re-tried binds is the
    program's own, and what comes back is read-only."""
    eng, _ = retrying
    rng = np.random.default_rng(5)
    S, N, RB = eng.S, eng.ec.num_nodes, eng.retry_buffer
    rq = eng._retry_queue(0)  # the engine's own, empty
    B = rq.t_id.shape[1]
    pos = eng._dev_rel_stage["pos"]
    va = rng.integers(0, N, size=(S, int(pos.max()) + 1)).astype(np.int32)
    va[rng.random(va.shape) < 0.5] = PAD
    va[:, -1] = PAD  # the slot of a task in no wave
    gang = eng.pods.group_id >= 0
    unplaced = np.take(va, pos, axis=1) < 0
    # as in a run: only a task with no node from its arrival wave, and no
    # gang member, is ever queued
    free = [rng.permutation(np.nonzero(unplaced[s] & ~gang)[0]) for s in range(S)]
    binds, queued = MERGE_RECORDS[record](S, B, RB)
    t_id = np.full((S, B, RB), PAD, np.int32)
    t_node = np.full((S, B, RB), PAD, np.int32)
    ids = np.full((S, RB), PAD, np.int32)
    for s, rows in binds.items():
        for b, j, nth in rows:
            t_id[s, b, j] = free[s][nth]
            t_node[s, b, j] = rng.integers(0, N)
    for s, k in queued.items():
        ids[s, :k] = free[s][-k:]  # the record takes from the front
    want_node, want_code = host_merge(eng, va, t_id, t_node, ids)
    want_merged = (want_code >= 0).sum(axis=1)
    assert want_merged.sum() == sum(len(r) for r in binds.values())
    node, code, merged, copied = eng._handback_retry(
        profiling.make_span(), jax.numpy.asarray(va),
        rq._replace(t_id=jax.numpy.asarray(t_id),
                    t_node=jax.numpy.asarray(t_node),
                    ids=jax.numpy.asarray(ids)),
        want_merged,
    )
    np.testing.assert_array_equal(node, want_node)
    np.testing.assert_array_equal(code, want_code)
    np.testing.assert_array_equal(merged, want_merged)
    assert node.dtype == code.dtype == np.int32
    assert copied == node.nbytes + code.nbytes == 2 * S * eng.pods.num_pods * 4
    assert not node.flags.writeable and not code.flags.writeable
    # the codes the merge may not touch are there to be touched
    assert (code[:, gang] == -4).any() and (code == -3).any() and (code == -1).any()
    assert (code == -2).sum() == sum(queued.values())


def test_retry_handback_is_each_runs_own_and_compiles_once(case, retrying):
    """Two later batches of one engine hand back equal arrays that share no
    memory with an earlier batch's (a caller keeps the first batch's and
    compares), read-only as on the other paths, and compile nothing; the
    bytes handed back are the two arrays' and the re-tried binds merged on
    the device are the ones the passes made."""
    ec, ep, cfg, scen = case
    eng, first = retrying
    compiles = backend_compiles()
    kept = [first]
    for _ in range(2):
        again = eng.run()
        for a, b in ((again.assignments, first.assignments),
                     (again.bind_boundary, first.bind_boundary)):
            np.testing.assert_array_equal(a, b)
            assert not any(np.shares_memory(a, getattr(k, n))
                           for k in kept for n in ("assignments", "bind_boundary"))
            assert not a.flags.writeable
        kept.append(again)
    assert compiles == []
    got = again.fleet_telemetry.summary()
    assert got["handback_bytes"] == 2 * len(scen) * ep.num_pods * 4
    assert got["handback_bytes"] == (again.assignments.nbytes
                                     + again.bind_boundary.nbytes)
    retry = got["retry"]
    merged = (again.bind_boundary >= 0).sum(axis=1)
    assert retry["handback_merged"] == retry["retry_placed"] == {
        "mean": float(merged.mean()), "max": int(merged.max())}
    assert retry["scenario0"]["handback_merged"] == int(merged[0])


def test_retry_handback_under_a_mesh_is_the_unmeshed(case, retrying):
    """The merge indexes the whole batch at once; with the scenario axis
    sharded over two devices it hands back what one device does."""
    from kubernetes_simulator_tpu.parallel.mesh import make_mesh

    ec, ep, cfg, scen = case
    _, plain = retrying
    res = device_engine(ec, ep, cfg, scen[:4], retry_buffer=16,
                        mesh=make_mesh(2)).run()
    np.testing.assert_array_equal(res.assignments, plain.assignments[:4])
    np.testing.assert_array_equal(res.bind_boundary, plain.bind_boundary[:4])
    retry = res.fleet_telemetry.summary()["retry"]
    assert retry["handback_merged"] == retry["retry_placed"]


def test_retry_passes_under_a_mesh_end_with_their_own_devices_fullest(case, retrying):
    """With the scenario axis over two devices each device's passes end with
    ITS fullest scenario's last queued wave (the ``pmax`` is over the vmapped
    axis of one device's slice: no collective crosses devices,
    tests/test_mesh_hlo.py): scenarios 0 and 1 queue little, scenario 3 fills
    its buffer on the other device, and each pair reports its own device's
    ``pass_waves``, worked out from the anchors' answers. The hand-back is
    the unmeshed run's."""
    from test_retry_device import pass_waves_of, queue_depths

    from kubernetes_simulator_tpu.parallel.mesh import make_mesh

    ec, ep, cfg, scen = case
    _, plain = retrying
    res = device_engine(ec, ep, cfg, scen[:4], retry_buffer=16,
                        mesh=make_mesh(2)).run()
    np.testing.assert_array_equal(res.assignments, plain.assignments[:4])
    np.testing.assert_array_equal(res.bind_boundary, plain.bind_boundary[:4])
    own = ScenarioSet(ec, scen, keep_host_stacks=True).host_clusters(ec)
    depths = []
    for s in range(6):
        ref = greedy_replay(own[s], ep, cfg, wave_width=W,
                            completions_chunk_waves=C, retry_buffer=16)
        np.testing.assert_array_equal(plain.bind_boundary[s], ref.bind_boundary)
        depths.append(queue_depths(ep, ref.bind_boundary, W, C))
    depths = np.array(depths)
    here, there = pass_waves_of(depths[:2], W), pass_waves_of(depths[2:4], W)
    assert depths[:2].max() < 16 == depths[3].max() and 0 < here < there
    retry = res.fleet_telemetry.summary()["retry"]
    assert retry["scenario0"]["pass_waves"] == here
    assert retry["pass_waves"] == {"mean": (here + there) / 2, "max": there}
    # on one device all six end together, with the deepest of them
    unmeshed = plain.fleet_telemetry.summary()["retry"]
    whole = pass_waves_of(depths, W)
    assert unmeshed["pass_waves"] == {"mean": float(whole), "max": whole}
    assert there < whole < unmeshed["passes"] * 16 // W


def test_phases_cover_a_whatif_run(answered):
    """As tests/test_stage_scopes.py holds the replay: the phases are
    sequential on one thread and what run() spends outside them is small.
    Best of three."""
    eng, _ = answered
    shares = []
    for _ in range(3):
        t = time.perf_counter()
        res = eng.run()
        wall = time.perf_counter() - t
        phases = {k.split("/", 1)[1]: v
                  for k, v in res.fleet_telemetry.phases.items()}
        assert set(phases) <= set(PHASE_NAMES)
        assert {"stage", "dispatch", "boundary_fold", "device_wait", "gather",
                "handback"} <= set(phases)
        shares.append(sum(phases.values()) / wall)
    assert max(shares) >= 0.95, shares


def test_a_profiled_run_registers_its_programs(case, tmp_path, monkeypatch):
    """The chunk program under the name the benchmark's reader looks for,
    the release program under one pinned name per bucket; both lower to
    modules whose stages ``stage_tables`` can name."""
    ec, ep, cfg, scen = case
    profiling._PROGRAMS.clear()
    monkeypatch.setenv("KSIM_PROFILE_DIR", str(tmp_path))
    device_engine(ec, ep, cfg, scen[:2]).run()
    monkeypatch.delenv("KSIM_PROFILE_DIR")
    assert set(profiling._PROGRAMS) == {"jit_per_scenario_rel",
                                        "jit_whatif_release_k256"}
    tables = profiling.stage_tables()
    profiling._PROGRAMS.clear()
    assert "ksim.release" in set(tables["jit_whatif_release_k256"].values())
    ran = {p.split("/")[0] for p in tables["jit_per_scenario_rel"].values()}
    assert {"ksim.filter_score", "ksim.select", "ksim.commit",
            "ksim.release"} <= ran


def test_asking_for_placements_leaves_the_chunk_program_alone(case):
    """Handing the placements back is the end of run()'s business: with and
    without the flag the engine builds the same chunk program, to the
    letter of its lowered module."""
    import jax.numpy as jnp

    ec, ep, cfg, scen = case
    texts = []
    for collect in (False, True):
        eng = WhatIfEngine(ec, ep, scen, cfg, wave_width=W, chunk_waves=C,
                           completions=True, collect_assignments=collect)
        idx = eng.waves.idx
        idx = np.concatenate(
            [idx, np.full((-idx.shape[0] % C, W), PAD, np.int32)])
        stg = eng._stage_dev_rel(idx, C)
        va = jnp.broadcast_to(stg["va"][None], (eng.S,) + stg["va"].shape)
        texts.append(eng._chunk_fn.lower(
            eng.sset.dc, eng._init_states(), *eng._slot_srcs,
            jnp.asarray(idx[:C]), stg["b_c"][0], va).as_text())
    assert texts[0] == texts[1]
