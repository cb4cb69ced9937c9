"""``retry_groups``: a standing queue of whole JOBS (pod groups) on the device
retry path, held to its host twin ``greedy_replay(retry_groups=True)`` answer
for answer over scenarios whose queues differ."""

import numpy as np
import pytest

from kubernetes_simulator_tpu.framework.framework import FrameworkConfig
from kubernetes_simulator_tpu.models.encode import PAD, encode
from kubernetes_simulator_tpu.sim.greedy import greedy_replay
from kubernetes_simulator_tpu.sim.synthetic import make_cluster, make_job_workload
from kubernetes_simulator_tpu.sim.waves import (
    GROUP_COUNTERS, WIDE_GANG_UNSUPPORTED, job_table, pack_waves,
)
from kubernetes_simulator_tpu.sim.whatif import (
    Perturbation, Scenario, ScenarioSet, WhatIfEngine,
)

GPU = "nvidia.com/gpu"
SIZES = {1: 0.5, 2: 0.1, 4: 0.1, 8: 0.1, 16: 0.1, 32: 0.1}


def job_trace(nodes=24, gpu_nodes=10, pods=600, seed=0, rate=4.0,
              duration=(30.0, 1.0), sizes=SIZES, wide_fraction=0.8):
    """A GPU training cluster in small: jobs of 1 to 32 workers, every member
    with its job's arrival time, priority and (log-normal) duration."""
    cluster = make_cluster(nodes, seed, extended_resources={GPU: (8, gpu_nodes)})
    pods, _ = make_job_workload(
        pods, seed, arrival_rate=rate, gang_sizes=sizes,
        job_extended_resource={
            "resource": GPU, "counts": {1: 0.7, 2: 0.3}, "wideFrom": 8,
            "smallJobFraction": 0.1, "wideJobFraction": wide_fraction})
    rng = np.random.default_rng(seed + 7)
    first = {}
    for p in pods:
        key = p.pod_group or p.name
        if key not in first:
            first[key] = (p.arrival_time, p.priority,
                          float(rng.lognormal(np.log(duration[0]), duration[1])))
        p.arrival_time, p.priority, p.duration = first[key]
    return encode(cluster, pods)


def scenarios(n_nodes):
    down = lambda *nodes: Scenario([Perturbation("node_down", nodes=list(nodes))])
    return [Scenario(), down(0, 3), down(1, 2, 5, 8), Scenario([Perturbation(
        "scale_capacity", nodes=np.arange(n_nodes), resource="cpu", factor=0.5)])]


def run_both(ec, ep, scen, W=8, C=8, RB=128):
    eng = WhatIfEngine(
        ec, ep, scen, FrameworkConfig(), wave_width=W, chunk_waves=C,
        completions=True, retry_buffer=RB, retry_groups=True,
        collect_assignments=True, granularity_guard=False)
    assert eng.release_path == "device"
    res = eng.run()
    own = ScenarioSet(ec, scen, keep_host_stacks=True).host_clusters(ec)
    twins = [greedy_replay(c, ep, FrameworkConfig(), wave_width=W,
                           completions_chunk_waves=C, retry_buffer=RB,
                           retry_groups=True) for c in own]
    return eng, res, twins


@pytest.fixture(scope="module")
def contended():
    ec, ep = job_trace()
    scen = scenarios(ec.num_nodes)
    return (ec, ep, scen) + run_both(ec, ep, scen)


def jobs_of(ep):
    """[(members in arrival order)] of every job; a pod in no group alone."""
    out = {}
    for p in np.argsort(ep.arrival, kind="stable"):
        g = int(ep.group_id[p])
        out.setdefault(g if g != PAD else -1 - int(p), []).append(int(p))
    return list(out.values())


def test_the_device_gives_the_twins_answers_scenario_for_scenario(contended):
    ec, ep, scen, eng, res, twins = contended
    for s, twin in enumerate(twins):
        np.testing.assert_array_equal(res.assignments[s], twin.assignments)
        np.testing.assert_array_equal(res.bind_boundary[s], twin.bind_boundary)
        assert res.placed[s] == twin.placed
        assert res.retry_dropped[s] == twin.retry_dropped
        for k in GROUP_COUNTERS:
            assert res.group_counts[k][s] == twin.group_counts[k], (s, k)
    # the queues differ by scenario
    assert len({tuple(b) for b in res.bind_boundary}) > 1


def test_a_job_is_bound_by_a_pass_after_two_rollbacks(contended):
    ec, ep, scen, eng, res, twins = contended
    tab = job_table(ep, pack_waves(ep, 8).idx, 8)
    late = (res.bind_boundary >= 0) & (
        res.bind_boundary - tab[None, :, 2] >= 3) & (tab[None, :, 0] > 8)
    assert late.any()  # a wide job that sat through two passes or more
    counts = res.group_counts
    assert (counts["pass_rollbacks_after_bind"] > 0).any()
    assert (counts["pass_attempts"]
            == counts["pass_rollbacks"] + counts["jobs_bound_pass"]).all()


def test_no_job_is_split_between_placed_queued_and_dropped(contended):
    ec, ep, scen, eng, res, twins = contended
    for members in jobs_of(ep):
        for s in range(len(scen)):
            assert len(set(res.bind_boundary[s][members])) == 1
            assert len(set(res.assignments[s][members] >= 0)) == 1
    # placed + still queued + dropped = offered
    codes = res.bind_boundary
    assert ((codes >= -1).sum(1) == res.placed).all()
    assert ((codes == -3).sum(1) == res.retry_dropped).all()
    assert not ((codes < -3) | ((codes >= -1) != (res.assignments >= 0))).any()
    retry = res.fleet_telemetry.summary()["retry"]
    assert retry["groups"]["dropped_jobs"]["sum"] == res.group_counts[
        "dropped_jobs"].sum()


def test_a_job_is_dropped_whole_at_a_full_buffer_and_a_smaller_one_joins():
    ec, ep = job_trace(pods=400, duration=(400.0, 0.2))
    scen = scenarios(ec.num_nodes)[:2]
    eng, res, twins = run_both(ec, ep, scen, RB=40)
    for s, twin in enumerate(twins):
        np.testing.assert_array_equal(res.assignments[s], twin.assignments)
        np.testing.assert_array_equal(res.bind_boundary[s], twin.bind_boundary)
    assert (res.group_counts["dropped_jobs"] > 0).all()
    tab = job_table(ep, pack_waves(ep, 8).idx, 8)
    for s in range(len(scen)):
        dropped = res.bind_boundary[s] == -3
        queued_late = (res.bind_boundary[s] != -1) & ~dropped
        # a job behind a dropped one (a later chunk's, or smaller) still joined
        assert dropped.any() and queued_late.any()
        first_drop = np.nonzero(dropped)[0].min()
        assert (np.nonzero(queued_late)[0] > first_drop).any()
        for members in jobs_of(ep):
            assert len(set(dropped[members])) == 1
    assert res.fleet_telemetry.summary()["retry"]["depth_max"]["max"] <= 40


def test_a_wide_job_straddles_a_chunk_edge_with_a_pass_in_between(contended):
    ec, ep, scen, eng, res, twins = contended
    idx = pack_waves(ep, 8).idx
    tab = job_table(ep, idx, 8)
    chunk = np.full(ep.num_pods, -1)
    flat = idx.reshape(-1)
    chunk[flat[flat >= 0]] = np.nonzero(flat >= 0)[0] // 64
    straddlers = np.nonzero((tab[:, 0] > 8) & (chunk < tab[:, 2]))[0]
    assert straddlers.size  # members that sit in the chunk before the closing one
    # one such job is rolled back in some scenario and bound in another, and a
    # pass ran at the edge it crosses (the queue was not empty there)
    fates = {tuple(sorted(set((res.bind_boundary[:, p] == -1).tolist())))
             for p in straddlers}
    assert (False, True) in fates or {(False,), (True,)} <= fates


def test_a_job_is_released_whole(contended):
    """The members of a job give their resources back at one boundary: with
    the whole trace replayed and every bind released, the twin's planes end
    where the device's do, and the device's releases are owed to the last."""
    ec, ep, scen, eng, res, twins = contended
    retry = res.fleet_telemetry.summary()["retry"]
    assert retry["release_leaked"]["max"] == 0
    assert retry["retry_placed"]["max"] > 0


@pytest.mark.parametrize("key", sorted(WIDE_GANG_UNSUPPORTED))
def test_every_refusal_of_a_wide_gang_runs_or_is_refused_by_name(key, contended):
    """``completions`` and ``retry_buffer`` run, together, under
    ``retry_groups`` (compared above); each alone, and the other six, are
    refused through the one list."""
    from kubernetes_simulator_tpu.sim.waves import (
        WIDE_GANG_WITH_RETRY_GROUPS, refuse_wide_gangs,
    )

    refuse_wide_gangs(8, 8, **{key: True})  # no group wider than the wave
    with pytest.raises(ValueError, match="not supported with"):
        refuse_wide_gangs(8, 16, **{key: True})
    if key in WIDE_GANG_WITH_RETRY_GROUPS:
        refuse_wide_gangs(8, 16, retry_groups=True, **{key: True})
        ec, ep, scen, eng, res, twins = contended
        assert eng.retry_groups and eng._wide_gangs and eng.retry_buffer
        with pytest.raises(ValueError, match="not supported with"):
            WhatIfEngine(ec, ep, scen[:1], FrameworkConfig(), wave_width=8,
                         chunk_waves=8, completions=True, retry_buffer=128,
                         granularity_guard=False)
    else:
        with pytest.raises(ValueError, match="not supported with") as err:
            refuse_wide_gangs(8, 16, retry_groups=True, completions=True,
                              retry_buffer=True, **{key: True})
        assert WIDE_GANG_UNSUPPORTED[key] in str(err.value)
        assert WIDE_GANG_UNSUPPORTED["completions"] not in str(err.value)


def test_retry_groups_off_keeps_the_codes_and_the_refusals():
    ec, ep = job_trace(sizes={1: 0.6, 2: 0.2, 4: 0.2}, pods=300)
    res = greedy_replay(ec, ep, FrameworkConfig(), wave_width=8,
                        completions_chunk_waves=8, retry_buffer=64)
    gang = np.asarray(ep.group_id) >= 0
    none = res.assignments < 0
    assert (res.bind_boundary[none & gang] == -4).all()  # never queued
    on = greedy_replay(ec, ep, FrameworkConfig(), wave_width=8,
                       completions_chunk_waves=8, retry_buffer=64,
                       retry_groups=True)
    assert not (on.bind_boundary == -4).any()
    with pytest.raises(ValueError, match="retry_groups requires"):
        greedy_replay(ec, ep, FrameworkConfig(), wave_width=8,
                      retry_groups=True)


def test_the_twin_agrees_with_the_cpu_event_engines_permit_path():
    """``wave_width=1, chunk_waves=1`` on a queue-trivial trace (distinct
    arrivals a job, long durations, at most one job waiting at a time): a
    boundary follows every pod, so a pod group that is rejected whole, waits
    and binds once a running job has ended lands where the CPU event engine's
    coscheduling Permit path (reserve member by member, roll the group back
    on a member that fits nowhere, try again on the cluster event) puts it."""
    from kubernetes_simulator_tpu.models.core import Cluster, Node, Pod
    from kubernetes_simulator_tpu.sim.runtime import CpuReplayEngine

    nodes = [Node(f"n{i}", {"cpu": 4.0, "memory": 8 * 2**30, "pods": 8})
             for i in range(3)]
    pods = []

    def job(name, at, size, cpu, duration):
        for m in range(size):
            pods.append(Pod(
                f"{name}-{m}", labels={"app": name},
                requests={"cpu": cpu, "memory": 2**30}, priority=0,
                arrival_time=float(at), duration=float(duration),
                pod_group=name if size > 1 else None))

    job("a", 0, 2, 4.0, 35)    # fills two nodes until t = 35
    job("b", 10, 1, 2.0, 500)  # half of the third
    job("c", 20, 3, 2.0, 500)  # needs three halves: one is free -> waits
    job("d", 30, 1, 1.0, 500)  # backfills past the waiting group
    job("e", 40, 1, 1.0, 500)  # arrives after a's end: c went first
    job("f", 50, 2, 6.0, 500)  # fits no node, ever
    ec, ep = encode(Cluster(nodes=nodes), pods)
    plugins = [{"name": "NodeResourcesFit"}, {"name": "TaintToleration"}]
    cfg = FrameworkConfig(plugins=plugins)
    twin = greedy_replay(ec, ep, cfg, wave_width=1, completions_chunk_waves=1,
                         retry_buffer=16, retry_groups=True)
    cpu = CpuReplayEngine(ec, ep, cfg).replay()
    np.testing.assert_array_equal(twin.assignments, cpu.assignments)
    c = [i for i, p in enumerate(pods) if p.pod_group == "c"]
    assert (twin.assignments[c] >= 0).all() and (twin.bind_boundary[c] >= 0).all()
    assert twin.group_counts["pass_rollbacks"] >= 1  # it waited through a pass
    f = [i for i, p in enumerate(pods) if p.pod_group == "f"]
    assert (twin.assignments[f] < 0).all() and (twin.bind_boundary[f] == -2).all()


def test_a_job_whose_members_differ_is_refused():
    ec, ep = job_trace(pods=200)
    ep.priority = ep.priority.copy()
    member = int(np.nonzero(np.asarray(ep.group_id) >= 0)[0][1])
    ep.priority[member] += 7
    with pytest.raises(ValueError, match="must share one priority"):
        greedy_replay(ec, ep, FrameworkConfig(), wave_width=8,
                      completions_chunk_waves=8, retry_buffer=64,
                      retry_groups=True)
    with pytest.raises(ValueError, match="must share one priority"):
        WhatIfEngine(ec, ep, [Scenario()], FrameworkConfig(), wave_width=8,
                     chunk_waves=8, completions=True, retry_buffer=64,
                     retry_groups=True, granularity_guard=False)


def test_the_cli_takes_the_setting(tmp_path):
    """``validate`` refuses a wide gang with durations and a buffer unless
    ``whatIf.retryGroups`` is on, and ``what-if`` runs the deployment's rule
    at a small size from ``examples/config_pai_gang_backlog.yaml``'s keys."""
    import yaml
    from pathlib import Path

    from kubernetes_simulator_tpu import cli
    from kubernetes_simulator_tpu.utils.config import SimConfig

    root = Path(__file__).resolve().parents[1]
    doc = yaml.safe_load((root / "examples/config_pai_gang_backlog.yaml").read_text())
    doc["chunkWaves"] = 8
    doc["cluster"]["synthetic"].update(nodes=24, extendedResources={GPU: [8, 10]})
    doc["workload"]["synthetic"].update(pods=400, arrivalRate=4.0)
    doc["workload"]["synthetic"]["jobDurations"].update(median=30.0, mean=60.0)
    doc["whatIf"].update(scenarios=3, retryBuffer=128)
    doc["output"] = str(tmp_path / "rows.jsonl")
    on = tmp_path / "on.yaml"
    on.write_text(yaml.safe_dump(doc))
    assert cli.validate_config(SimConfig.load(str(on))) == []
    doc["whatIf"]["retryGroups"] = False
    off = tmp_path / "off.yaml"
    off.write_text(yaml.safe_dump(doc))
    errors = cli.validate_config(SimConfig.load(str(off)))
    assert any("not supported with" in e and "retry buffer" in e for e in errors)
    assert cli.main(["what-if", str(on)]) == 0
    rows = [l for l in (tmp_path / "rows.jsonl").read_text().splitlines() if l]
    assert len(rows) >= 3


def test_a_trace_with_no_group_wider_than_the_wave_carries_no_transaction():
    """Jobs of 1 to 8 workers at ``waveWidth`` 8: the state has no ``GangTxn``
    and the pass none of its own; a job is still queued, tried and released
    whole, and the device still gives the twin's answers."""
    ec, ep = job_trace(sizes={1: 0.5, 2: 0.2, 4: 0.2, 8: 0.1}, pods=500,
                       wide_fraction=0.9, duration=(40.0, 0.8))
    scen = scenarios(ec.num_nodes)[:3]
    eng, res, twins = run_both(ec, ep, scen, RB=64)
    assert not eng._wide_gangs
    for s, twin in enumerate(twins):
        np.testing.assert_array_equal(res.assignments[s], twin.assignments)
        np.testing.assert_array_equal(res.bind_boundary[s], twin.bind_boundary)
        for k in GROUP_COUNTERS:
            assert res.group_counts[k][s] == twin.group_counts[k], (s, k)
    assert (res.group_counts["jobs_bound_pass"] > 0).any()
    assert not res.group_counts["pass_rollbacks_after_bind"].any()


def test_the_waits_by_job_size_are_what_the_answers_imply(contended):
    """``WhatIfResult.job_waits`` is ``sim.waves.job_waits`` of the handed-back
    ``bind_boundary``: per job size of the trace the jobs a pass bound and the
    boundaries each waited since its closing chunk, counted here job by job."""
    ec, ep, scen, eng, res, twins = contended
    tab = job_table(ep, pack_waves(ep, 8).idx, 8)
    waits = res.job_waits
    assert list(waits["size"]) == sorted({len(m) for m in jobs_of(ep)})
    for s in range(len(scen)):
        want = {int(k): [0, 0, 0] for k in waits["size"]}
        for members in jobs_of(ep):
            b = int(res.bind_boundary[s][members[0]])
            if b >= 0:
                w, n = b - int(tab[members[0], 2]), want[len(members)]
                want[len(members)] = [n[0] + 1, n[1] + w, max(n[2], w)]
        got = {int(k): [int(waits[c][s, i]) for c in
                        ("bound_pass", "wait_sum", "wait_max")]
               for i, k in enumerate(waits["size"])}
        assert got == want
        assert waits["bound_pass"][s].sum() == res.group_counts[
            "jobs_bound_pass"][s]
    by_size = res.fleet_telemetry.summary()["retry"]["groups"][
        "waits_by_job_size"]
    assert sum(v["bound_pass"] for v in by_size.values()) == res.group_counts[
        "jobs_bound_pass"].sum()


def test_the_record_is_a_log_that_outgrows_one_block_of_the_buffer():
    """Under ``retry_groups`` a pass APPENDS what it bound to one log a
    scenario; the due releases read the log's blocks of ``retry_buffer``
    entries up to the fullest scenario's. With a buffer of 16 the passes bind
    more pods than two blocks hold, across the blocks' edges, and the answers are
    still the twin's, releases included."""
    ec, ep = job_trace(pods=400, seed=3, sizes={1: 0.4, 2: 0.2, 4: 0.2, 8: 0.2})
    scen = scenarios(ec.num_nodes)[:2]
    eng, res, twins = run_both(ec, ep, scen, RB=16)
    passes = (res.bind_boundary >= 0).sum(1)
    assert (passes > 2 * 16).all(), passes
    for s, twin in enumerate(twins):
        np.testing.assert_array_equal(res.assignments[s], twin.assignments)
        np.testing.assert_array_equal(res.bind_boundary[s], twin.bind_boundary)
    assert res.fleet_telemetry.summary()["retry"]["release_leaked"]["max"] == 0
