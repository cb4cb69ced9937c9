"""Timed node events on the what-if DEVICE path (PR 45): a batch whose
scenarios carry timelines, ``retry_buffer > 0`` and no ``preemption`` runs
on ``release_path == "device"`` with no host mirror, and answers, task for
task, what ``JaxReplayEngine(retry_buffer=...).replay(node_events=...)``
answers on each scenario's own timeline: node, ``bind_boundary`` and the
eviction log, with the disruption counts, ``release_leaked`` 0 and the final
``used`` beside them.

The cell is Borg-shaped with a resident set (48 nodes), its cpu requests
made dyadic: the anchor's retry pass runs on a host mirror whose float32
sums round apart from the device's where 0.1-core requests meet, and a score
on an integer edge then falls either way (PERF.md §6, PR 45)."""

import numpy as np
import pytest

from kubernetes_simulator_tpu.framework.framework import FrameworkConfig
from kubernetes_simulator_tpu.sim import borg
from kubernetes_simulator_tpu.sim.jax_runtime import JaxReplayEngine, wave_start_times
from kubernetes_simulator_tpu.sim.runtime import NodeEvent
from kubernetes_simulator_tpu.sim.waves import pack_waves
from kubernetes_simulator_tpu.sim.whatif import Scenario, WhatIfEngine

W, C = 8, 16


def cell(seed=3):
    spec = borg.BorgSpec(nodes=48, tasks=1536, seed=seed, tasks_per_day=4800,
                         resident_fill=0.9, resident_band=0.05)
    cols, res = borg._sample_cols(spec), borg._resident_cols(spec)
    cols["bound_node"] = np.full(spec.tasks, -1, np.int32)
    cols = {k: np.concatenate([res[k], cols[k]]) for k in res}
    cols["cpu"] = np.where(np.isclose(cols["cpu"], 0.1), 0.125,
                           cols["cpu"]).astype(np.float32)
    ec, ep, _ = borg.encoded_from_cols(spec, cols)
    tb = wave_start_times(ep, pack_waves(ep, W).idx)[0::C]
    return ec, ep, tb


def plan(tb, first, nodes, out_for=None):
    """``nodes`` go down at boundary ``first`` and, with ``out_for``, come
    back that many boundaries later."""
    events = [(tb[first], "node_down", n) for n in nodes]
    if out_for is not None and first + out_for < len(tb):
        events += [(tb[first + out_for], "node_up", n) for n in nodes]
    return events


def timeline(*plans):
    events = sorted((e for p in plans for e in p), key=lambda e: e[0])
    return [NodeEvent(time=float(t), kind=k, node=int(n)) for t, k, n in events]


CELL = {}


def batch(buffer):
    """One batch of every plan below at ``buffer``, and its cell: built once."""
    if buffer not in CELL:
        ec, ep, tb = cell()
        plans = {
            "base": [],
            "one_step": timeline(plan(tb, 2, range(6))),
            "with_a_return": timeline(plan(tb, 1, (10, 11, 12), 1)),
            "a_wide_step": timeline(plan(tb, 3, range(20, 40), 3)),
            # what the first half gives up can only go to the second, which
            # goes next
            "evicted_twice": timeline(plan(tb, 1, range(24), 1),
                                      plan(tb, 3, range(24, 48), 2)),
            "down_and_back_in_one_boundary": timeline(
                plan(tb, 2, (30, 31)), [(tb[2], "node_up", 30)]),
        }
        eng = WhatIfEngine(
            ec, ep, [Scenario(events=tl) for tl in plans.values()],
            FrameworkConfig(), wave_width=W, chunk_waves=C, completions=True,
            retry_buffer=buffer, collect_assignments=True, telemetry="summary")
        assert eng.release_path == "device" and not eng.kube
        res = eng.run()
        CELL[buffer] = (ec, ep, plans, eng, res, eng._last_states)
    return CELL[buffer]


ANCHOR = {}


def anchor(name, buffer):
    """The single replay of one plan at ``buffer`` and its boundary mirror:
    made once, read by every test that holds the batch to it."""
    if (name, buffer) not in ANCHOR:
        ec, ep, plans = batch(buffer)[:3]
        rep = JaxReplayEngine(ec, ep, FrameworkConfig(), wave_width=W,
                              chunk_waves=C, completions=True,
                              retry_buffer=buffer)
        ANCHOR[name, buffer] = (rep.replay(node_events=plans[name]),
                                rep._last_bops)
    return ANCHOR[name, buffer]


CASES = [(name, buffer) for buffer in (512, 64) for name in (
    "base", "one_step", "with_a_return", "a_wide_step", "evicted_twice",
    "down_and_back_in_one_boundary")]


@pytest.mark.parametrize("name, buffer", CASES)
def test_the_three_answers_are_the_single_replays(name, buffer):
    ec, ep, plans, eng, res, states = batch(buffer)
    s = list(plans).index(name)
    single, bops = anchor(name, buffer)
    np.testing.assert_array_equal(res.assignments[s], single.assignments)
    np.testing.assert_array_equal(res.bind_boundary[s], bops.bind_boundary_codes())
    log = np.asarray(bops.evict_log, np.int32).reshape(-1, 4)
    assert int(res.evictions[s]) == len(log) == single.evictions
    np.testing.assert_array_equal(res.eviction_log[s][:len(log)], log)
    assert (res.eviction_log[s][len(log):] == -1).all()
    assert int(res.placed[s]) == single.placed
    assert int(res.unschedulable[s]) == single.unschedulable
    assert int(res.evict_rescheduled[s]) == single.evict_rescheduled
    assert int(res.evict_stranded[s]) == single.evict_stranded
    assert int(res.retry_dropped[s]) == single.retry_dropped
    assert float(res.evict_latency_mean[s]) == pytest.approx(
        single.evict_latency_mean, rel=1e-5)
    used = np.asarray(states.used)[s].T
    np.testing.assert_allclose(used, single.state.used, rtol=1e-6, atol=1e-4)
    retry = res.fleet_telemetry.summary()["retry"]
    assert retry["release_leaked"]["max"] == 0
    if name != "base":
        assert len(log) > 0


@pytest.mark.parametrize("buffer", [512, 64])
def test_a_timelines_batch_counts_its_pass_waves_like_every_retry_batch(buffer):
    """``summary()["retry"]["pass_waves"]`` comes from the ``RetryQueue``
    (``_EV_COUNTERS`` has lost the name: it is no eviction counter) and is
    what the depths of the plans' queues give, the evicted standing in them
    from their boundary on: the sum over the boundaries of ``ceil(deepest
    plan's queue / W)``, each plan's depths from its single replay's answers
    and eviction log."""
    from test_retry_device import pass_waves_of, queue_depths

    from kubernetes_simulator_tpu.sim import whatif

    assert "pass_waves" not in whatif._EV_COUNTERS
    assert "pass_waves" in whatif.RetryQueue._fields
    ec, ep, plans, eng, res, _ = batch(buffer)
    depths = []
    for s, name in enumerate(plans):
        single, bops = anchor(name, buffer)
        codes = bops.bind_boundary_codes()
        np.testing.assert_array_equal(res.bind_boundary[s], codes)
        np.testing.assert_array_equal(res.assignments[s], single.assignments)
        depths.append(queue_depths(ep, codes, W, C, log=bops.evict_log))
    depths = np.array(depths)
    retry = res.fleet_telemetry.summary()["retry"]
    want = pass_waves_of(depths, W)
    assert retry["pass_waves"] == {"mean": float(want), "max": want}
    assert retry["scenario0"]["pass_waves"] == want
    assert retry["depth_max"]["max"] == depths.max()
    assert depths.shape[1] == retry["passes"]
    # the evicted are in the count: the base plan alone would end sooner
    assert 0 < pass_waves_of(depths[:1], W) < want < retry["passes"] * buffer // W


def test_what_the_plans_exercise():
    """The cases are what they say: a gang member on a leaving node (-5), a
    task evicted twice, a re-tried bind evicted, a buffer that overflows at
    an eviction, a node down and back inside one boundary."""
    ec, ep, plans, eng, res, _ = batch(64)
    names = list(plans)
    gang = np.asarray(ep.group_id) >= 0
    assert ((res.bind_boundary == -5) & gang[None, :]).any()
    assert not ((res.bind_boundary == -5) & ~gang[None, :]).any()
    twice = batch(512)[4].eviction_log[names.index("evicted_twice")]
    _, counts = np.unique(twice[twice[:, 1] >= 0, 1], return_counts=True)
    assert (counts >= 2).any()
    assert (res.eviction_log[:, :, 3] >= 0).any()  # a re-tried bind evicted
    summary = res.fleet_telemetry.summary()["retry"]
    assert summary["evict_dropped"]["max"] > 0
    assert summary["evict_rebound_same_boundary"]["max"] > 0
    s = names.index("down_and_back_in_one_boundary")
    both = res.eviction_log[s]
    assert {30, 31} == set(both[both[:, 1] >= 0, 2].tolist())
    # node 30 is back at once and takes tasks again; node 31 never does
    later = (res.bind_boundary[s] >= 2) & (res.assignments[s] >= 0)
    assert (res.assignments[s][later] == 30).any()
    assert not (res.assignments[s] == 31).any()


def test_scenario_0_is_the_batch_built_without_timelines():
    ec, ep, plans, eng, res, _ = batch(512)
    plain = WhatIfEngine(
        ec, ep, [Scenario() for _ in plans], FrameworkConfig(), wave_width=W,
        chunk_waves=C, completions=True, retry_buffer=512,
        collect_assignments=True)
    assert not plain._events_dev and plain._evict_stage is None
    got = plain.run()
    assert got.eviction_log is None and got.evictions is None
    np.testing.assert_array_equal(got.assignments[0], res.assignments[0])
    np.testing.assert_array_equal(got.bind_boundary[0], res.bind_boundary[0])
    assert int(got.placed[0]) == int(res.placed[0])


def test_a_batch_made_again_and_a_batch_swapped_compile_nothing():
    ec, ep, plans, eng, res, _ = batch(512)
    sizes = dict(eng._evict_sizes)
    chunk, evict = eng._chunk_fn._cache_size(), eng._evict_fn()._cache_size()
    retry = eng._retry_fn._cache_size()
    again = eng.run()
    np.testing.assert_array_equal(again.assignments, res.assignments)
    np.testing.assert_array_equal(again.bind_boundary, res.bind_boundary)
    np.testing.assert_array_equal(again.eviction_log, res.eviction_log)
    eng.set_scenarios([Scenario() for _ in plans])
    quiet = eng.run()
    assert int(quiet.evictions.sum()) == 0
    np.testing.assert_array_equal(quiet.assignments[1], res.assignments[0])
    eng.set_scenarios([Scenario(events=tl) for tl in plans.values()])
    back = eng.run()
    np.testing.assert_array_equal(back.eviction_log, res.eviction_log)
    assert eng._evict_sizes == sizes
    assert eng._chunk_fn._cache_size() == chunk
    assert eng._retry_fn._cache_size() == retry
    assert eng._evict_fn()._cache_size() == evict


def test_a_log_reckoned_too_small_is_made_again_larger():
    ec, ep, plans, eng, res, _ = batch(512)
    small = WhatIfEngine(
        ec, ep, [Scenario(events=tl) for tl in plans.values()],
        FrameworkConfig(), wave_width=W, chunk_waves=C, completions=True,
        retry_buffer=512, collect_assignments=True)
    real = small._stage_events

    def tight():
        stage = real()
        if small._evict_scale == 1:  # room for two blocks of 128 rows
            stage.update(E=128, cap=256)
            small._evict_sizes.update(E=128, cap=256)
        return stage

    small._stage_events = tight
    got = small.run()
    assert small._evict_scale > 1
    np.testing.assert_array_equal(got.eviction_log, res.eviction_log)
    np.testing.assert_array_equal(got.assignments, res.assignments)


def test_an_engine_built_without_timelines_takes_none_later():
    ec, ep, tb = cell()
    eng = WhatIfEngine(
        ec, ep, [Scenario()], FrameworkConfig(), wave_width=W, chunk_waves=C,
        completions=True, retry_buffer=64)
    with pytest.raises(ValueError, match="built without"):
        eng.set_scenarios([Scenario(events=timeline(plan(tb, 1, (0,))))])


def test_the_eviction_program_carries_its_stage_scope(tmp_path, monkeypatch):
    from kubernetes_simulator_tpu.utils import profiling

    # the registry is the process's: an armed run of an earlier file may have
    # left a program there that cannot be lowered again (a stub)
    profiling._PROGRAMS.clear()
    monkeypatch.setenv("KSIM_PROFILE_DIR", str(tmp_path))
    ec, ep, tb = cell()
    eng = WhatIfEngine(
        ec, ep, [Scenario(events=timeline(plan(tb, 1, (0, 1), 1)))],
        FrameworkConfig(), wave_width=W, chunk_waves=C, completions=True,
        retry_buffer=64)
    eng.run()
    tables = profiling.stage_tables()
    assert "ksim.evict" in set(tables["jit_whatif_evict"].values())
    assert "ksim.evict" in profiling.STAGES
    # the boundary's two chunk programs under timelines (PR 47): the pass,
    # which counts the evicted it binds again, and the arrival scan
    assert "ksim.retry/ksim.select" in set(tables["jit_per_scenario_retry"].values())
    assert {"ksim.select", "ksim.retry"} <= set(
        tables["jit_per_scenario_arrivals"].values())


def test_the_two_programs_answer_what_the_one_program_answered():
    """Value-exact under timelines: the three hand-back arrays of the six
    plans at buffer 64 and ``summary()["retry"]``, the events' counters in
    it, are what the tree before PR 47 (d4a9348: one chunk program a
    boundary) gave."""
    import hashlib

    res = batch(64)[4]
    sha = lambda a: hashlib.sha256(
        np.ascontiguousarray(a).tobytes()).hexdigest()[:16]
    assert sha(res.assignments) == "94ff73674528097f"
    assert sha(res.bind_boundary) == "4982ac2248a6953e"
    assert sha(res.eviction_log) == "38f209657d60365c"
    got = res.fleet_telemetry.summary()["retry"]
    assert got.pop("scenario0") == {
        "depth_at_end": 0, "depth_max": 64, "evict_dropped": 0,
        "evict_gang_stranded": 0, "evict_rebound_later": 0,
        "evict_rebound_same_boundary": 0, "evict_retried": 0,
        "evict_stranded": 0, "evict_wait_boundaries_max": 0,
        "evict_wait_boundaries_mean": 0.0, "evictions": 0,
        "handback_merged": 194, "pass_waves": 94, "release_leaked": 0,
        "retry_dropped": 3, "retry_placed": 194}
    assert got.pop("buffer") == 64 and got.pop("passes") == 13
    assert {k: v["max"] for k, v in got.items()} == {
        "depth_at_end": 41, "depth_max": 64, "evict_dropped": 999,
        "evict_gang_stranded": 8, "evict_rebound_later": 22,
        "evict_rebound_same_boundary": 110, "evict_retried": 40,
        "evict_stranded": 1002, "evict_wait_boundaries_max": 10,
        "evict_wait_boundaries_mean": 0.8035714285714286, "evictions": 1130,
        "handback_merged": 435, "pass_waves": 94, "release_leaked": 0,
        "retry_dropped": 1052, "retry_placed": 435}
    assert {k: v["mean"] for k, v in got.items()} == pytest.approx({
        "depth_at_end": 9.0, "depth_max": 64.0,
        "evict_dropped": 269.1666666666667, "evict_gang_stranded": 2.0,
        "evict_rebound_later": 10.5,
        "evict_rebound_same_boundary": 51.666666666666664,
        "evict_retried": 6.833333333333333,
        "evict_stranded": 271.3333333333333,
        "evict_wait_boundaries_max": 3.8333333333333335,
        "evict_wait_boundaries_mean": 0.30117527521761395,
        "evictions": 333.5, "handback_merged": 251.66666666666666,
        "pass_waves": 94.0, "release_leaked": 0.0,
        "retry_dropped": 358.3333333333333, "retry_placed": 258.5})
