"""A maintenance drain under disruption budgets on the what-if DEVICE path
(PR 49): a batch whose scenarios carry ``node_cordon`` events and a
``DisruptionBudget`` runs on ``release_path == "device"`` with no host mirror
and answers, task for task, what ``JaxReplayEngine(retry_buffer=...).replay(
node_events=..., budget=...)`` answers through ``BoundaryOps.budget_events``,
the host twin of the same rule: node, ``bind_boundary``, the eviction log with
each row's kind, ``node_out_at`` and the counters. With every budget infinite
and ``grace`` 0 the answers are those of the drain rule (``node_down`` at the
cordon's boundary, ``node_up`` ``out_for`` later: PR 45's path, no budget
anywhere) on the same trace; and a batch WITHOUT budgets keeps PR 47's three
programs, letter for letter.

The cell is ``tests/test_whatif_events_device.py``'s (48 nodes, dyadic cpu
requests: PERF.md §6, PR 45)."""

import hashlib

import numpy as np
import pytest

from kubernetes_simulator_tpu.framework.framework import FrameworkConfig
from kubernetes_simulator_tpu.sim import borg
from kubernetes_simulator_tpu.sim.boundary import BUDGET_COUNTERS, EVICT_KINDS
from kubernetes_simulator_tpu.sim.jax_runtime import JaxReplayEngine, wave_start_times
from kubernetes_simulator_tpu.sim.runtime import (
    DisruptionBudget, NodeEvent, validate_node_events)
from kubernetes_simulator_tpu.sim.waves import pack_waves
from kubernetes_simulator_tpu.sim.whatif import Scenario, WhatIfEngine

W, C, BUFFER = 8, 16, 512
FREE = 1 << 30


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


def timeline(tb, *events):
    """``(boundary, kind, node)`` -> a sorted timeline at the boundaries'
    start times."""
    events = sorted(events, key=lambda e: e[0])
    return [NodeEvent(float(tb[b]), kind, int(n)) for b, kind, n in events]


def cordons(first, nodes, step):
    return [(first + i // step, "node_cordon", n) for i, n in enumerate(nodes)]


CELL = {}


def batch():
    """One batch of every plan below, and its cell: built once."""
    if not CELL:
        ec, ep, tb = cell()
        app = np.asarray(ep.app_id)
        A = int(app.max()) + 1
        budget = lambda most, grace, out_for: DisruptionBudget(
            app, np.full(A, most, np.int32), grace, out_for)
        plans = {
            "base": ([], None),
            # one task of an application at a time: the nodes drain over
            # several boundaries, each re-bind freeing the next eviction
            "a_budget_of_1": (cordons(1, range(4), 2), budget(1, 8, 1)),
            # nothing may leave: a node empties by its tasks' own releases,
            # or is forced at its deadline
            "a_budget_of_0": (cordons(1, (8, 9), 2), budget(0, 3, 2)),
            # two of an application at a time: both nodes drain EMPTY, the
            # second over three boundaries, and go out with no deadline near
            "a_budget_of_2": (cordons(1, (44, 45), 2), budget(2, 9, 1)),
            "a_deadline": (cordons(2, range(16, 24), 4), budget(1, 1, 2)),
            "failures": (
                cordons(1, range(24, 36), 4) + [
                    (2, "node_down", 25), (4, "node_up", 25),  # cordoned
                    (1, "node_down", 40), (3, "node_up", 40),  # plain
                    (1, "node_down", 30), (3, "node_up", 30),  # out at its cordon (b 2)
                    (3, "node_down", 33), (5, "node_up", 33),  # fails at its cordon
                    (5, "node_down", 2),                       # never back
                ], budget(2, 2, 2)),
            # the drain rule, written as a budgeted drain
            "infinite_grace_0": (cordons(1, (10, 11, 12), 3)
                                 + cordons(3, range(20, 40), 20),
                                 budget(FREE, 0, 2)),
        }
        scenarios = [Scenario(events=timeline(tb, *ev), budget=b)
                     for ev, b in plans.values()]
        eng = WhatIfEngine(
            ec, ep, scenarios, FrameworkConfig(), wave_width=W, chunk_waves=C,
            completions=True, retry_buffer=BUFFER, collect_assignments=True,
            telemetry="summary")
        assert eng.release_path == "device" and not eng.kube and eng._budget_on
        CELL.update(ec=ec, ep=ep, tb=tb, plans=plans, scenarios=scenarios,
                    eng=eng, res=eng.run())
    return CELL


ANCHOR = {}


def anchor(name):
    """The single replay of one plan through the host twin."""
    if name not in ANCHOR:
        c = batch()
        s = list(c["plans"]).index(name)
        rep = JaxReplayEngine(c["ec"], c["ep"], FrameworkConfig(), wave_width=W,
                              chunk_waves=C, completions=True, retry_buffer=BUFFER)
        sc = c["scenarios"][s]
        ANCHOR[name] = (rep.replay(node_events=sc.events, budget=sc.budget),
                        rep._last_bops)
    return ANCHOR[name]


NAMES = ("base", "a_budget_of_1", "a_budget_of_0", "a_budget_of_2",
         "a_deadline", "failures", "infinite_grace_0")


@pytest.mark.parametrize("name", NAMES)
def test_the_four_answers_are_the_host_twins(name):
    c = batch()
    res, s = c["res"], list(c["plans"]).index(name)
    single, bops = anchor(name)
    np.testing.assert_array_equal(res.assignments[s], single.assignments)
    np.testing.assert_array_equal(res.bind_boundary[s], bops.bind_boundary_codes())
    got = res.eviction_log[s]
    got = got[got[:, 1] >= 0]
    assert got.shape[1] == 5 and len(got) == int(res.evictions[s])
    if name == "base":
        assert bops.budget is None and not len(got)
        assert (res.node_out_at[s] == -1).all()
        return
    log = np.asarray(bops.evict_log, np.int32).reshape(-1, 4)
    kinds = np.asarray(bops.evict_kind, np.int32)
    np.testing.assert_array_equal(got, np.concatenate([log, kinds[:, None]], 1))
    np.testing.assert_array_equal(res.node_out_at[s], bops.node_out_at)
    assert int(res.placed[s]) == single.placed
    assert int(res.evict_rescheduled[s]) == single.evict_rescheduled
    assert int(res.retry_dropped[s]) == single.retry_dropped
    assert len(log) > 0


def test_the_counters_are_the_host_twins():
    c = batch()
    retry = c["res"].fleet_telemetry.summary()["retry"]
    twins = [anchor(n)[1] for n in NAMES[1:]]
    for k in BUDGET_COUNTERS:
        per = [0] + [t.budget_counts[k] for t in twins]
        assert retry[k]["max"] == max(per), k
        assert retry[k]["mean"] == pytest.approx(np.mean(per)), k
    assert retry["release_leaked"]["max"] == 0


def test_what_the_plans_exercise():
    """The cases are what they say."""
    c = batch()
    res, names = c["res"], list(c["plans"])
    app = np.asarray(c["ep"].app_id)
    kinds_of = lambda n: res.eviction_log[names.index(n)][
        res.eviction_log[names.index(n)][:, 1] >= 0]
    # a budget of 1: an application loses one task a boundary and none while
    # its evicted task waits in the queue of a cell that is full (a re-bind in
    # between frees the next eviction: several boundaries an application);
    # two applications leave one node at one boundary; tasks are refused and
    # asked again; the drain stalls and the deadline takes the rest
    one = kinds_of("a_budget_of_1")
    vol = one[one[:, 4] == EVICT_KINDS["voluntary"]]
    per_app = {}
    for b, task in vol[:, :2]:
        per_app.setdefault(int(app[task]), []).append(int(b))
    assert all(len(set(v)) == len(v) for v in per_app.values())
    assert max(len(v) for v in per_app.values()) >= 3
    first = vol[(vol[:, 0] == vol[0, 0]) & (vol[:, 2] == vol[0, 2])]
    assert len({int(app[t]) for t in first[:, 1]}) >= 2
    _, bops = anchor("a_budget_of_1")
    assert bops.budget_counts["evict_deferred"] > 0
    assert bops.budget_counts["nodes_forced"] == 4
    out = res.node_out_at[names.index("a_budget_of_1")]
    assert out[:4].tolist() == [9, 9, 10, 10] and (out[4:] == -1).all()
    # a budget of 0: no voluntary eviction; what leaves leaves by its own
    # release, and the rest at the deadline
    zero = kinds_of("a_budget_of_0")
    assert len(zero) and (zero[:, 4] == EVICT_KINDS["deadline"]).all()
    assert set(zero[:, 0].tolist()) == {4}
    # a budget of 2: every eviction voluntary, some boundaries after the
    # cordon; each node goes out at the boundary it is found empty
    two = kinds_of("a_budget_of_2")
    assert (two[:, 4] == EVICT_KINDS["voluntary"]).all()
    assert set(two[:, 0].tolist()) == {1, 2, 3}
    out = res.node_out_at[names.index("a_budget_of_2")]
    assert out[44] == 1 and out[45] == 3 == two[two[:, 2] == 45][:, 0].max()
    _, bops = anchor("a_budget_of_2")
    assert bops.budget_counts["nodes_drained"] == 2
    assert bops.budget_counts["nodes_forced"] == 0
    # a deadline: both kinds, the forced a boundary after the cordon
    dead = kinds_of("a_deadline")
    assert {EVICT_KINDS["voluntary"], EVICT_KINDS["deadline"]} == set(
        dead[:, 4].tolist())
    # failures: of a cordoned node (out there, maintenance done), of a node
    # in service, of a node whose cordon finds it out, at the boundary of
    # its cordon
    s = names.index("failures")
    fail = kinds_of("failures")
    forced = fail[fail[:, 4] == EVICT_KINDS["failure"]]
    assert {25, 40, 30, 33, 2} >= set(forced[:, 2].tolist()) >= {25, 40, 30, 33}
    assert res.node_out_at[s][25] == 2 and res.node_out_at[s][40] == -1
    assert res.node_out_at[s][30] == -1 and res.node_out_at[s][33] == -1
    assert not (fail[(fail[:, 2] == 30)][:, 4] != EVICT_KINDS["failure"]).any()
    # node 2 never comes back; node 40 takes tasks again from boundary 3 on
    late = (res.bind_boundary[s] >= 5) & (res.assignments[s] >= 0)
    assert not (res.assignments[s][late] == 2).any()
    assert (res.assignments[s][res.bind_boundary[s] >= 3] == 40).any()


def test_infinite_budgets_and_no_grace_answer_what_the_drain_rule_answers():
    """``node_cordon`` at b with every limit infinite, grace 0, ``out_for``
    2, against PR 45's path: ``node_down`` at b and ``node_up`` at b + 2, no
    budget anywhere (``BoundaryOps.evict_node``)."""
    c = batch()
    res, s = c["res"], list(c["plans"]).index("infinite_grace_0")
    tb = c["tb"]
    rule = timeline(
        tb, *[(1, "node_down", n) for n in (10, 11, 12)],
        *[(3, "node_up", n) for n in (10, 11, 12)],
        *[(3, "node_down", n) for n in range(20, 40)],
        *[(5, "node_up", n) for n in range(20, 40)])
    rep = JaxReplayEngine(c["ec"], c["ep"], FrameworkConfig(), wave_width=W,
                          chunk_waves=C, completions=True, retry_buffer=BUFFER)
    single = rep.replay(node_events=rule)
    bops = rep._last_bops
    assert bops.budget is None
    np.testing.assert_array_equal(res.assignments[s], single.assignments)
    np.testing.assert_array_equal(res.bind_boundary[s], bops.bind_boundary_codes())
    log = np.asarray(bops.evict_log, np.int32).reshape(-1, 4)
    got = res.eviction_log[s][: len(log)]
    np.testing.assert_array_equal(got[:, :4], log)
    assert (got[:, 4] == EVICT_KINDS["voluntary"]).all()
    assert (res.eviction_log[s][len(log):] == -1).all()
    out = res.node_out_at[s]
    assert (out[[10, 11, 12]] == 1).all() and (out[20:40] == 3).all()
    assert (np.delete(out, [10, 11, 12] + list(range(20, 40))) == -1).all()


# sha256 of ``.lower().as_text()`` of the three programs a boundary of
# ``test_whatif_events_device.batch(512)`` dispatches, on the parent commit
# (c04e4fa, PR 47): a batch without budgets has to keep them. The eviction
# program's is PR 50's, whose one search both eviction programs call (until
# then dcc741d2...ba75267, c04e4fa's): budgets still add nothing to it. The
# pass program's is PR 53's, whose one packed row a slot holds ``app`` under
# budgets alone (until then 66ceeb0f...a1776d0b, c04e4fa's).
PARENTS = {
    "jit_whatif_evict":
        "f619a16f915967c9f3490a3b1d3fa90356eb68324ad3ff00b35b55936e96bbf6",
    "jit_per_scenario_retry":
        "fca751621fd7d8ce67ea2a29317d146183f94907071f73fa7460662a3305c49d",
    "jit_per_scenario_arrivals":
        "4737374757b7bb09c2bf9541d9a2da6678b2e08160c80ed49b30c119f056bb94",
}


def test_a_batch_without_budgets_keeps_the_parents_three_programs(
        tmp_path, monkeypatch):
    import test_whatif_events_device as drained

    from kubernetes_simulator_tpu.utils import profiling

    ec, ep, tb = drained.cell()
    plans = [[], drained.timeline(drained.plan(tb, 2, range(6))),
             drained.timeline(drained.plan(tb, 1, (10, 11, 12), 1)),
             drained.timeline(drained.plan(tb, 3, range(20, 40), 3)),
             drained.timeline(drained.plan(tb, 1, range(24), 1),
                              drained.plan(tb, 3, range(24, 48), 2)),
             drained.timeline(drained.plan(tb, 2, (30, 31)),
                              [(tb[2], "node_up", 30)])]
    eng = WhatIfEngine(
        ec, ep, [Scenario(events=tl) for tl in plans], FrameworkConfig(),
        wave_width=W, chunk_waves=C, completions=True, retry_buffer=512,
        collect_assignments=True, telemetry="summary")
    assert eng._events_dev and not eng._budget_on
    profiling._PROGRAMS.clear()
    monkeypatch.setenv("KSIM_PROFILE_DIR", str(tmp_path))
    res = eng.run()
    monkeypatch.delenv("KSIM_PROFILE_DIR")
    assert res.node_out_at is None and res.eviction_log.shape[2] == 4
    state = eng._evict_state()
    assert state.until is None and state.unavail is None and state.bn is None
    for name, want in PARENTS.items():
        text = profiling._PROGRAMS[name]().as_text()
        assert hashlib.sha256(text.encode()).hexdigest() == want, name
    profiling._PROGRAMS.clear()


def test_a_batch_made_again_and_a_batch_swapped_compile_nothing():
    c = batch()
    eng, res = c["eng"], c["res"]
    sizes = dict(eng._evict_sizes)
    counts = lambda: (eng._chunk_fn._cache_size(), eng._retry_fn._cache_size(),
                      eng._evict_fn()._cache_size())
    before = counts()
    eng.set_scenarios([Scenario() for _ in c["scenarios"]])
    quiet = eng.run()
    assert int(quiet.evictions.sum()) == 0 and (quiet.node_out_at == -1).all()
    np.testing.assert_array_equal(quiet.assignments[1], res.assignments[0])
    eng.set_scenarios(c["scenarios"])
    back = eng.run()
    for k in ("assignments", "bind_boundary", "eviction_log", "node_out_at"):
        np.testing.assert_array_equal(getattr(back, k), getattr(res, k))
    assert eng._evict_sizes == sizes and counts() == before


def test_what_is_refused_and_why():
    c = batch()
    ec, ep, tb = c["ec"], c["ep"], c["tb"]
    app = np.asarray(ep.app_id)
    bud = DisruptionBudget(app, np.ones(int(app.max()) + 1, np.int32), 1, 1)
    tl = timeline(tb, (1, "node_cordon", 3))
    make = lambda scenarios, **kw: WhatIfEngine(
        ec, ep, scenarios, FrameworkConfig(), wave_width=W, chunk_waves=C,
        completions=True, **kw)
    with pytest.raises(ValueError, match="retry_buffer > 0"):
        make([Scenario(events=tl, budget=bud)])
    with pytest.raises(ValueError, match="node_cordon event or a disruption budget"):
        make([Scenario(events=tl, budget=bud)], retry_buffer=64,
             preemption="kube")
    with pytest.raises(ValueError, match="the batch carries none"):
        make([Scenario(events=tl)], retry_buffer=64)
    with pytest.raises(ValueError, match="share app_of"):
        make([Scenario(events=tl, budget=bud), Scenario(
            events=tl, budget=DisruptionBudget(app[::-1].copy(),
                                               bud.max_unavailable))],
             retry_buffer=64)
    with pytest.raises(ValueError, match="cordoned again"):
        eng = make([Scenario(events=timeline(
            tb, (1, "node_cordon", 3), (2, "node_cordon", 3)), budget=bud)],
            retry_buffer=64, collect_assignments=True)
        eng.run()
    # an engine built without budgets cannot be handed them later
    plain = make([Scenario(events=timeline(tb, (1, "node_down", 3)))],
                 retry_buffer=64)
    with pytest.raises(ValueError, match="built without any"):
        plain.set_scenarios([Scenario(events=tl, budget=bud)])
    # the single replay: a cordon on the boundary path needs the budget
    rep = JaxReplayEngine(ec, ep, FrameworkConfig(), wave_width=W,
                          chunk_waves=C, completions=True, retry_buffer=64)
    with pytest.raises(ValueError, match="pass budget="):
        rep.replay(node_events=tl)
    with pytest.raises(ValueError, match="requires retry_buffer"):
        JaxReplayEngine(ec, ep, FrameworkConfig(), wave_width=W, chunk_waves=C,
                        completions=True).replay(node_events=tl, budget=bud)
    with pytest.raises(ValueError, match="names application"):
        DisruptionBudget(app, np.ones(2, np.int32))
    with pytest.raises(ValueError, match="out_for >= 1"):
        DisruptionBudget(app, bud.max_unavailable, 1, 0)


def test_validate_node_events_knows_the_fourth_kind():
    ok = [NodeEvent(1.0, "node_cordon", 0), NodeEvent(2.0, "node_down", 0),
          NodeEvent(3.0, "node_up", 0), NodeEvent(4.0, "node_cordon", 0)]
    assert validate_node_events(ok, 4) is ok
    with pytest.raises(ValueError, match="node_down, node_up, capacity_scale, "
                                         "node_cordon"):
        validate_node_events([NodeEvent(1.0, "node_drain", 0)], 4)
    with pytest.raises(ValueError, match="without a prior node_down"):
        validate_node_events([NodeEvent(1.0, "node_cordon", 0),
                              NodeEvent(2.0, "node_up", 0)], 4)


def test_the_cpu_event_engine_closes_a_cordoned_node():
    """``node_cordon`` on the CPU event engine: the node takes no bind from
    the event on, what runs there keeps running, a ``node_up`` after a
    ``node_down`` opens it again."""
    from kubernetes_simulator_tpu.sim.runtime import CpuReplayEngine

    ec, ep, tb = cell()
    resident = np.asarray(ep.bound_node)
    plain = CpuReplayEngine(ec, ep, FrameworkConfig()).replay()
    assert (plain.assignments[resident < 0] == 5).any()
    got = CpuReplayEngine(ec, ep, FrameworkConfig()).replay(
        node_events=[NodeEvent(0.0, "node_cordon", 5)])
    assert not (got.assignments[resident < 0] == 5).any()
    np.testing.assert_array_equal(got.assignments[resident == 5], 5)
    assert got.evictions == 0
