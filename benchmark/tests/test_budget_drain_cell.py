"""The budgeted drain's own tests (``borg10k-budget128``), on the CPU, run by
hand like their siblings:

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_budget_drain_cell.py -q
"""

import functools
import json
import re
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path[:0] = [str(ROOT), str(BENCH)]

import budget_plans  # noqa: E402
import roofline_budget_drain  # noqa: E402
import run  # noqa: E402
import trace_reduce  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELL = "borg10k-budget128"
SEED = 2147483999
METRICS = ("budget_evict_ms_per_boundary", "budget_evict_share",
           "budget_admit_ms_per_boundary", "budget_admit_roofline",
           "budget_retry_ms_per_boundary", "budget_handback_ms_per_batch",
           "budget_host_events_ms_per_boundary", "budget_host_untraced_share")
BUDGET_ROWS = ("ref.voluntary_evictions_over_budget",
               "ref.candidates_passed_over_with_allowance_left",
               "ref.voluntary_evictions_out_of_walk_order",
               "ref.binds_on_a_cordoned_or_out_node",
               "ref.tasks_left_on_a_node_that_went_out",
               "ref.nodes_out_while_holding_a_task_before_their_deadline",
               "ref.nodes_not_out_though_empty",
               "ref.forced_flag_disagrees_with_the_plan",
               "ref.returns_not_outFor_after_going_out")
EXACT_ROWS = BUDGET_ROWS + (
    "ref.evictions_not_from_a_leaving_node",
    "ref.log_entries_out_of_order_or_doubled",
    "ref.retried_binds_out_of_queue_order",
    "ref.releases_not_at_their_boundary",
    "ref.drops_while_the_queue_had_room",
    "ref.log_rows_that_do_not_chain",
    "ref.codes_that_disagree_with_the_nodes",
    "ref.retried_binds_not_failed_in_an_earlier_chunk",
    "ref.boundaries_with_the_queue_over_the_buffer",
    "ref.scenario0_differs_from_the_run_without_plans",
    "ref.placed_differs_from_answers_max",
    "ref.log_rows_differ_from_evictions")


def test_the_rehearsal_is_correct(monkeypatch, capsys):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    rc = run.main(["--workload", CELL, "--seed", str(SEED), "--seconds", "1",
                   "--trace", "0", "--rehearse"])
    lines = capsys.readouterr().out.strip().splitlines()
    batches, res = json.loads(lines[-2]), json.loads(lines[-1])
    checks = {n: v for n, v, *_ in batches["checks"]}
    assert rc == 0 and res["correct"] is True and res["attempted"] >= 2
    assert set(res["metrics"]) == {"placements_per_s", "setup_s"}
    assert checks["ref.choices_not_the_references_share"] == 0.0
    assert checks["window.compiles"] == 0
    assert all(checks[r] == 0 for r in EXACT_ROWS)
    # the rehearsal's plans work: every kind of eviction, evictions that had
    # to wait for their budget, re-binds of what a budget let go judged at
    # their turn, nodes that went out
    for row in ("ref.evictions_voluntary", "ref.evictions_forced_at_a_deadline",
                "ref.evictions_forced_by_a_failure",
                "ref.voluntary_evictions_a_boundary_or_more_after_the_cordon",
                "ref.voluntarily_evicted_rebinds_compared",
                "ref.nodes_that_went_out"):
        assert checks[row] > 0, row


@functools.lru_cache(maxsize=1)
def answered():
    """The rehearsal's trace, configuration as run, reference and one batch's
    answers (made once: tier-1 imports these cases as plain functions)."""
    import os

    os.environ["JAX_PLATFORMS"] = "cpu"
    _, _, config, traffic = run.load_cell(CELL)
    trace, config, engine = run.prepare(config, traffic, SEED, True, {})
    answers = engine.answers(engine.batch())
    answers["without_plans"] = answers["without_plans"]()
    return trace, config, traffic, run.load_part(
        "references", config["reference"]), answers, engine.plans


def rows_of(control=None, **tampered):
    trace, config, traffic, reference, answers, _ = answered()
    rows = reference.check(trace, config, {**answers, **tampered}, SEED,
                           traffic["check_samples"], control)
    return {n: (v, lim) for n, v, lim in rows}


def failed(rows):
    return {n for n, (v, lim) in rows.items() if lim is not None and v > lim}


def test_the_sound_answers_breach_no_rule():
    got = rows_of()
    assert not failed(got)
    assert all(got[r][0] == 0 for r in EXACT_ROWS)


@pytest.mark.parametrize("control, row", [
    ("bf16", "ref.choices_not_the_references_share"),
    ("no-budget", "ref.tasks_left_on_a_node_that_went_out"),
    ("budget-never-restored", "ref.voluntary_evictions_over_budget"),
    ("failures-free", "ref.candidates_passed_over_with_allowance_left"),
    ("static-out", "ref.returns_not_outFor_after_going_out")])
def test_a_control_is_not_correct(control, row):
    """The reference in bfloat16 in the program's place; the drain cell's rule
    (everything leaves at its cordon); a re-bind that gives nothing back;
    forced evictions that spend no budget; a node that goes out at its
    deadline only: each reads the program's answers as wrong, by the row of
    its own rule."""
    assert row in failed(rows_of(control))


def test_an_unknown_control_is_refused():
    with pytest.raises(ValueError, match="unknown control"):
        rows_of("no-evict")


def breach(kind):
    """The sound answers made wrong by hand in one rule: ({answer: array},
    the row that holds the rule)."""
    trace, config, _, reference, answers, plans = answered()
    assign, bind = answers["assignments"].copy(), answers["bind_boundary"].copy()
    log, out = answers["eviction_log"].copy(), answers["node_out_at"].copy()
    tasks = trace["tasks"]
    sched = reference.B.schedule(tasks, config["engine"]["waveWidth"],
                                 config["engine"]["chunkWaves"])
    C, N = sched["chunks"], len(trace["nodes"]["cpu"])
    s = 1
    nodes = reference.Nodes(plans[s], C, N, out[s].astype(np.int64))
    rows = np.nonzero(log[s][:, 1] >= 0)[0]
    kinds = log[s][rows, 4]
    vol = rows[kinds == reference.VOLUNTARY]
    if kind == "over_budget":
        # a task that waited for its budget reads as evicted with the first
        late = next(int(r) for r in vol
                    if log[s][r, 0] > nodes.cordon[log[s][r, 2]])
        log[s][late, 0] = nodes.cordon[log[s][late, 2]]
        order = np.lexsort((log[s][rows, 1], log[s][rows, 0]))
        log[s][rows] = log[s][rows][order]
        return {"eviction_log": log}, "ref.voluntary_evictions_over_budget"
    if kind == "passed_over":
        # the first voluntary eviction never happened: its task stays bound
        # where it was, to its release, though its application had room
        r = int(vol[0])
        k, n = int(log[s][r, 1]), int(log[s][r, 2])
        assign[s, k], bind[s, k] = n, log[s][r, 3]
        log[s][r:rows[-1]] = log[s][r + 1:rows[-1] + 1]
        log[s][rows[-1]] = -1
        ev = list(answers["evictions"])
        ev[s] -= 1
        return ({"eviction_log": log, "assignments": assign,
                 "bind_boundary": bind, "evictions": ev},
                "ref.candidates_passed_over_with_allowance_left")
    if kind == "out_of_walk_order":
        a, b = next((int(x), int(y)) for x, y in zip(vol[:-1], vol[1:])
                    if y == x + 1 and log[s][x, 0] == log[s][y, 0])
        log[s][[a, b]] = log[s][[b, a]]
        return {"eviction_log": log}, "ref.voluntary_evictions_out_of_walk_order"
    if kind == "bind_on_a_cordoned_node":
        k = next(int(k) for k in sched["seq"]
                 if assign[s, k] >= 0 and bind[s, k] == -1
                 and nodes.closed[sched["chunk"][k]].any()
                 and not (log[s][:, 1] == k).any())
        assign[s, k] = int(np.nonzero(nodes.closed[sched["chunk"][k]])[0][0])
        return {"assignments": assign}, "ref.binds_on_a_cordoned_or_out_node"
    if kind == "left_behind":
        r = int(rows[kinds == reference.FAILURE][0])
        log[s][r, 0] += 1  # evicted a boundary after its node failed
        return {"eviction_log": log}, "ref.tasks_left_on_a_node_that_went_out"
    if kind == "out_while_holding":
        # a node that waited (for its budgets, or to its deadline) reads as
        # out at its cordon
        n = next(int(n) for n in np.nonzero(nodes.cordon >= 0)[0]
                 if out[s, n] > nodes.cordon[n])
        out[s, n] = nodes.cordon[n]
        return ({"node_out_at": out},
                "ref.nodes_out_while_holding_a_task_before_their_deadline")
    if kind == "not_out_though_empty":
        # a node that went out reads as never out
        n = next(int(n) for n in np.nonzero(nodes.cordon >= 0)[0]
                 if out[s, n] >= 0)
        out[s, n] = -1
        return {"node_out_at": out}, "ref.nodes_not_out_though_empty"
    if kind == "forced_flag":
        log[s][rows[kinds == reference.DEADLINE][0], 4] = reference.VOLUNTARY
        return {"eviction_log": log}, "ref.forced_flag_disagrees_with_the_plan"
    if kind == "back_too_early":
        # a bind onto a node while it is out for its maintenance
        c, n = next((int(c), int(n)) for c, n in np.argwhere(nodes.mwin))
        k = next(int(k) for k in sched["seq"]
                 if sched["chunk"][k] == c and assign[s, k] >= 0
                 and bind[s, k] == -1 and not (log[s][:, 1] == k).any())
        assign[s, k] = n
        return {"assignments": assign}, "ref.returns_not_outFor_after_going_out"
    if kind == "evicted_from_a_node_in_service":
        r = int(rows[0])
        b = int(log[s][r, 0])
        log[s][r, 2] = next(n for n in range(N) if not (
            nodes.fails[b, n] or nodes.dead[b, n] or nodes.asks[b, n]))
        return {"eviction_log": log}, "ref.evictions_not_from_a_leaving_node"
    if kind == "scenario_0_moved":
        plain = {k: v.copy() for k, v in answers["without_plans"].items()}
        k = int(np.nonzero(plain["assignments"] >= 0)[0][-1])
        plain["assignments"][k] ^= 1
        return ({"without_plans": plain},
                "ref.scenario0_differs_from_the_run_without_plans")
    raise ValueError(kind)


@pytest.mark.parametrize("kind", [
    "over_budget", "passed_over", "out_of_walk_order",
    "bind_on_a_cordoned_node", "left_behind", "out_while_holding",
    "not_out_though_empty", "forced_flag", "back_too_early",
    "evicted_from_a_node_in_service", "scenario_0_moved"])
def test_a_breach_made_by_hand_is_seen_by_its_row(kind):
    tampered, row = breach(kind)
    assert rows_of(**tampered)[row][0] > 0


def test_the_plans_are_a_pure_function_of_the_file():
    _, _, config, _ = run.load_cell(CELL)
    zone = np.arange(10000) % config["cluster"]["zones"]
    a = budget_plans.sample(config, zone, 128, 22)
    b = budget_plans.sample(config, zone, 128, 22)
    spec = config["scenarios"]
    assert a[0]["step"] == 0 and not len(a[0]["walk"]) and not a[0]["failures"]
    for i, (p, q) in enumerate(zip(a[1:], b[1:]), 1):
        assert {k: v for k, v in p.items() if k != "walk"} == {
            k: v for k, v in q.items() if k != "walk"}
        np.testing.assert_array_equal(p["walk"], q["walk"])
        for key, among in (("step", "steps"), ("grace", "grace"),
                           ("outFor", "outFor"), ("share", "shares"),
                           ("order", "orders")):
            assert p[key] in spec[among]
        assert spec["firstBoundary"][0] <= p["first"] <= spec["firstBoundary"][1]
        assert sorted(p["walk"].tolist()) == list(range(10000))
        # a node fails again only once it is back; a rack in every fourth
        held = {}
        for down, up, n in p["failures"]:
            assert 1 <= down < 22 and up - down in (1, 2)
            assert all(up < d or u < down for d, u in held.get(n, ()))
            held.setdefault(n, []).append((down, up))
        rack = [f for f in p["failures"][:spec["rackNodes"]]
                if f[:2] == p["failures"][0][:2]]
        if i % spec["rackEvery"] == 0:
            assert len(rack) == spec["rackNodes"]
            assert len({zone[n] for _, _, n in rack}) == 1
    singles = [len(p["failures"]) - (spec["rackNodes"] if i % 4 == 0 else 0)
               for i, p in enumerate(a[1:], 1)]
    assert 12 < np.mean(singles) < 20  # Poisson(16), less what overlapped
    # what a boundary's moves are: the walk's next step, the storm's nodes
    p = a[1]
    moves = budget_plans.moves(p, 22)
    assert len(moves[p["first"]][2]) == p["step"] and not len(moves[p["first"] - 1][2])
    np.testing.assert_array_equal(moves[p["first"] + 1][2],
                                  p["walk"][p["step"]:2 * p["step"]])
    down, up, n = p["failures"][0]
    assert n in moves[down][1] and (up >= 22 or n in moves[up][0])
    np.testing.assert_array_equal(
        budget_plans.max_unavailable(0.001, np.array([10, 999, 1000, 25000])),
        [1, 1, 1, 25])


def test_the_configuration_is_the_drain_cells_with_budgets():
    _, _, config, traffic = run.load_cell(CELL)
    drain = json.loads((BENCH / "configs" / "borg2019-10k-drain.json").read_text())
    for key in ("cluster", "resources", "workload", "scheduler", "engine",
                "deal", "generator", "reduced"):
        assert config[key] == drain[key]
    assert config["architecture"] is None
    assert config["reference"] == "budget_drain_scenarios"
    # no guarantee of the drain cell is weakened: each is here, or is here
    # with "out" widened to "cordoned or out"
    ours = config["guarantees"]
    for g in drain["guarantees"]:
        assert g in ours or g.startswith((
            "no task is bound to a node while it is out",
            "every task bound on a node at the boundary it leaves"))
    assert "no task is bound to a node while it is cordoned or out" in ours
    spec = config["scenarios"]
    for key in ("orders", "outFor", "firstBoundary"):
        assert spec[key] == drain["scenarios"][key]
    assert traffic["scenarios"] == spec["perChip"] == 128
    for row in BUDGET_ROWS:
        assert config["limits"][row.removeprefix("ref.")] == 0


def test_the_configuration_records_what_the_builder_measured():
    """The three targets that make the plan set worth its chip time, on the
    builder's chip run at --seed 0 (``chip_readings_budget_drain.py``)."""
    _, _, config, traffic = run.load_cell(CELL)
    m = config["scenarios"]["measured"]
    assert m["plans"] == traffic["scenarios"] - 1
    assert m["medianShareOfVoluntaryEvictionsAfterTheCordon"] >= 0.20
    assert m["plansWithNoDeadlineForcedEviction"] >= 25
    assert m["plansWithSomeDeadlineForcedEviction"] >= 25
    assert m["evictScale"] == 1  # E and the log fitted: no batch made again
    assert m["scenario0"]["evictions"] == 0
    assert m["candidateTurnsMean"] > m["evictionsMean"] > 0
    assert m["boundariesThatEvict"] <= m["boundaries"] == 22


def test_names_units_and_files():
    b = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = {w["name"]: w for w in b["workloads"]}[CELL]
    conf = {c["name"]: c for c in b["configs"]}[cell["config"]]
    config = json.loads((ROOT / conf["file"]).read_text())
    assert conf["reduced"] == config["reduced"] == ["tasks", "scenarios"]
    assert conf["source"] == config["source"]
    assert len(conf["source"]) <= 200 and len(conf["why"]) <= 200
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(cell["name"]) and NAME.match(cell["traffic"])
    assert (BENCH / "traffic" / f"{cell['traffic']}.json").is_file()
    for part in ("generator", "reference"):
        assert (BENCH / f"{part}s" / f"{config[part]}.py").is_file()
    for key in ("assumed", "guarantees", "limits"):
        assert config[key]
    metrics = {m["name"]: m for m in b["per_layer"]}
    layers = {m["layer"] for m in b["per_layer"] if m["name"] not in METRICS}
    for name in METRICS:
        m = metrics[name]
        assert NAME.match(name) and UNIT.match(m["unit"])
        assert m["workloads"] == [CELL] and m["moves"] == "placements_per_s"
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves",
                          "workloads"}
        assert m["layer"] in layers
        assert (BENCH / "layer_metrics" / f"{name}.py").is_file()
    assert metrics["budget_admit_roofline"]["better"] == "higher"
    # the reference and the plans import nothing of the program
    for path in ("references/budget_drain_scenarios.py", "budget_plans.py"):
        text = (BENCH / path).read_text()
        assert "kubernetes_simulator_tpu" not in text.split('"""', 2)[2]


US = 1000


def made_up_trace():
    """One traced batch, 0..2000 us: the eviction program before two of three
    boundaries (30 and 50 us; of each 10 us under two ops of the admission's
    scope), each boundary's two chunk programs, the host's part of each
    eviction under ``host_events`` (4 and 6 us)."""
    modules = [["jit_whatif_evict(3)", 90 * US, 30 * US],
               ["jit_per_scenario_retry(7)", 130 * US, 300 * US],
               ["jit_per_scenario_arrivals(9)", 430 * US, 100 * US],
               ["jit_whatif_evict(3)", 600 * US, 50 * US],
               ["jit_per_scenario_retry(7)", 660 * US, 200 * US],
               ["jit_per_scenario_arrivals(9)", 860 * US, 100 * US],
               ["jit_per_scenario_retry(7)", 1100 * US, 100 * US],
               ["jit_per_scenario_arrivals(9)", 1200 * US, 100 * US]]
    ops = [[f"%fusion.{i} = s32[]{{:T(128)}} fusion(%a)", s, d]
           for i, (_, s, d) in enumerate(modules)]
    for at in (92, 610):
        ops += [["%admit.1 = s32[8]{0} fusion(%a)", at * US, 4 * US],
                ["%admit.2 = s32[8]{0} fusion(%b)", (at + 5) * US, 6 * US]]
    host = [["bench:batch:0", 0, 2000 * US], ["whatif_run:1", 10 * US, 1900 * US],
            ["host_events", 80 * US, 4 * US], ["host_events", 590 * US, 6 * US],
            ["handback", 1600 * US, 200 * US]]
    events = {"devices": [{"modules": modules, "ops": sorted(ops, key=lambda e: e[1]),
                           "dropped": []}],
              "host": sorted(host, key=lambda e: e[1])}
    events["program_span_events"] = [[n, s, d, 1, {}] for n, s, d in events["host"]]
    return events


def read_all(events, monkeypatch, nodes=10000, table=True):
    from layer_metrics import _stages

    tables = {"jit_whatif_evict": {"admit.1": "ksim.evict/Budget",
                                   "admit.2": "ksim.evict/Budget",
                                   "fusion.0": "ksim.evict"}}
    monkeypatch.setattr(_stages, "stage_tables",
                        lambda: tables if table else None)
    ctx = {"trace": trace_reduce.Reduced(events), "device_kind": "TPU v5 lite",
           "program_span_events": events.get("program_span_events"),
           "shape": {"scenarios_per_chip": 128, "nodes": nodes, "resources": 3,
                     "wave_width": 8, "chunk_waves": 768 + 600, "planes": 2}}
    return {m: run.load_part("layer_metrics", m).read(ctx) for m in METRICS}


def test_the_new_readers_on_a_made_up_trace(monkeypatch):
    got = read_all(made_up_trace(), monkeypatch)
    assert got["budget_evict_ms_per_boundary"] == pytest.approx(0.040)
    # the merged op events cover the eight programs' 980 us
    assert got["budget_evict_share"] == pytest.approx(100 * 80 / 980)
    assert got["budget_admit_ms_per_boundary"] == pytest.approx(0.010)
    # one run of the pass program a boundary, not one of every chunk program
    assert got["budget_retry_ms_per_boundary"] == pytest.approx(0.200)
    assert got["budget_host_events_ms_per_boundary"] == pytest.approx(0.005)
    assert got["budget_handback_ms_per_batch"] == pytest.approx(0.200)
    assert got["budget_host_untraced_share"] == pytest.approx(100 * (1 - 210 / 1900))
    _, _, config, _ = run.load_cell(CELL)
    m = config["scenarios"]["measured"]
    if "candidateTurnsMean" in m:
        least = roofline_budget_drain.admit_min_ms(
            "TPU v5 lite", scenarios=128,
            candidates=m["candidateTurnsMean"] / m["boundariesThatEvict"],
            apps=48)
        assert got["budget_admit_roofline"] == pytest.approx(100 * least / 0.010)
        assert 0 < least < 0.1  # kilobytes a plan: microseconds at HBM speed
    else:
        assert got["budget_admit_roofline"] is None
    # where the device's buffer overflowed inside the batch the window ends
    # there: the device-side metrics read what is left, the host-side ones the
    # whole batch
    cut = made_up_trace()
    cut["devices"][0]["dropped"] = [700 * US]
    short = read_all(cut, monkeypatch)
    assert short["budget_evict_ms_per_boundary"] == pytest.approx(0.040)
    assert short["budget_retry_ms_per_boundary"] == pytest.approx(0.300)
    for m in ("budget_host_events_ms_per_boundary", "budget_handback_ms_per_batch",
              "budget_host_untraced_share"):
        assert short[m] == got[m]


def test_the_new_readers_read_nothing_from_a_tree_without_the_program(monkeypatch):
    """The parent's tree has no admission scope (its eviction program's table
    names ``ksim.evict`` alone), a tree before PR 45 no eviction program and
    no ``host_events`` span, and one that exports no span names no span at
    all: None, no raise."""
    from kubernetes_simulator_tpu.sim import telemetry

    from layer_metrics import _stages

    events = made_up_trace()
    got = read_all(events, monkeypatch, table=False)
    assert got["budget_admit_ms_per_boundary"] is None
    assert got["budget_admit_roofline"] is None
    assert got["budget_evict_ms_per_boundary"] == pytest.approx(0.040)
    monkeypatch.setattr(_stages, "stage_tables", lambda: {
        "jit_whatif_evict": {"fusion.0": "ksim.evict"}})
    ctx = {"trace": trace_reduce.Reduced(events), "device_kind": "TPU v5 lite",
           "shape": {"scenarios_per_chip": 128, "nodes": 10000}}
    assert run.load_part(
        "layer_metrics", "budget_admit_ms_per_boundary").read(ctx) is None
    events["devices"][0]["modules"] = [
        m for m in events["devices"][0]["modules"]
        if "evict" not in m[0] and "retry" not in m[0]]
    events["program_span_events"] = [
        e for e in events["program_span_events"] if e[0] != "host_events"]
    got = read_all(events, monkeypatch)
    assert [m for m in METRICS if got[m] is None] == [
        m for m in METRICS if m not in ("budget_handback_ms_per_batch",
                                        "budget_host_untraced_share")]
    monkeypatch.delattr(telemetry, "HOST_SPAN_NAMES")
    assert read_all(events, monkeypatch) == dict.fromkeys(METRICS)


@pytest.mark.parametrize("fault, says", [
    ({"release_path": "host"}, "not on the device-release path"),
    ({"kube": True}, "did not take the plans and their budgets"),
    ({"_events_dev": False}, "did not take the plans and their budgets"),
    ({"_budget_on": False}, "did not take the plans and their budgets"),
    ({"chunk_waves": 8}, "a chunk of 8 waves"),
    ({"retry_buffer": 8}, "a retry buffer of 8"),
])
def test_the_adapter_refuses_another_program_before_any_batch(
        monkeypatch, fault, says):
    import kubernetes_simulator_tpu.sim.whatif as program

    class Other:
        release_path, chunk_waves, retry_buffer = "device", 16, 128
        kube, _events_dev, _budget_on = False, True, True

        def __init__(self, *a, **kw):
            for k, v in fault.items():
                setattr(self, k, v)

    _, _, config, traffic = run.load_cell(CELL)
    generator = run.load_part("generators", config["generator"])
    trace = generator.generate(config, 64, 256, 1)
    ec, ep = generator.to_program(trace, config)
    monkeypatch.setattr(program, "WhatIfEngine", Other)
    adapter = run.load_part("engines", traffic["engine"])
    with pytest.raises(RuntimeError, match=says):
        adapter.Engine(ec, ep, config, traffic, 16)


def test_a_result_without_the_fourth_answer_is_refused():
    adapter = run.load_part("engines", "whatif_budget_drain")
    eng = object.__new__(adapter.Engine)

    class Result:
        eviction_log = np.zeros((1, 1, 5), np.int32)
        node_out_at = None

    with pytest.raises(RuntimeError, match="no node_out_at"):
        eng.answers(Result())
    Result.node_out_at = np.zeros((1, 4), np.int32)
    Result.eviction_log = np.zeros((1, 1, 4), np.int32)
    eng._first = {}
    with pytest.raises(RuntimeError, match="carry no kind"):
        eng.answers(Result())
