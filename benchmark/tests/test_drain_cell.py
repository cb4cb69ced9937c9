"""The drained Borg cell's own tests (``borg10k-drain128``), on the CPU, run by
hand like their siblings:

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_drain_cell.py -q
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

import drain_plans  # noqa: E402
import roofline_drain  # noqa: E402
import run  # noqa: E402
import trace_reduce  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELL = "borg10k-drain128"
SEED = 2147483999
OWN_METRICS = ("drain_evict_ms_per_boundary", "drain_evict_share",
               "drain_evict_roofline", "drain_host_events_ms_per_boundary",
               "drain_handback_ms_per_batch", "drain_host_untraced_share")
BORROWED = {"drain_retry_ms_per_boundary": "backlog_retry_ms_per_boundary",
            "drain_release_ms_per_boundary": "backlog_release_ms_per_boundary",
            "drain_handback_ms_per_batch": "host_handback_ms_per_batch",
            "drain_host_untraced_share": "host_untraced_share"}
EXACT_ROWS = ("ref.binds_on_a_node_while_it_is_out",
              "ref.tasks_left_on_a_node_that_went_out",
              "ref.evictions_not_from_a_leaving_node",
              "ref.log_entries_out_of_order_or_doubled",
              "ref.retried_binds_out_of_queue_order",
              "ref.releases_not_at_their_boundary",
              "ref.drops_while_the_queue_had_room",
              "ref.codes_that_disagree_with_the_nodes",
              "ref.retried_binds_not_failed_in_an_earlier_chunk",
              "ref.boundaries_with_the_queue_over_the_buffer",
              "ref.scenario0_differs_from_the_run_without_plans",
              "ref.placed_differs_from_answers_max",
              "ref.log_rows_differ_from_evictions")


def rehearse(monkeypatch, capsys, *extra):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    rc = run.main(["--workload", CELL, "--seed", str(SEED), "--seconds",
                   "1", "--trace", "0", "--rehearse", *extra])
    lines = capsys.readouterr().out.strip().splitlines()
    batches, res = json.loads(lines[-2]), json.loads(lines[-1])
    return rc, {n: v for n, v, *_ in batches["checks"]}, res


def test_the_rehearsal_is_correct(monkeypatch, capsys):
    rc, checks, res = rehearse(monkeypatch, capsys)
    assert rc == 0 and res["correct"] is True and res["attempted"] >= 2
    assert set(res["metrics"]) == {"placements_per_s", "setup_s"}
    assert checks["ref.choices_not_the_references_share"] == 0.0
    assert checks["window.compiles"] == 0
    assert all(checks[r] == 0 for r in EXACT_ROWS)
    # the rehearsal's plans work: evictions, re-binds of the evicted judged
    # at their turn, binds onto nodes just back, drops at a full buffer
    assert checks["ref.evictions_handed_back"] > 1000
    assert checks["ref.evicted_rebinds_compared"] > 100
    assert checks["ref.binds_onto_a_node_just_back_in_the_answers"] > 100
    assert checks["ref.tasks_dropped_at_a_full_buffer"] > 0
    assert checks["ref.no_node_samples_compared"] > 100


@pytest.mark.parametrize("control, row", [
    ("bf16", None),
    ("no-evict", "ref.tasks_left_on_a_node_that_went_out"),
    ("no-return", "ref.binds_on_a_node_while_it_is_out"),
    ("evicted-last", "ref.retried_binds_out_of_queue_order")])
def test_a_control_is_not_correct(monkeypatch, capsys, control, row):
    """The reference in bfloat16 in the program's place; the reference that
    lets the nodes go and their tasks stay; the one in which a node never
    comes back; the one that queues the evicted behind everything: each reads
    the program's answers as wrong, by the share and by the row of its rule."""
    rc, checks, res = rehearse(monkeypatch, capsys, "--control", control)
    assert rc == 0 and res["correct"] is False
    assert checks["ref.choices_not_the_references_share"] > 0.05
    if row:
        assert checks[row] > 0


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


def rows_of(**tampered):
    trace, config, traffic, reference, answers, _ = answered()
    rows = reference.check(trace, config, {**answers, **tampered}, SEED,
                           traffic["check_samples"])
    return {n: v for n, v, _ in rows}


def breach(kind):
    """The sound answers made wrong by hand in one rule: ({answer: array},
    the row that holds the rule)."""
    trace, config, _, reference, answers, plans = answered()
    assign, bind = answers["assignments"].copy(), answers["bind_boundary"].copy()
    log = answers["eviction_log"].copy()
    tasks = trace["tasks"]
    sched = reference.B.schedule(tasks, config["engine"]["waveWidth"],
                                 config["engine"]["chunkWaves"])
    C, N = sched["chunks"], len(trace["nodes"]["cpu"])
    s = 1
    out = drain_plans.out_at(plans[s], C, N)
    rows = np.nonzero(log[s][:, 1] >= 0)[0]
    if kind == "bind_on_an_out_node":
        k = next(int(k) for k in sched["seq"]
                 if assign[s, k] >= 0 and bind[s, k] == -1
                 and out[sched["chunk"][k]].any()
                 and not (log[s][:, 1] == k).any())
        assign[s, k] = int(np.nonzero(out[sched["chunk"][k]])[0][0])
        return {"assignments": assign}, "ref.binds_on_a_node_while_it_is_out"
    if kind == "left_behind":
        log[s][rows[0], 0] += 1  # evicted a boundary after its node went
        return {"eviction_log": log}, "ref.tasks_left_on_a_node_that_went_out"
    if kind == "evicted_from_another_node":
        leaving = set(drain_plans.moves(plans[s], C)[log[s][rows[0], 0]][0].tolist())
        log[s][rows[0], 2] = next(n for n in range(N) if n not in leaving)
        return {"eviction_log": log}, "ref.evictions_not_from_a_leaving_node"
    if kind == "rows_swapped":
        log[s][[rows[0], rows[1]]] = log[s][[rows[1], rows[0]]]
        return {"eviction_log": log}, "ref.log_entries_out_of_order_or_doubled"
    if kind == "row_doubled":
        log[s][rows[1]] = log[s][rows[0]]
        return {"eviction_log": log}, "ref.log_entries_out_of_order_or_doubled"
    if kind == "dropped_with_room":
        # a task queued to the end read as dropped (or, where no plan leaves
        # one queued, a dropped one read as queued: the queue then passes
        # the buffer where the rule dropped it)
        queued, dropped = np.argwhere(bind == -2), np.argwhere(bind == -3)
        (s, k), code = (queued[0], -3) if len(queued) else (dropped[0], -2)
        bind[s, k] = code
        return {"bind_boundary": bind}, "ref.drops_while_the_queue_had_room"
    if kind == "code_of_a_stranded_gang_member":
        k = int(np.nonzero((bind[s] == -4))[0][0])
        bind[s, k] = -5  # no row of the log evicted it
        return {"bind_boundary": bind}, "ref.codes_that_disagree_with_the_nodes"
    if kind == "scenario_0_moved":
        plain = {k: v.copy() for k, v in answers["without_plans"].items()}
        k = int(np.nonzero(plain["assignments"] >= 0)[0][-1])
        plain["assignments"][k] ^= 1
        return ({"without_plans": plain},
                "ref.scenario0_differs_from_the_run_without_plans")
    if kind == "a_row_less_than_counted":
        log[s][rows[-1]] = -1
        return {"eviction_log": log}, "ref.log_rows_differ_from_evictions"
    raise ValueError(kind)


def test_the_sound_answers_breach_no_rule():
    got = rows_of()
    assert all(got[r] == 0 for r in EXACT_ROWS)
    assert got["ref.choices_not_the_references_share"] == 0.0


@pytest.mark.parametrize("kind", [
    "bind_on_an_out_node", "left_behind", "evicted_from_another_node",
    "rows_swapped", "row_doubled", "dropped_with_room",
    "code_of_a_stranded_gang_member", "scenario_0_moved",
    "a_row_less_than_counted"])
def test_a_breach_made_by_hand_is_seen_by_its_row(kind):
    tampered, row = breach(kind)
    assert rows_of(**tampered)[row] > 0


def test_the_plans_are_a_pure_function_of_the_file():
    _, _, config, _ = run.load_cell(CELL)
    zone = np.arange(10000) % config["cluster"]["zones"]
    a, b = drain_plans.sample(config, zone, 128), drain_plans.sample(config, zone, 128)
    spec = config["scenarios"]
    assert a[0]["step"] == 0 and len(a[0]["walk"]) == 0
    for p, q in zip(a[1:], b[1:]):
        assert {k: v for k, v in p.items() if k != "walk"} == {
            k: v for k, v in q.items() if k != "walk"}
        np.testing.assert_array_equal(p["walk"], q["walk"])
        assert p["step"] in spec["steps"] and p["outFor"] in spec["outFor"]
        assert p["order"] in spec["orders"]
        assert spec["firstBoundary"][0] <= p["first"] <= spec["firstBoundary"][1]
        assert sorted(p["walk"].tolist()) == list(range(10000))
    assert {p["step"] for p in a[1:]} == set(spec["steps"])
    assert {(p["order"], p["outFor"]) for p in a[1:]} == {
        (o, f) for o in spec["orders"] for f in spec["outFor"]}
    # a zone walk stays in one zone until it is through; a striped one
    # takes the zones in turn
    zonal = next(p for p in a[1:] if p["order"] == "zone")
    assert (np.diff(zone[zonal["walk"]]) != 0).sum() <= 8
    striped = next(p for p in a[1:] if p["order"] == "striped")
    assert len(set(zone[striped["walk"][:8]].tolist())) == 8
    # what leaves at b is back at b + outFor, and out in between
    p = a[1]
    moves = drain_plans.moves(p, 22)
    out = drain_plans.out_at(p, 22, 10000)
    b = p["first"]
    assert len(moves[b][0]) == p["step"] and not len(moves[b - 1][0])
    np.testing.assert_array_equal(moves[b + p["outFor"]][1], moves[b][0])
    assert out[b][moves[b][0]].all() and not out[b + p["outFor"]][moves[b][0]].any()
    assert out.sum(axis=1).max() <= p["step"] * p["outFor"]


def test_every_seed_gets_the_same_work_and_deals_its_tail():
    """Up to arrival slot ``deal.from`` every seed gets ``baseSeed``'s own
    deal (the backlog generator's at that seed); behind it the same multiset
    in another order."""
    _, _, config, _ = run.load_cell(CELL)
    generator = run.load_part("generators", config["generator"])
    backlog = run.load_part("generators", "borg_backlog")
    a = generator.generate(config, 64, 1024, 1)
    b = generator.generate(config, 64, 1024, 2**31 + 5)
    base = backlog.generate(config, 64, 1024, config["workload"]["baseSeed"])
    R = a["resident"]
    first = R + config["deal"]["from"] * 1024 // config["deal"]["of"]
    assert R == b["resident"] == base["resident"] and first == R + 896
    for k in a["tasks"]:
        np.testing.assert_array_equal(a["tasks"][k][:first], base["tasks"][k][:first])
        np.testing.assert_array_equal(b["tasks"][k][:first], base["tasks"][k][:first])
    for k in ("arrival", "group_id", "bound_node"):
        np.testing.assert_array_equal(a["tasks"][k], b["tasks"][k])
    for k in ("cpu", "mem", "priority", "duration"):
        assert (a["tasks"][k][first:] != b["tasks"][k][first:]).any()
        np.testing.assert_array_equal(np.sort(a["tasks"][k][first:]),
                                      np.sort(base["tasks"][k][first:]))
    for k in ("cpu", "mem", "zone", "tainted"):
        np.testing.assert_array_equal(a["nodes"][k], base["nodes"][k])


def test_the_configuration_records_what_the_builder_measured():
    _, _, config, traffic = run.load_cell(CELL)
    backlog = json.loads((BENCH / "configs" / "borg2019-10k-backlog.json").read_text())
    for key in ("cluster", "resources", "workload", "scheduler"):
        assert config[key] == backlog[key]
    assert config["engine"] == {**backlog["engine"], "retryBuffer": 8192}
    assert config["architecture"] is None
    measured = config["scenarios"]["measured"]
    assert measured["plans"] == traffic["scenarios"] - 1
    # the three targets that make the cell worth its chip time
    assert measured["medianPlan"]["evictions"] >= 15000
    assert measured["reboundShareOfTheEvicted"] >= 0.90
    assert 10 <= measured["plansAPlannerWouldReject"] <= 40
    assert measured["scenario0"]["evictions"] == 0
    assert measured["releaseLeakedMax"] == 0


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
    for name in OWN_METRICS + tuple(BORROWED):
        m = metrics[name]
        assert NAME.match(name) and UNIT.match(m["unit"])
        assert m["workloads"] == [CELL] and m["moves"] == "placements_per_s"
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves",
                          "workloads"}
        assert (BENCH / "layer_metrics" / f"{name}.py").is_file()
    for name, lender in BORROWED.items():
        for key in ("unit", "better", "source", "layer"):
            assert metrics[name][key] == metrics[lender][key]
    assert metrics["drain_evict_roofline"]["better"] == "higher"
    # the reference imports nothing of the program
    text = (BENCH / "references" / "drain_scenarios.py").read_text()
    assert "kubernetes_simulator_tpu" not in text.split('"""', 2)[2]


US = 1000


def made_up_trace():
    """One traced batch, 0..2000 us: the eviction program before two of three
    chunk calls (30 and 50 us), the host's part of each under ``host_events``
    (4 and 6 us)."""
    modules = [["jit_whatif_evict(3)", 90 * US, 30 * US],
               ["jit_per_scenario_retry(7)", 130 * US, 400 * US],
               ["jit_whatif_evict(3)", 600 * US, 50 * US],
               ["jit_per_scenario_retry(7)", 660 * US, 400 * US],
               ["jit_per_scenario_retry(7)", 1100 * US, 400 * US]]
    ops = [[f"%fusion.{i} = s32[]{{:T(128)}} fusion(%a)", s, d]
           for i, (_, s, d) in enumerate(modules)]
    host = [["bench:batch:0", 0, 2000 * US], ["whatif_run:1", 10 * US, 1900 * US],
            ["host_events", 80 * US, 4 * US], ["host_events", 590 * US, 6 * US],
            ["handback", 1600 * US, 200 * US]]
    events = {"devices": [{"modules": modules, "ops": ops, "dropped": []}],
              "host": sorted(host, key=lambda e: e[1])}
    events["program_span_events"] = [[n, s, d, 1, {}] for n, s, d in events["host"]]
    return events


def read_all(events, nodes=10000):
    ctx = {"trace": trace_reduce.Reduced(events), "device_kind": "TPU v5 lite",
           "program_span_events": events.get("program_span_events"),
           "shape": {"scenarios_per_chip": 128, "nodes": nodes, "resources": 3,
                     "wave_width": 8, "chunk_waves": 768 + 1024, "planes": 2}}
    return {m: run.load_part("layer_metrics", m).read(ctx) for m in OWN_METRICS}


def test_the_new_readers_on_a_made_up_trace():
    got = read_all(made_up_trace())
    assert got["drain_evict_ms_per_boundary"] == pytest.approx(0.040)
    assert got["drain_evict_share"] == pytest.approx(100 * 80 / 1280)
    assert got["drain_host_events_ms_per_boundary"] == pytest.approx(0.005)
    assert got["drain_handback_ms_per_batch"] == pytest.approx(0.200)
    # the call's spans cover 4 + 6 + 200 us of its root's 1,900
    assert got["drain_host_untraced_share"] == pytest.approx(100 * (1 - 210 / 1900))
    # where the device's buffer overflowed inside the batch the window ends
    # there: the device-side metrics read what is left, the host-side ones
    # the whole batch
    cut = made_up_trace()
    cut["devices"][0]["dropped"] = [700 * US]
    short = read_all(cut)
    assert short["drain_evict_ms_per_boundary"] == pytest.approx(0.040)
    for m in ("drain_host_events_ms_per_boundary", "drain_handback_ms_per_batch",
              "drain_host_untraced_share"):
        assert short[m] == got[m]
    _, _, config, _ = run.load_cell(CELL)
    m = config["scenarios"]["measured"]
    least = roofline_drain.evict_min_ms(
        "TPU v5 lite", scenarios=128, nodes=10000, resources=3,
        tasks=m["tasks"], buffer=8192, boundaries=m["boundaries"],
        victims=m["evictionsMean"] / m["boundariesThatEvict"], planes=2)
    assert got["drain_evict_roofline"] == pytest.approx(100 * least / 0.040)
    # what an eviction has to touch, whatever implements it: the places, the
    # record, the planes; megabytes a scenario, far under a millisecond each
    assert 128 * (4 * m["tasks"] + 8 * 22 * 8192) / 819e9 * 1e3 < least < 5.0


def test_the_new_readers_read_nothing_from_a_tree_without_the_program(monkeypatch):
    """The parent's tree runs no eviction program and writes no
    ``host_events`` span: None, no raise (the spans it does write are read
    as in the accepted cells); a tree that exports no span names reads
    nothing at all; nor does the roofline at another cell's size."""
    from kubernetes_simulator_tpu.sim import telemetry

    events = made_up_trace()
    events["devices"][0]["modules"] = [
        m for m in events["devices"][0]["modules"] if "evict" not in m[0]]
    events["program_span_events"] = [
        e for e in events["program_span_events"] if e[0] != "host_events"]
    got = read_all(events)
    assert [m for m in OWN_METRICS if got[m] is None] == list(OWN_METRICS[:4])
    assert got["drain_handback_ms_per_batch"] == pytest.approx(0.200)
    monkeypatch.delattr(telemetry, "HOST_SPAN_NAMES")
    assert read_all(events) == dict.fromkeys(OWN_METRICS)
    monkeypatch.undo()
    assert read_all(made_up_trace(), nodes=64)["drain_evict_roofline"] is None


@pytest.mark.parametrize("fault, says", [
    ({"release_path": "host"}, "not on the device-release path"),
    ({"kube": True}, "did not take the plans as device events"),
    ({"_events_dev": False}, "did not take the plans as device events"),
    ({"chunk_waves": 8}, "a chunk of 8 waves"),
    ({"retry_buffer": 8}, "a retry buffer of 8"),
])
def test_the_adapter_refuses_another_program_before_any_batch(
        monkeypatch, fault, says):
    import kubernetes_simulator_tpu.sim.whatif as program

    class Other:
        release_path, chunk_waves, retry_buffer = "device", 16, 128
        kube, _events_dev = False, True

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


def test_a_result_without_the_log_is_refused():
    adapter = run.load_part("engines", "whatif_drain")
    eng = object.__new__(adapter.Engine)

    class Result:
        eviction_log = None

    with pytest.raises(RuntimeError, match="no eviction log"):
        eng.answers(Result())
