"""The full Borg cell's own tests (``borg10k-backlog128``), on the CPU, run by
hand like their siblings:

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_backlog_cell.py -q
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

import roofline  # noqa: E402
import roofline_backlog  # noqa: E402
import run  # noqa: E402
import trace_reduce  # noqa: E402
from kubernetes_simulator_tpu.utils import profiling  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELL = "borg10k-backlog128"
SEED = 2147483999
OWN_METRICS = ("backlog_retry_ms_per_boundary", "backlog_retry_share",
               "backlog_retry_roofline", "backlog_release_ms_per_boundary",
               "backlog_handback_ms_per_batch")
BORROWED = ("idle_unattributed_share", "host_untraced_share",
            "host_stage_ms_per_batch", "host_dispatch_ms_per_batch",
            "host_gather_ms_per_batch")
NEW_METRICS = OWN_METRICS + tuple("backlog_" + m for m in BORROWED)


def rehearse(monkeypatch, capsys, *extra):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    rc = run.main(["--workload", CELL, "--seed", str(SEED), "--seconds",
                   "1", "--trace", "0", "--rehearse", *extra])
    lines = capsys.readouterr().out.strip().splitlines()
    batches, res = json.loads(lines[-2]), json.loads(lines[-1])
    return rc, {n: v for n, v, *_ in batches["checks"]}, res


EXACT_ROWS = ("ref.retried_binds_out_of_queue_order",
              "ref.releases_not_at_their_boundary",
              "ref.codes_that_disagree_with_the_nodes",
              "ref.retried_binds_not_failed_in_an_earlier_chunk",
              "ref.boundaries_with_the_queue_over_the_buffer",
              "ref.drops_not_the_newest_at_a_full_buffer",
              "ref.placements_on_down_or_injected_taint_nodes",
              "ref.placed_differs_from_answers_max")


def test_the_rehearsal_is_correct(monkeypatch, capsys):
    rc, checks, res = rehearse(monkeypatch, capsys)
    assert rc == 0 and res["correct"] is True and res["attempted"] >= 2
    assert set(res["metrics"]) == {"placements_per_s", "setup_s"}
    assert checks["ref.choices_not_the_references_share"] == 0.0
    assert checks["window.compiles"] == 0
    assert all(checks[r] == 0 for r in EXACT_ROWS)
    # the rehearsal's queue works: binds by later passes, and the small
    # buffer overflows in some scenario, so the drop path is inside correct
    assert checks["ref.retried_binds_handed_back"] > 100
    assert checks["ref.tasks_dropped_at_a_full_buffer"] > 0
    assert checks["ref.retried_binds_compared_share"] >= 0.25
    assert checks["ref.no_node_samples_compared"] > 100


@pytest.mark.parametrize("control, least", [
    ("bf16", 0.1), ("unperturbed", 0.1), ("arrival_state", 0.2)])
def test_a_control_is_not_correct(monkeypatch, capsys, control, least):
    """The reference in bfloat16 in the program's place; scenario 0's table in
    every scenario's place; and a re-tried bind judged on the state at its
    arrival, which only fails if the check sees ``bind_boundary``."""
    rc, checks, res = rehearse(monkeypatch, capsys, "--control", control)
    assert rc == 0 and res["correct"] is False
    assert checks["ref.choices_not_the_references_share"] > least
    # nothing else fails: the share is the limit that catches it
    assert all(checks[r] == 0 for r in EXACT_ROWS)


@functools.lru_cache(maxsize=1)
def answered():
    """The rehearsal's trace, configuration as run, reference and one
    batch's answers (made once: tier-1 imports these cases as plain
    functions, tests/test_benchmark_cases.py, so no fixture)."""
    import os

    os.environ["JAX_PLATFORMS"] = "cpu"
    _, _, config, traffic = run.load_cell(CELL)
    trace, config, engine = run.prepare(config, traffic, SEED, True, {})
    answers = engine.answers(engine.batch())
    return trace, config, traffic, run.load_part(
        "references", config["reference"]), answers


def rows_of(**tampered):
    trace, config, traffic, reference, answers = answered()
    rows = reference.check(trace, config, {**answers, **tampered}, SEED,
                           traffic["check_samples"])
    return {n: v for n, v, _ in rows}


def test_the_rows_over_every_task_see_a_queue_that_breaks_its_rules():
    """Answers made wrong by hand, one rule at a time; each is seen by the row
    that holds that rule (and the sound answers by none)."""
    trace, config, _, reference, answers = answered()
    assert all(rows_of()[r] == 0 for r in EXACT_ROWS)
    tasks, bind = trace["tasks"], answers["bind_boundary"]
    assign = answers["assignments"]
    sched = reference.schedule(tasks, config["engine"]["waveWidth"],
                               config["engine"]["chunkWaves"])
    s = int(np.argmax((bind == -3).sum(axis=1)))  # a scenario that drops
    # a re-tried bind handed back at the boundary of its own arrival chunk
    b2 = bind.copy()
    k = int(np.nonzero(bind[s] >= 0)[0][0])
    b2[s, k] = sched["chunk"][k]
    assert rows_of(bind_boundary=b2)[
        "ref.retried_binds_not_failed_in_an_earlier_chunk"] == 1
    # a dropped task handed back as still queued: the queue passes the buffer
    b2 = bind.copy()
    b2[s, np.nonzero(bind[s] == -3)[0][:1]] = -2
    got = rows_of(bind_boundary=b2)
    assert got["ref.drops_not_the_newest_at_a_full_buffer"] > 0
    # a task with a node and the code of one without
    b2 = bind.copy()
    b2[s, k] = -2
    assert rows_of(bind_boundary=b2)[
        "ref.codes_that_disagree_with_the_nodes"] == 1


def test_a_walk_out_of_queue_order_is_seen():
    """Four tasks of one wave each (chunks of one wave). Tasks 1 and 2 fail at
    their arrival and wait; the pass of boundary 3 binds task 2 and leaves task
    1 queued until boundary 4. Task 1 asks no more than task 2, so the node
    that took task 2 had room for task 1 at its turn: if task 1 stands before
    task 2 in the walk (a higher priority, or the same and the earlier
    arrival) the walk was out of order; if task 2 has the higher priority it
    was not."""
    reference = run.load_part("references", "backlog_scenarios")
    base = {
        "arrival": np.arange(5, dtype=np.float64), "cpu": np.ones(5, np.float32),
        "mem": np.ones(5, np.float32), "tolerates": np.zeros(5, np.int32),
        "group_id": np.full(5, -1, np.int32), "bound_node": np.full(5, -1, np.int32),
        "duration": np.full(5, 100.0, np.float32),
    }
    assign = np.array([0, 0, 0, 0, 0], np.int64)
    bind = np.array([-1, 4, 3, -1, -1], np.int64)
    for prio, out_of_order in (([0, 100, 0, 0, 0], 1), ([0, 0, 0, 0, 0], 1),
                               ([0, 0, 100, 0, 0], 0)):
        tasks = {**base, "priority": np.asarray(prio, np.int32)}
        sched = reference.schedule(tasks, 1, 1)
        held = reference.Held(tasks, sched, assign, bind)
        rows = reference.queue_rows(tasks, sched, held, tasks["group_id"], 8)
        assert rows == (0, 0, 0, 0, out_of_order), prio
    # asking more than the task bound behind it, it may have fitted nowhere
    tasks = {**base, "priority": np.zeros(5, np.int32), "cpu": base["cpu"].copy()}
    tasks["cpu"][1] = 2.0
    sched = reference.schedule(tasks, 1, 1)
    held = reference.Held(tasks, sched, assign, bind)
    assert reference.queue_rows(tasks, sched, held, tasks["group_id"], 8)[4] == 0


def test_a_task_is_judged_only_at_a_pass_that_tried_it():
    """A task that failed in the LAST chunk joined the queue behind the last
    pass and reads -2 untried: it is sampled at its arrival, never at a turn
    in the last pass (at the cell's size such samples read 100 points short:
    the chip run that found it is in PERF.md section 6). Three tasks of one
    wave each: task 0 binds, tasks 1 and 2 wait to the end; only task 1 was
    in the queue when the last pass (boundary 2) ran."""
    reference = run.load_part("references", "backlog_scenarios")
    tasks = {
        "arrival": np.arange(3, dtype=np.float64), "cpu": np.ones(3, np.float32),
        "mem": np.ones(3, np.float32), "tolerates": np.zeros(3, np.int32),
        "group_id": np.full(3, -1, np.int32), "bound_node": np.full(3, -1, np.int32),
        "duration": np.full(3, 100.0, np.float32), "priority": np.zeros(3, np.int32),
    }
    sched = reference.schedule(tasks, 1, 1)
    held = reference.Held(tasks, sched, np.array([0, -1, -1]), np.array([-1, -2, -2]))
    drawn, behind = reference.draw(np.random.default_rng(0), 64, sched, held,
                                   np.ones(3, bool), tasks["group_id"])
    assert behind == 0 and sched["chunks"] == 3
    assert sorted(set(drawn), key=str) == sorted(
        {(0, None), (1, None), (2, None), (1, 2)}, key=str)


def test_a_release_at_another_boundary_is_seen():
    """The same answers under a rule that holds every re-tried bind one
    boundary less (as a program that released early would have placed): binds
    land on nodes the rule still holds full; and under the real rule none."""
    trace, config, _, reference, answers = answered()
    tasks = trace["tasks"]
    sched = reference.schedule(tasks, config["engine"]["waveWidth"],
                               config["engine"]["chunkWaves"])
    import whatif_scenarios

    S = len(answers["placed"])
    scen = whatif_scenarios.sample(config, len(trace["nodes"]["cpu"]), S)
    sound = late = 0
    for s in range(S):
        own = reference.GS.node_table(trace["nodes"], scen[s])
        held = reference.Held(tasks, sched, answers["assignments"][s].astype(np.int64),
                              answers["bind_boundary"][s].astype(np.int64))
        sound += reference.over_allocatable(own, tasks, sched, held)
        # every bound task held three boundaries longer than the rule says
        held.until = np.where(held.bound, held.until + 3, held.until)
        late += reference.over_allocatable(own, tasks, sched, held)
    assert sound == 0 and late > 0


def test_every_seed_gets_the_same_work_in_another_deal():
    _, _, config, _ = run.load_cell(CELL)
    generator = run.load_part("generators", config["generator"])
    a = generator.generate(config, 64, 1024, 1)
    b = generator.generate(config, 64, 1024, 2**31 + 5)
    R = a["resident"]
    assert R == b["resident"] > 0
    for k in ("cpu", "mem", "zone", "tainted"):
        np.testing.assert_array_equal(a["nodes"][k], b["nodes"][k])
    for k in a["tasks"]:  # the residents are the same, column for column
        np.testing.assert_array_equal(a["tasks"][k][:R], b["tasks"][k][:R])
    for k in ("arrival", "group_id", "bound_node"):
        np.testing.assert_array_equal(a["tasks"][k], b["tasks"][k])
    for k in ("cpu", "mem", "priority", "duration"):
        assert (a["tasks"][k][R:] != b["tasks"][k][R:]).any()
        np.testing.assert_array_equal(np.sort(a["tasks"][k][R:]),
                                      np.sort(b["tasks"][k][R:]))
    t = a["tasks"]
    assert (t["bound_node"][:R] >= 0).all() and (t["bound_node"][R:] == -1).all()
    assert (t["arrival"][:R] == 0).all() and (t["group_id"][:R] == -1).all()
    # a tainted node holds only residents that tolerate it
    assert t["tolerates"][:R][a["nodes"]["tainted"][t["bound_node"][:R]]].all()
    # no node over its cpu, memory or pods before the window starts
    for col, cap in (("cpu", "cpu"), ("mem", "mem")):
        use = np.bincount(t["bound_node"][:R], t[col][:R].astype(np.float64), 64)
        assert (use <= a["nodes"][cap]).all()
    assert np.bincount(t["bound_node"][:R], minlength=64).max() <= 110


def test_the_deployment_at_the_cells_size():
    """The resident set and the window at the cell's own size are what the
    configuration's file records (a pure function of the file)."""
    _, _, config, traffic = run.load_cell(CELL)
    generator = run.load_part("generators", config["generator"])
    trace = generator.generate(config, config["cluster"]["nodes"],
                               traffic["tasks"], 0)
    t, R = trace["tasks"], trace["resident"]
    want = config["workload"]["resident"]["measured"]
    assert R == want["residents"]
    assert t["cpu"][:R].sum() / trace["nodes"]["cpu"].sum() == pytest.approx(
        want["cpuShareOfBaseCluster"], abs=1e-4)
    assert t["mem"][:R].sum() / trace["nodes"]["mem"].sum() == pytest.approx(
        want["memoryShareOfBaseCluster"], abs=1e-4)
    assert t["arrival"][-1] == pytest.approx(want["windowSeconds"], abs=0.5)
    # the window runs at the deployment's rate: tasks / rate seconds
    rate = config["workload"]["deployedTasksPerDay"] / 86400.0
    assert t["arrival"][-1] == pytest.approx(traffic["tasks"] / rate, rel=0.02)
    # a chunk spans a fraction of a mean duration: the guard's 0.5 holds
    span = t["arrival"][-1] / (traffic["tasks"] / 8 / config["engine"]["chunkWaves"])
    assert config["workload"]["meanDuration"] / span > 0.5
    # the residents' shapes are the source's: upper cpu buckets, its tiers
    assert set(np.unique(t["cpu"][:R])) == {1.0, 2.0, 4.0, 8.0}
    assert set(np.unique(t["priority"][:R])) <= {0, 100, 200, 360, 450}
    assert (t["duration"][:R] < t["arrival"][-1]).mean() == pytest.approx(
        0.0185, abs=0.004)
    assert len(t["arrival"]) - R == traffic["tasks"] == config["scenario0"]["arriving"]


def test_names_units_and_files():
    b = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = {w["name"]: w for w in b["workloads"]}[CELL]
    conf = {c["name"]: c for c in b["configs"]}[cell["config"]]
    config = json.loads((ROOT / conf["file"]).read_text())
    assert conf["reduced"] == config["reduced"] == ["tasks", "scenarios"]
    assert len(conf["source"]) <= 200 and len(conf["why"]) <= 200
    assert config["architecture"] is None
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(cell["name"]) and NAME.match(cell["traffic"])
    assert (BENCH / "traffic" / f"{cell['traffic']}.json").is_file()
    for part in ("generator", "reference"):
        assert (BENCH / f"{part}s" / f"{config[part]}.py").is_file()
    for key in ("assumed", "guarantees", "limits", "scenario0"):
        assert config[key]
    assert config["limits"]["retried_binds_out_of_queue_order"] == 0
    assert config["limits"]["releases_not_at_their_boundary"] == 0
    metrics = {m["name"]: m for m in b["per_layer"]}
    layers = {m["layer"] for m in b["per_layer"]}
    for name in NEW_METRICS:
        m = metrics[name]
        assert NAME.match(name) and UNIT.match(m["unit"])
        assert m["workloads"] == [CELL] and m["moves"] == "placements_per_s"
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves",
                          "workloads"}
        assert (BENCH / "layer_metrics" / f"{name}.py").is_file()
    for name in BORROWED:  # the borrowed readers keep the lender's entry
        for key in ("unit", "better", "source", "layer"):
            assert metrics["backlog_" + name][key] == metrics[name][key]
    assert metrics["backlog_retry_roofline"]["better"] == "higher"
    assert "what-if retry pass" in layers


TABLE = {"jit_per_scenario_retry": {
    "fusion.1": "ksim.retry/ksim.reads", "fusion.2": "ksim.retry/ksim.select",
    "sort.3": "ksim.retry", "fusion.4": "ksim.release", "fusion.5": "ksim.select",
    "fusion.6": "ksim.commit",
}}
US = 1000


def made_up_trace():
    """One traced batch, 0..2000 us: two calls of the chunk program with the
    static release program before each; in a call the pass's wave steps (two
    ops, 40 + 60 us), the queue's sort (20 us), the re-tried binds' release
    (30 us) and the arrival waves (100 + 50 us)."""
    ops, modules = [], []
    body = [("fusion.4", 30), ("fusion.1", 40), ("fusion.2", 60),
            ("fusion.5", 100), ("fusion.6", 50), ("sort.3", 20)]
    for start in (100, 600):
        modules.append([f"jit_whatif_release_k256({start})", (start - 50) * US, 10 * US])
        modules.append(["jit_per_scenario_retry(7)", start * US, 400 * US])
        t = start
        for name, us in body:
            ops.append([f"%{name} = s32[]{{:T(128)}} fusion(%a, %b)", t * US, us * US])
            t += us + 1
    host = [["bench:batch:0", 0, 2000 * US], ["whatif_run:1", 10 * US, 1900 * US],
            ["handback", 1100 * US, 700 * US]]
    return {"devices": [{"modules": modules, "ops": ops, "dropped": []}],
            "host": sorted(host, key=lambda e: e[1])}


def read_all(events):
    # 64 chunk waves + 8 / 8 pass waves: the adapter's count of a call's steps
    ctx = {"trace": trace_reduce.Reduced(events), "device_kind": "TPU v5 lite",
           "shape": {"scenarios_per_chip": 128, "nodes": 10000, "resources": 3,
                     "wave_width": 8, "chunk_waves": 768 + 512, "planes": 2}}
    return {m: run.load_part("layer_metrics", m).read(ctx) for m in OWN_METRICS}


def test_the_new_readers_on_a_made_up_trace(monkeypatch):
    monkeypatch.setattr(profiling, "stage_tables", lambda: TABLE, raising=False)
    got = read_all(made_up_trace())
    assert got["backlog_retry_ms_per_boundary"] == pytest.approx(0.120)
    assert got["backlog_retry_share"] == pytest.approx(100 * 120 / 400)
    assert got["backlog_release_ms_per_boundary"] == pytest.approx(0.030 + 0.010)
    least = roofline_backlog.retry_min_ms(
        "TPU v5 lite", scenarios=128, nodes=10000, resources=3, wave_width=8,
        planes=2, buffer=4096, chunk_slots=768 * 8)
    assert got["backlog_retry_roofline"] == pytest.approx(100 * least / 0.120)
    # 512 wave steps of a wave's bytes and the upkeep's: one sort of the
    # queue and the chunk's slots, four words each way, and the record's row
    assert least == pytest.approx(
        512 * roofline.wave_min_ms("TPU v5 lite", scenarios=128, nodes=10000,
                                   resources=3, wave_width=8, planes=2)
        + 128 * (2 * (4096 + 6144) * 16 + 4096 * 7 * 4) / 819e9 * 1e3)


def test_the_new_readers_read_nothing_from_a_tree_without_the_scope(monkeypatch):
    """The parent's tree: no ``ksim.retry`` in its tables (and a tree with no
    tables at all), or no chunk program in the window: None, no raise."""
    older = {"jit_per_scenario_retry": {
        k: ("ksim.select" if v.startswith("ksim.retry") else v)
        for k, v in TABLE["jit_per_scenario_retry"].items()}}
    monkeypatch.setattr(profiling, "stage_tables", lambda: older, raising=False)
    got = read_all(made_up_trace())
    assert got["backlog_retry_ms_per_boundary"] is None
    assert got["backlog_retry_share"] is None
    assert got["backlog_retry_roofline"] is None
    monkeypatch.delattr(profiling, "stage_tables", raising=False)
    assert read_all(made_up_trace()) == dict.fromkeys(OWN_METRICS) | {
        "backlog_handback_ms_per_batch": got["backlog_handback_ms_per_batch"]}
    recorded = json.loads((BENCH / "testdata" / "trace_cut.json").read_text())
    ctx = {"trace": trace_reduce.Reduced(recorded), "device_kind": "TPU v5 lite",
           "shape": {"scenarios_per_chip": 128, "nodes": 10000, "resources": 3,
                     "wave_width": 8, "chunk_waves": 1280, "planes": 2}}
    for m in OWN_METRICS[:4]:
        assert run.load_part("layer_metrics", m).read(ctx) is None


@pytest.mark.parametrize("fault, says", [
    ({"release_path": "host"}, "not on the device-release path"),
    ({"chunk_waves": 8}, "a chunk of 8 waves"),
    ({"retry_buffer": 8}, "a retry buffer of 8"),
])
def test_the_adapter_refuses_another_program_before_any_batch(
        monkeypatch, fault, says):
    import kubernetes_simulator_tpu.sim.whatif as program

    class Other:
        release_path, chunk_waves, retry_buffer = "device", 16, 64

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
