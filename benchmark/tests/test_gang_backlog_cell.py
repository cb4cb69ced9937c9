"""The job-queue cell's own tests (``pai1800-gangqueue256``), on the CPU, run
by hand like their siblings (tier-1 imports them through
``tests/test_benchmark_cases.py``):

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_gang_backlog_cell.py -q
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
import roofline_gang_backlog  # noqa: E402
import run  # noqa: E402
import trace_reduce  # noqa: E402
import whatif_scenarios  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELL = "pai1800-gangqueue256"
METRICS = ("gangq_retry_ms_per_boundary", "gangq_retry_share",
           "gangq_layout_ms_per_boundary", "gangq_txn_ms_per_pass_wave",
           "gangq_join_ms_per_boundary", "gangq_release_ms_per_boundary",
           "gangq_handback_ms_per_batch", "gangq_host_untraced_share",
           "gangq_retry_roofline", "gangq_due_release_ms_per_boundary",
           "gangq_record_ms_per_boundary", "gangq_steps_ms_per_boundary",
           "gangq_pass_unscoped_share", "gangq_arrival_txn_ms_per_wave")
FULL_ROWS = (
    "ref.codes_that_disagree_with_the_nodes", "ref.pods_split_from_their_job",
    "ref.retried_jobs_not_closed_in_an_earlier_chunk",
    "ref.boundaries_with_the_queue_over_the_buffer",
    "ref.jobs_dropped_or_joined_against_the_rule",
    "ref.binds_on_a_node_over_its_allocatable",
    "ref.placements_on_down_or_injected_taint_nodes",
    "ref.placed_differs_from_answers_max", "ref.pods_unaccounted_for_max",
    "ref.counters_the_arrays_do_not_imply")


def rehearse(monkeypatch, capsys, *extra):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    rc = run.main(["--workload", CELL, "--seed", "2147483664", "--seconds",
                   "0.2", "--trace", "0", "--rehearse", *extra])
    lines = capsys.readouterr().out.strip().splitlines()
    batches, res = json.loads(lines[-2]), json.loads(lines[-1])
    return rc, {n: v for n, v, *_ in batches["checks"]}, res


def parts():
    _, _, config, traffic = run.load_cell(CELL)
    return (config, traffic, run.load_part("generators", config["generator"]),
            run.load_part("references", config["reference"]))


def test_the_rehearsal_is_correct(monkeypatch, capsys):
    rc, checks, res = rehearse(monkeypatch, capsys)
    assert rc == 0 and res["correct"] is True and res["attempted"] >= 2
    assert set(res["metrics"]) == {"placements_per_s", "setup_s"}
    assert checks["ref.choices_not_the_references_share"] == 0.0
    assert checks["window.compiles"] == 0
    assert [checks[row] for row in FULL_ROWS] == [0] * len(FULL_ROWS)
    # the strata hold what the deployment exists for
    assert checks["ref.retried_binds_compared"] > 500
    assert checks["ref.rolled_back_in_a_pass_compared"] > 500
    assert checks["ref.retried_binds_handed_back"] > 0


@pytest.mark.parametrize("control, least", [
    ("bf16", 0.03), ("never-retried", 0.1), ("members-singly", 0.05),
    ("wave-local-pass", 0.05)])
def test_a_control_is_not_correct(monkeypatch, capsys, control, least):
    """The reference in bfloat16 in the program's place; a reference that
    never re-tries a group; one that re-tries members singly; one that judges
    a wide job wave by wave in the pass. Each by the share, none by a row over
    the whole batch: those read the program's answers."""
    rc, checks, res = rehearse(monkeypatch, capsys, "--control", control)
    assert rc == 0 and res["correct"] is False
    assert checks["ref.choices_not_the_references_share"] > least
    assert [checks[row] for row in FULL_ROWS] == [0] * len(FULL_ROWS)


def test_an_unknown_control_is_refused():
    config, traffic, gen, ref = parts()
    with pytest.raises(ValueError, match="unknown control"):
        ref.check({}, config, {}, 0, 8, "no-such")


@functools.lru_cache(maxsize=None)
def program_answers():
    """The rehearsal-size trace through the cell's adapter: 8 scenarios in one
    ``WhatIfEngine.run()``. (Cached, not a fixture: tier-1 imports this
    file's cases by name.)"""
    config, traffic, gen, ref = parts()
    reh = traffic["rehearse"]
    config = {**config, "engine": {**config["engine"],
                                   "chunkWaves": reh["chunkWaves"]}}
    trace = gen.generate(config, reh["nodes"], reh["tasks"], 11)
    ec, ep = gen.to_program(trace, config)
    adapter = run.load_part("engines", traffic["engine"]).Engine(
        ec, ep, config, traffic, reh["chunkWaves"])
    return config, trace, ref, adapter.answers(adapter.batch())


def test_the_program_gives_the_rule_run_whole_pod_for_pod():
    """``schedule`` (the rule, numpy, scenario by scenario) against the
    program's answers in EVERY scenario of the rehearsal: nodes, boundaries
    and counters."""
    config, trace, ref, ans = program_answers()
    eng = config["engine"]
    scen = whatif_scenarios.sample(config, len(trace["nodes"]["cpu"]),
                                   len(ans["placed"]))
    seen = set()
    for s, sc in enumerate(scen):
        stats = {}
        assign, bind = ref.schedule(
            ref.GJ.node_table(trace["nodes"], sc), trace["tasks"],
            eng["waveWidth"], eng["chunkWaves"], ans["retry_buffer"],
            config["scheduler"]["weights"], stats)
        np.testing.assert_array_equal(assign, ans["assignments"][s])
        np.testing.assert_array_equal(bind, ans["bind_boundary"][s])
        for k in ref.COUNTERS + ("pass_rollbacks_after_bind", "dropped",
                                 "depth_max"):
            assert stats[k] == ans["groups"][k][s], (s, k)
        seen.add(bind.tobytes())
    assert len(seen) > 1  # the queues differ by scenario
    assert max(ans["groups"]["pass_rollbacks_after_bind"]) > 0
    assert max(ans["groups"]["jobs_bound_pass"]) > 0


def broken(kind):
    """The program's sound answers with one breach made by hand."""
    config, trace, ref, ans = program_answers()
    out = {**ans, "assignments": ans["assignments"].copy(),
           "bind_boundary": ans["bind_boundary"].copy()}
    lay = ref.layout(trace["tasks"], 8, config["engine"]["chunkWaves"])
    bind, assign = out["bind_boundary"][0], out["assignments"][0]
    wide = np.nonzero((bind >= 0) & (lay["jsize"] > 8))[0]
    if kind == "a member left behind":
        bind[wide[0]], assign[wide[0]] = -2, -1
    elif kind == "bound in its own chunk":
        job = lay["job"] == lay["job"][wide[0]]
        bind[job] = lay["closing"][wide[0]]
    elif kind == "released late":
        # a job bound at its arrival holds its node past the batch: a pass
        # then bound onto a node that the rule's state shows full
        k = np.nonzero((bind == -1) & (trace["tasks"]["gpu"] > 0)
                       & (lay["release"] < lay["chunks"] // 2))[0]
        full = np.nonzero(bind >= lay["chunks"] // 2)[0]
        assign[full[:64]] = assign[k[0]]
    return config, trace, ref, out


@pytest.mark.parametrize("kind, row", [
    ("a member left behind", "ref.pods_split_from_their_job"),
    ("bound in its own chunk", "ref.retried_jobs_not_closed_in_an_earlier_chunk"),
    ("released late", "ref.binds_on_a_node_over_its_allocatable")])
def test_a_breach_made_by_hand_is_seen_by_its_row(kind, row):
    config, trace, ref, ans = broken(kind)
    rows = {n: (v, lim) for n, v, lim in ref.check(trace, config, ans, 3, 64)}
    assert rows[row][0] > 0 and rows[row][1] == 0


def test_every_seed_gets_the_same_work_in_another_deal():
    config, traffic, gen, ref = parts()
    a = gen.generate(config, 64, 2400, 1)["tasks"]
    b = gen.generate(config, 64, 2400, 2147483700)["tasks"]
    for k in ("arrival", "gang", "gpu", "priority", "duration"):
        np.testing.assert_array_equal(a[k], b[k])
    assert not np.array_equal(a["app"], b["app"])
    # cpu and memory are dealt behind the slot the configuration states (the
    # last sixteenth), among the pods that ask for no GPU, as (cpu, memory)
    # pairs; before it every seed has baseSeed's
    slot = config["workload"]["dealFrom"] * 2400 // config["workload"]["tasks"]
    assert slot == 2250
    for k in ("cpu", "mem"):
        np.testing.assert_array_equal(a[k][:slot], b[k][:slot])
        np.testing.assert_array_equal(a[k][a["gpu"] > 0], b[k][a["gpu"] > 0])
        np.testing.assert_array_equal(np.sort(a[k]), np.sort(b[k]))
    assert not np.array_equal(a["cpu"], b["cpu"])
    # a job is one arrival: one time, one priority, one duration
    job = gen.job_of(a["gang"])
    for k in ("arrival", "priority", "duration"):
        np.testing.assert_array_equal(a[k], a[k][job])
    assert (np.diff(a["arrival"]) >= 0).all()


def test_the_configuration_is_the_gang_cells_cluster_with_a_queue():
    config, traffic, gen, ref = parts()
    _, _, base, _ = run.load_cell("pai1800-whatif256")
    for key in ("cluster", "resources", "scenarios"):
        assert config[key] == base[key]
    for key in ("jobSizes", "gpuJobs", "numApps", "workerFraction", "baseSeed"):
        assert config["workload"][key] == base["workload"][key]
    eng = config["engine"]
    assert eng["retryGroups"] is True and eng["retryBuffer"] == 4096
    assert eng["waveWidth"] == 8 and eng["completions"] is True
    c = config["counts"]
    assert c["pods"] == config["workload"]["tasks"] == 65536  # ISSUE 54's size
    assert c["waves"] == eng["chunkWaves"] * c["boundaries"]  # no padded wave
    assert c["boundaries"] == 102
    # PAI's own arrival rate; what was moved is the duration's scale
    assert config["workload"]["arrivalRate"] == 1.5
    # the targets (a) to (f), as the reference counted them: every one
    assert c["a_pods_in_wide_groups_share"] >= 0.55
    assert c["b_boundaries_queue_non_empty_share_after_first_quarter"] >= 0.75
    assert 1024 <= c["depth_max"] <= 4096 and c["dropped"] == 0
    assert c["c_wide_jobs_bound_by_a_pass"] >= 150
    assert c["c_of_them_after_two_failed_passes_share"] >= 0.30
    assert c["pass_rollbacks"] >= 100 and c["pass_rollbacks_after_bind"] >= 20
    assert c["e_placed_pods_released_inside_the_batch_share"] >= 0.40
    assert c["f_gpu_pods_placed_share"] >= 0.90
    assert c["f_gpus_in_use_at_peak_boundaries_share"] >= 0.85
    assert c["chunk_span_s"]["max"] <= c["duration_s"]["median"] / 2
    assert config["reduced"] == ["tasks"]


def test_names_units_and_files():
    b = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = {w["name"]: w for w in b["workloads"]}[CELL]
    conf = {c["name"]: c for c in b["configs"]}[cell["config"]]
    config = json.loads((ROOT / conf["file"]).read_text())
    assert conf["reduced"] == config["reduced"] == ["tasks"]
    assert conf["source"] == config["source"]
    assert len(conf["source"]) <= 200 and len(conf["why"]) <= 200
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(cell["name"]) and NAME.match(cell["traffic"])
    assert (BENCH / "traffic" / f"{cell['traffic']}.json").is_file()
    for part in ("generator", "reference"):
        assert (BENCH / f"{part}s" / f"{config[part]}.py").is_file()
    for key in ("assumed", "guarantees", "limits", "counts"):
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
    assert metrics["gangq_retry_roofline"]["better"] == "higher"
    # the reference and the generator's plain part import nothing of the program
    text = (BENCH / "references/gang_backlog_scenarios.py").read_text()
    assert "kubernetes_simulator_tpu" not in text.split('"""', 2)[2]


US = 1000


def made_up_trace(groups=True):
    """One traced batch, 0..3000 us: three boundaries, each the release
    program (20 us), the pass program (300 / 200 / 100 us) and the arrival
    program (100 us); the pass program's ops by stage; the program's mark of
    the 60 wave steps its three passes executed."""
    modules, ops = [], []
    for b, dur in enumerate((300, 200, 100)):
        at = 100 + 700 * b
        modules += [["jit_whatif_release_k256(5)", at * US, 20 * US],
                    ["jit_per_scenario_retry(7)", (at + 30) * US, dur * US],
                    ["jit_whatif_record(8)", (at + 350) * US, 30 * US],
                    ["jit_per_scenario_arrivals(9)", (at + 400) * US, 100 * US]]
        t = (at + 30) * US
        for name, d in (("due", 10), ("gather", 10), ("layout", 5),
                        ("close", dur // 10), ("txn", dur // 20),
                        ("step", dur // 2), ("record", 5), ("copy", 15)):
            ops.append([f"%{name}.1 = s32[8]{{0}} fusion(%a)", t, d * US])
            t += d * US
        ops.append(["%append.1 = s32[8]{0} fusion(%a)", (at + 350) * US, 25 * US])
        t = (at + 400) * US
        for name, d in (("scan", 60), ("join", 8), ("sort", 20), ("atxn", 12)):
            ops.append([f"%{name}.1 = s32[8]{{0}} fusion(%a)", t, d * US])
            t += d * US
    host = [["bench:batch:0", 0, 3000 * US], ["whatif_run:1", 10 * US, 2900 * US],
            ["handback", 2500 * US, 200 * US]]
    events = {"devices": [{"modules": modules, "ops": sorted(ops, key=lambda e: e[1]),
                           "dropped": []}],
              "host": sorted(host, key=lambda e: e[1])}
    events["program_span_events"] = [[n, s, d, 1, {}] for n, s, d in events["host"]]
    if groups:
        events["program_span_events"].append(
            ["retry_pass_waves", 2400 * US, 0, 1, {"waves": 60, "passes": 3}])
    return events


TABLES = {
    "jit_per_scenario_retry": {
        "due.1": "ksim.release", "gather.1": "ksim.retry/Gather",
        "layout.1": "ksim.retry/Layout", "close.1": "ksim.retry/Close",
        "txn.1": "ksim.retry/ksim.gang_txn", "step.1": "ksim.retry/ksim.select",
        "record.1": "ksim.retry/Record"},
    "jit_whatif_record": {"append.1": "ksim.retry/Record"},
    "jit_per_scenario_arrivals": {
        "scan.1": "ksim.select", "join.1": "ksim.retry/Join",
        "sort.1": "ksim.retry", "atxn.1": "ksim.gang_txn"},
}


def read_all(events, monkeypatch, tables=TABLES):
    from layer_metrics import _stages

    monkeypatch.setattr(_stages, "stage_tables", lambda: tables)
    ctx = {"trace": trace_reduce.Reduced(events), "device_kind": "TPU v5 lite",
           "program_span_events": events.get("program_span_events"),
           "shape": {"scenarios_per_chip": 256, "nodes": 1800, "resources": 4,
                     "wave_width": 8, "chunk_waves": 91, "planes": 1}}
    return {m: run.load_part("layer_metrics", m).read(ctx) for m in METRICS}


def test_the_new_readers_on_a_made_up_trace(monkeypatch):
    got = read_all(made_up_trace(), monkeypatch)
    # a boundary is a run of the pass program: three, not six chunk programs
    per_pass = lambda f: sum(f(d) for d in (300, 200, 100)) / 3 / 1e3
    retry = per_pass(lambda d: 10 + 5 + d // 10 + d // 20 + d // 2 + 5)
    assert got["gangq_retry_ms_per_boundary"] == pytest.approx(retry)
    # ... and the due releases, a copy under no scope, the arrival program
    both = retry + 0.010 + 0.015 + 0.100
    assert got["gangq_retry_share"] == pytest.approx(100 * retry / both)
    assert got["gangq_layout_ms_per_boundary"] == pytest.approx(0.005)
    # over the 60 wave steps the passes EXECUTED, not the buffer's 512 a pass
    txn = per_pass(lambda d: d // 10 + d // 20)
    assert got["gangq_txn_ms_per_pass_wave"] == pytest.approx(txn * 3 / 60)
    assert got["gangq_join_ms_per_boundary"] == pytest.approx(0.008)
    assert got["gangq_release_ms_per_boundary"] == pytest.approx(0.010 + 0.020)
    assert got["gangq_due_release_ms_per_boundary"] == pytest.approx(0.010)
    # the pass program's own scope and the program that appends to the log
    assert got["gangq_record_ms_per_boundary"] == pytest.approx(0.005 + 0.025)
    assert got["gangq_steps_ms_per_boundary"] == pytest.approx(
        per_pass(lambda d: d // 20 + d // 2))
    assert got["gangq_pass_unscoped_share"] == pytest.approx(
        100 * 0.015 / (retry + 0.010 + 0.015))
    # by a run of the arrival program ALONE, over the chunk's 91 waves
    assert got["gangq_arrival_txn_ms_per_wave"] == pytest.approx(0.012 / 91)
    assert got["gangq_handback_ms_per_batch"] == pytest.approx(0.200)
    assert got["gangq_host_untraced_share"] == pytest.approx(100 * (1 - 200 / 2900))
    least = roofline_gang_backlog.retry_min_ms(
        "TPU v5 lite", waves_per_pass=20, scenarios=256, nodes=1800,
        resources=4, wave_width=8, planes=1, buffer=4096, chunk_slots=91 * 8)
    assert got["gangq_retry_roofline"] == pytest.approx(100 * least / retry)
    # a pass wave needs more bytes than an arrival wave: the transaction's plane
    one = roofline_gang_backlog.pass_wave_bytes(256, 1800, 4, 8, 1)
    assert one == roofline.wave_bytes(256, 1800, 4, 8, 1) + 2 * 256 * 4 * 1800 * 4
    # where the device's buffer overflowed inside the batch the window ends
    # there: the device-side metrics read what is left, the host-side ones
    # and the executed waves the whole batch
    cut = made_up_trace()
    cut["devices"][0]["dropped"] = [1400 * US]
    short = read_all(cut, monkeypatch)
    assert short["gangq_layout_ms_per_boundary"] == pytest.approx(0.005)
    assert short["gangq_retry_ms_per_boundary"] > got["gangq_retry_ms_per_boundary"]
    for m in ("gangq_handback_ms_per_batch", "gangq_host_untraced_share"):
        assert short[m] == got[m]


def test_the_new_readers_read_nothing_from_a_tree_without_the_setting(monkeypatch):
    """The parent's pass program has no job layout and writes no mark, a tree
    before PR 52 no stage table, one that exports no span names no span:
    None, no raise."""
    from kubernetes_simulator_tpu.sim import telemetry

    parent = {m: {i: ("ksim.retry" if p.startswith("ksim.retry/") and p.split("/")[1]
                      in ("Layout", "Close", "Join") else p)
                  for i, p in t.items()} for m, t in TABLES.items()}
    got = read_all(made_up_trace(groups=False), monkeypatch, tables=parent)
    assert got == dict.fromkeys(METRICS)
    got = read_all(made_up_trace(), monkeypatch, tables=None)
    assert [m for m in METRICS if got[m] is not None] == [
        "gangq_handback_ms_per_batch", "gangq_host_untraced_share"]
    monkeypatch.delattr(telemetry, "HOST_SPAN_NAMES")
    got = read_all(made_up_trace(), monkeypatch)
    assert [m for m in METRICS if got[m] is not None] == [
        "gangq_retry_ms_per_boundary", "gangq_retry_share",
        "gangq_layout_ms_per_boundary", "gangq_join_ms_per_boundary",
        "gangq_release_ms_per_boundary", *METRICS[9:]]


@pytest.mark.parametrize("fault, says", [
    ({"release_path": "host"}, "release path 'host'"),
    ({"chunk_waves": 8}, "chunk 8"),
    ({"retry_buffer": 8}, "retry buffer 8"),
    ({"retry_groups": False}, "retry_groups False"),
])
def test_the_adapter_refuses_another_program_before_any_batch(
        monkeypatch, fault, says):
    import kubernetes_simulator_tpu.sim.whatif as program

    class Other:
        release_path, chunk_waves, retry_buffer, retry_groups = (
            "device", 18, 256, True)

        def __init__(self, *a, **kw):
            for k, v in fault.items():
                setattr(self, k, v)

    config, traffic, gen, ref = parts()
    trace = gen.generate(config, 64, 256, 1)
    ec, ep = gen.to_program(trace, config)
    monkeypatch.setattr(program, "WhatIfEngine", Other)
    adapter = run.load_part("engines", traffic["engine"])
    with pytest.raises(RuntimeError, match=says):
        adapter.Engine(ec, ep, config, traffic, 18)
    off = {**config, "engine": {**config["engine"], "retryGroups": False}}
    with pytest.raises(RuntimeError, match="does not turn retryGroups on"):
        adapter.Engine(ec, ep, off, traffic, 18)


def test_two_batches_that_differ_in_a_counter_raise():
    adapter = run.load_part("engines", "whatif_gang_backlog")
    config, trace, ref, ans = program_answers()
    eng = object.__new__(adapter.Engine)
    eng.retry_buffer, eng.classes = ans["retry_buffer"], {}
    eng._first_bind_boundary = ans["bind_boundary"]
    eng._first_groups = {k: v + (k == "pass_attempts")
                         for k, v in ans["groups"].items()}

    class Telemetry:
        def summary(self):
            return {"retry": {"pass_waves": {"max": 1}}}

    class Result:
        placed = unschedulable = np.zeros(len(ans["placed"]), np.int32)
        assignments, bind_boundary = ans["assignments"], ans["bind_boundary"]
        group_counts, job_waits = ans["groups"], {}
        fleet_telemetry = Telemetry()

    with pytest.raises(RuntimeError, match="differ in a job-queue counter"):
        eng.answers(Result())
