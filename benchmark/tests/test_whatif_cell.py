"""The what-if cell's own tests, on the CPU, run by hand like their
siblings:

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_whatif_cell.py -q
"""

import json
import re
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path[:0] = [str(ROOT), str(BENCH)]

import roofline_whatif  # noqa: E402
import run  # noqa: E402
import trace_reduce  # noqa: E402
import whatif_scenarios  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELL = "borg10k-whatif128"
NEW_METRICS = ("whatif_release_ms_per_boundary", "whatif_release_roofline",
               "whatif_release_share", "whatif_handback_ms_per_batch")


def rehearse(monkeypatch, capsys, *extra):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    rc = run.main(["--workload", CELL, "--seed", "2147483664", "--seconds",
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


@pytest.mark.parametrize("control, least", [("bf16", 0.06), ("unperturbed", 0.06)])
def test_a_control_is_not_correct(monkeypatch, capsys, control, least):
    """The reference in bfloat16 in the program's place; and the reference
    of scenario 0 in every scenario's place, which only fails if the check
    sees the perturbations."""
    rc, checks, res = rehearse(monkeypatch, capsys, "--control", control)
    assert rc == 0 and res["correct"] is False
    assert checks["ref.choices_not_the_references_share"] > least
    # nothing else fails: the share is the limit that catches it
    assert checks["ref.placements_on_down_or_injected_taint_nodes"] == 0


def cell_parts(nodes: int, count: int):
    _, _, config, _ = run.load_cell(CELL)
    generator = run.load_part("generators", config["generator"])
    trace = generator.generate(config, nodes, 256, 1)
    ec, _ = generator.to_program(trace, config)
    return config, trace, ec, whatif_scenarios.sample(config, nodes, count)


def test_scenarios_give_the_program_the_references_node_tables():
    """The benchmark-side scenarios, made into the program's ``Scenario``s
    by the engine adapter, give ``ScenarioSet.host_clusters`` exactly the
    per-scenario node tables the reference builds."""
    from kubernetes_simulator_tpu.models.encode import PAD
    from kubernetes_simulator_tpu.sim.whatif import ScenarioSet

    config, trace, ec, plain = cell_parts(64, 8)
    assert not any(len(plain[0][k]) for k in ("down", "scaled", "tainted"))
    for kind in ("down", "scaled", "tainted"):  # every kind in a handful
        assert any(len(sc[kind]) for sc in plain), kind
    adapter = run.load_part("engines", "whatif")
    reference = run.load_part("references", config["reference"])
    clusters = ScenarioSet(ec, adapter.program_scenarios(config, plain),
                           keep_host_stacks=True).host_clusters(ec)
    r = ec.vocab._r
    key = ec.vocab.key(config["scenarios"]["taintKey"])
    for sc, own in zip(plain, clusters):
        table = reference.node_table(trace["nodes"], sc)
        for name, col in (("cpu", "cpu"), ("mem", "memory"), ("pods", "pods")):
            np.testing.assert_array_equal(table[name], own.allocatable[:, r[col]])
        np.testing.assert_array_equal(
            table["injected"], (own.taint_key == key).any(axis=1))
        base = (own.taint_key != PAD) & (own.taint_key != key)
        np.testing.assert_array_equal(table["tainted"], base.any(axis=1))
        # what takes no task is left out of the table choices are judged on
        judged, renumbered = reference.judged_on(
            table, np.arange(len(table["cpu"])))
        blocked = np.zeros(64, bool)
        blocked[sc["down"]] = blocked[sc["tainted"]] = True
        assert len(judged["cpu"]) == int((~blocked).sum())
        assert (renumbered[blocked] == -2).all() and judged["cpu"].min() > 0


def test_the_set_is_the_deployments_at_the_cells_size():
    """128 scenarios over 10,000 nodes from the configuration alone: the
    same set on every call, scenario 0 the base, every kind present, no
    more nodes touched than the sampler's bounds."""
    _, _, config, traffic = run.load_cell(CELL)
    a = whatif_scenarios.sample(config, 10000, traffic["scenarios"])
    b = whatif_scenarios.sample(config, 10000, traffic["scenarios"])
    assert len(a) == 128 == config["scenarios"]["perChip"]
    assert config["scenarios"]["deployed"] == 128 * config["scenarios"]["chips"]
    for x, y in zip(a, b):
        assert x["factor"] == y["factor"] and all(
            np.array_equal(x[k], y[k]) for k in ("down", "scaled", "tainted"))
    n = {k: sum(bool(len(sc[k])) for sc in a) for k in ("down", "scaled", "tainted")}
    assert n == {"down": 3, "scaled": 34, "tainted": 14}
    assert max(len(sc["down"]) for sc in a) < 200
    assert max(len(sc["scaled"]) for sc in a) < 1000
    assert max(len(sc["tainted"]) for sc in a) < 500
    assert {sc["factor"] for sc in a if len(sc["scaled"])} <= set(
        config["scenarios"]["capacityFactors"])


def test_the_sampler_draws_as_the_programs():
    """A copy, so that the traffic cannot move; today the two agree."""
    from kubernetes_simulator_tpu.sim.whatif import uniform_scenarios

    config, _, ec, plain = cell_parts(64, 8)
    p = config["scenarios"]["rehearse"]
    theirs = uniform_scenarios(
        ec, 8, seed=config["scenarios"]["seed"], p_node_down=p["pNodeDown"],
        p_capacity=p["pCapacity"], p_taint=p["pTaint"])
    ours = run.load_part("engines", "whatif").program_scenarios(config, plain)
    for a, b in zip(ours, theirs):
        assert [(x.op, list(x.nodes), x.factor) for x in a.perturbations] == [
            (x.op, list(x.nodes), x.factor) for x in b.perturbations]


def test_names_units_and_files():
    b = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = {w["name"]: w for w in b["workloads"]}[CELL]
    conf = {c["name"]: c for c in b["configs"]}[cell["config"]]
    config = json.loads((ROOT / conf["file"]).read_text())
    assert conf["reduced"] == config["reduced"] == ["tasks", "scenarios"]
    assert conf["source"] == config["source"] and len(conf["source"]) <= 200
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    assert NAME.match(cell["name"]) and NAME.match(cell["traffic"])
    assert all(NAME.match(k) for k in conf["reduced"])
    metrics = {m["name"]: m for m in b["per_layer"]}
    for name in NEW_METRICS:
        m = metrics[name]
        assert NAME.match(name) and UNIT.match(m["unit"])
        assert m["workloads"] == [CELL] and m["moves"] == "placements_per_s"
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves",
                          "workloads"}
        assert (BENCH / "layer_metrics" / f"{name}.py").is_file()
    # what the new cell adds comes last: nothing before it moved
    assert [m["name"] for m in b["per_layer"]][-4:] == list(NEW_METRICS)
    assert b["workloads"][-1]["name"] == CELL and b["configs"][-1] is conf


def test_release_and_handback_metrics_on_a_made_up_trace():
    """Two release programs (widths 256 and 1,024), a chunk program and a
    small end-of-run program inside one batch span; and a trace of the
    parent's, whose release program has another name, reads nothing."""
    ms = 1_000_000
    modules = [
        ["jit_whatif_release_k256(123)", 10 * ms, 2 * ms],
        ["jit_per_scenario_rel(456)", 12 * ms, 50 * ms],
        ["jit_whatif_release_k1024(789)", 62 * ms, 6 * ms],
        ["jit_per_scenario_rel(456)", 68 * ms, 50 * ms],
        ["jit__util(1)", 119 * ms, 1 * ms],
    ]
    events = {"devices": [{"modules": modules, "dropped": [],
                           "ops": [[n, s, d] for n, s, d in modules]}],
              "host": [["bench:batch:0", 0, 200 * ms]]}
    ctx = {"trace": trace_reduce.Reduced(events), "device_kind": "TPU v5 lite",
           "shape": {"scenarios_per_chip": 128, "nodes": 10000, "resources": 3}}
    got = {m: run.load_part("layer_metrics", m).read(ctx) for m in NEW_METRICS}
    assert got["whatif_release_ms_per_boundary"] == pytest.approx(4.0)
    assert got["whatif_release_share"] == pytest.approx(100 * 8 / 200)
    assert got["whatif_handback_ms_per_batch"] == pytest.approx(82.0)
    least = sum(roofline_whatif.release_min_ms(
        "TPU v5 lite", scenarios=128, nodes=10000, resources=3, rows=k)
        for k in (256, 1024))
    assert got["whatif_release_roofline"] == pytest.approx(100 * least / 8)
    assert 0 < got["whatif_release_roofline"] < 100
    # 128 scenarios' used planes read and written, the placements read,
    # and the 256 shared rows
    assert roofline_whatif.release_bytes(
        scenarios=128, nodes=10000, resources=3, rows=256) == 4 * (
        128 * (60000 + 256) + 256 * 5)
    for m in modules:
        m[0] = m[0].replace("jit_whatif_release_k256", "jit_rel_one").replace(
            "jit_whatif_release_k1024", "jit_rel_one")
    ctx["trace"] = trace_reduce.Reduced(events)
    assert [run.load_part("layer_metrics", m).read(ctx)
            for m in NEW_METRICS[:3]] == [None, None, None]
