"""The multi-tenant mesh cell's own tests, on the CPU (four virtual devices:
``XLA_FLAGS=--xla_force_host_platform_device_count=4``, or the eight that
``tests/conftest.py`` gives), run by hand like their siblings:

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_multitenant_cell.py -q
"""

import json
import os
import re
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path[:0] = [str(ROOT), str(BENCH)]
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=4")

import roofline_whatif_arrivals  # noqa: E402
import run  # noqa: E402
import trace_reduce  # noqa: E402
import whatif_scenarios  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELL = "multitenant-mesh4"
CONFIG = "multitenant-1k-mesh"
TRAFFIC = "whatif-1024-mesh4"
NEW_METRICS = ("mesh_stage_ms_per_batch", "mesh_handback_ms_per_batch",
               "mesh_handback_roofline", "mesh_device_skew_share")
LISTLESS = ("encode_s", "compile_s", "chunk_gap_ms", "chunk_ms_per_wave",
            "chunk_roofline", "device_idle_share")
# the rows over every placement of every scenario, limit 0
FULL_ROWS = ("ref.placements_on_down_or_injected_taint_nodes",
             "ref.nodes_over_allocatable_cpu_memory_pods",
             "ref.nodes_over_allocatable_extended_resource",
             "ref.pod_groups_partly_bound", "ref.placed_differs_from_answers_max")
# What BENCHMARK.json held before this cell, in its order: a later PR appends.
EARLIER_CELLS = ["borg10k-replay1", "borg10k-whatif128", "k8s5k-whatif256"]
EARLIER_CONFIGS = ["borg2019-10k-gangs", "borg2019-10k-whatif",
                   "k8s5k-default-plugins"]
EARLIER_LAST_METRICS = ["whatif_chunk_select_ms_per_wave",
                        "whatif_chunk_unattributed_share"]


def rehearse(monkeypatch, capsys, *extra):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    rc = run.main(["--workload", CELL, "--seed", "2147483664", "--seconds",
                   "1", "--trace", "0", "--rehearse", *extra])
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
    assert res["device"]["count"] >= 4
    assert checks["ref.choices_not_the_references_share"] == 0.0
    assert checks["window.compiles"] == 0
    assert [checks[row] for row in FULL_ROWS] == [0] * len(FULL_ROWS)
    assert checks["ref.samples_left_out_share"] <= 0.02


@pytest.mark.parametrize("control, least", [
    ("bf16", 0.08), ("unperturbed", 0.2), ("no-extended", 0.12),
    ("no-gang", 0.01)])
def test_a_control_is_not_correct(monkeypatch, capsys, control, least):
    """The reference in bfloat16 in the program's place; the reference of
    scenario 0 in every scenario's place, which only fails if the check sees
    the perturbations; the reference's fit without the ``google.com/tpu`` row,
    which only fails if the check sees the extended resource; and the
    reference without the gang rollback, which only fails if it sees that."""
    rc, checks, res = rehearse(monkeypatch, capsys, "--control", control)
    assert rc == 0 and res["correct"] is False
    assert checks["ref.choices_not_the_references_share"] > least
    # nothing over the whole batch fails: those rows read the program's
    # answers, and the share is the limit that catches a control
    assert [checks[row] for row in FULL_ROWS] == [0] * len(FULL_ROWS)


def test_the_rows_over_every_placement_see_each_mechanism_left_out():
    """Placements made without a mechanism break the row that holds it, and
    only that: the reference's own whole-trace schedule without the extended
    resource in the fit (accelerator nodes over their 8, and nodes with no
    device plugin over their 0), without the gang rollback (pod groups
    partly bound), and sound (nothing). The sampled pods lean on the
    accelerator pods and the gang members."""
    config, _, gen, ref = parts()
    trace = gen.generate(config, 64, 1024, 5)
    nodes = ref.node_table(trace["nodes"],
                           whatif_scenarios.sample(config, 64, 1)[0])
    pods, weights = trace["tasks"], config["scheduler"]["weights"]
    width = config["engine"]["waveWidth"]

    def rows(assign):
        return (*ref.over_allocatable(nodes, pods, assign),
                ref.gangs_partly_bound(pods, assign))

    sound = ref.schedule(nodes, pods, width, weights)
    assert rows(sound) == (0, 0, 0)
    assert (sound < 0).sum() > 0 and (sound[pods["tpu"] > 0] >= 0).sum() > 0
    core, extended, partly = rows(
        ref.schedule(nodes, pods, width, weights, extended=False))
    assert (core, partly) == (0, 0) and extended > 0
    core, extended, partly = rows(
        ref.schedule(nodes, pods, width, weights, gang=False))
    assert (core, extended) == (0, 0) and partly > 0
    # a gang the sound schedule rolled back has a member that fits nowhere
    gang = pods["gang"]
    rolled = np.unique(gang[(gang != ref.PAD) & (sound < 0)])
    assert len(rolled) > 0
    assert all((sound[gang == g] < 0).all() for g in rolled)
    order = ref.order_tried(pods, width)
    pairs = ref.draw(np.random.default_rng(3), 8, order["seq"], 0, pods)
    assert len(pairs) >= 8 * 4
    leaning = (pods["tpu"] > 0) | (gang != ref.PAD)
    assert leaning[pairs[:, 1]].mean() > leaning.mean() + 0.15
    for s in range(8):
        mine = pairs[pairs[:, 0] == s, 1]
        assert (pods["tpu"][mine] > 0).sum() >= ref.PER_STRATUM
        assert (gang[mine] != ref.PAD).sum() >= ref.PER_STRATUM
        assert order["seq"][-1] in mine


def test_a_rolled_back_gang_is_rebuilt_by_the_references_own_picks():
    """Teacher-forced on the reference's own schedule, every sampled pod is
    sound or left out on an edge, also the members of rolled-back gangs and
    the pods behind one in its wave; a rolled-back gang whose members all
    fit is called wrong, and ``no-gang`` calls a rolled-back member that
    fits by itself wrong."""
    config, _, gen, ref = parts()
    trace = gen.generate(config, 64, 1024, 7)
    nodes = ref.node_table(trace["nodes"],
                           whatif_scenarios.sample(config, 64, 1)[0])
    pods, weights = trace["tasks"], config["scheduler"]["weights"]
    order = ref.order_tried(pods, config["engine"]["waveWidth"])
    assign = ref.schedule(nodes, pods, config["engine"]["waveWidth"], weights)
    gang = pods["gang"]
    rolled = np.unique(gang[(gang != ref.PAD) & (assign < 0)])
    members = np.nonzero(np.isin(gang, rolled))[0]
    behind = [k for k in range(len(gang)) if gang[k] not in rolled
              and np.isin(gang[order["idx"][order["wave"][k]]], rolled).any()]
    got = [ref.judge_in_wave(nodes, pods, order, assign, int(k), weights)
           for k in list(members) + behind]
    assert len(members) >= 8 and len(behind) >= 8
    assert all(g in (0.0, None) for g in got)
    assert sum(g is None for g in got) <= 0.1 * len(got)
    alone = [ref.judge_in_wave(nodes, pods, order, assign, int(k), weights,
                               "no-gang") for k in members]
    assert sum(g == 100.0 for g in alone) > 0
    # roll back a gang that was bound whole: every member fitted
    whole = next(g for g in np.unique(gang[gang != ref.PAD])
                 if (assign[gang == g] >= 0).all())
    wrong = assign.copy()
    wrong[gang == whole] = ref.PAD
    k = int(np.nonzero(gang == whole)[0][0])
    assert ref.judge_in_wave(nodes, pods, order, wrong, k, weights) == 100.0


def test_every_seed_gets_the_same_work_in_another_deal():
    """Two seeds: the same cluster, arrival times, gang layout, accelerator
    pods and multiset of pods, the other pods on other arrival slots; the
    same waves; the scenario set does not know the seed."""
    config, _, gen, ref = parts()
    a = gen.generate(config, 64, 512, 1)
    b = gen.generate(config, 64, 512, 2147483700)
    for k, v in a["nodes"].items():
        assert np.array_equal(v, b["nodes"][k]), k
    for fixed in ("arrival", "gang", "tpu"):
        assert np.array_equal(a["tasks"][fixed], b["tasks"][fixed])
    cols = [k for k in a["tasks"] if k not in ("arrival", "gang", "tpu")]
    assert sorted(cols) == sorted(gen.DEALT)
    asking = a["tasks"]["tpu"] > 0  # an accelerator pod keeps its slot whole
    assert asking.sum() > 50
    assert all(np.array_equal(a["tasks"][k][asking], b["tasks"][k][asking])
               for k in cols)
    rows = lambda t: sorted(zip(*(t["tasks"][k].tolist() for k in cols)))
    assert rows(a) == rows(b)
    assert any(not np.array_equal(a["tasks"][k], b["tasks"][k]) for k in cols)
    again = gen.generate(config, 64, 512, 1)
    assert all(np.array_equal(a["tasks"][k], again["tasks"][k]) for k in cols)
    assert np.array_equal(ref.order_tried(a["tasks"], 8)["idx"],
                          ref.order_tried(b["tasks"], 8)["idx"])
    x = whatif_scenarios.sample(config, 64, 8)
    y = whatif_scenarios.sample(config, 64, 8)
    assert all(np.array_equal(p[k], q[k]) for p, q in zip(x, y)
               for k in ("down", "scaled", "tainted"))


def test_the_generator_draws_as_the_programs():
    """A copy, so that the traffic cannot move; today the two agree: the
    undealt columns are ``config5_multitenant()``'s nodes and pods, object
    for object. The program numbers resources and gangs as the trace does."""
    from kubernetes_simulator_tpu.sim.synthetic import config5_multitenant

    config, _, gen, _ = parts()
    wl, cl = config["workload"], config["cluster"]
    trace = {"nodes": gen.node_table(40, wl["baseSeed"], cl),
             "tasks": gen.pod_columns(600, wl["baseSeed"], wl,
                                      cl["accelerator"]["count"])}
    cluster, pods = gen.program_objects(trace, config)
    theirs_cluster, theirs, _ = config5_multitenant(40, 600, wl["baseSeed"])
    assert cluster.nodes == theirs_cluster.nodes
    assert pods == theirs
    t = trace["tasks"]
    assert (t["tpu"] > 0).sum() > 60 and (t["gang"] != gen.PAD).sum() > 60
    ec, ep = gen.to_program(trace, config)
    assert list(ec.vocab.resources) == config["resources"]
    assert np.array_equal(np.asarray(ep.group_id), t["gang"])


def test_the_deployment_at_the_cells_size():
    """Nothing is cut: 1,000 nodes, a quarter with 8 google.com/tpu, 10,000
    pods in 1,292 waves (a gang is never split) that the chunk divides,
    1,024 scenarios with every kind of perturbation, 256 a chip over the 4
    chips that the cell, the traffic file and the configuration all state."""
    config, traffic, gen, ref = parts()
    b = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = {w["name"]: w for w in b["workloads"]}[CELL]
    assert config["reduced"] == [] and config["cluster"]["nodes"] == 1000
    assert run.sizes(config, traffic, False) == {
        "nodes": 1000, "tasks": 10000, "chunkWaves": 323}
    assert cell["chips"] == traffic["chips"] == config["scenarios"]["chips"] == 4
    assert traffic["scenarios"] == 1024 == config["scenarios"]["deployed"]
    assert config["scenarios"]["perChip"] * 4 == 1024
    assert config["resources"] == ["cpu", "memory", "pods", "google.com/tpu"]
    trace = gen.generate(config, 1000, 10000, 3)
    nodes, pods = trace["nodes"], trace["tasks"]
    assert (nodes["tpu"] > 0).sum() == 246 and nodes["tpu"].sum() == 1968
    assert pods["tpu"].sum() == 7025 and (pods["tpu"] > 0).sum() == 1924
    assert sorted(np.unique(pods["tpu"]).tolist()) == [0, 1, 2, 8]
    assert (pods["gang"] != gen.PAD).sum() == 1704 == 4 * (pods["gang"].max() + 1)
    waves = ref.order_tried(pods, config["engine"]["waveWidth"])["idx"]
    assert waves.shape == (1292, 8) and (waves < 0).sum() == 336
    assert 1292 % config["engine"]["chunkWaves"] == 0
    reh = traffic["rehearse"]
    small = gen.generate(config, reh["nodes"], reh["tasks"], 3)["tasks"]
    assert len(ref.order_tried(small, 8)["idx"]) % reh["chunkWaves"] == 0
    assert reh["scenarios"] % traffic["chips"] == 0
    scen = whatif_scenarios.sample(config, 1000, 1024)
    n = {k: sum(bool(len(sc[k])) for sc in scen)
         for k in ("down", "scaled", "tainted")}
    assert n == {"down": 15, "scaled": 319, "tainted": 106}
    assert not any(len(scen[0][k]) for k in n)


def test_names_units_and_files():
    b = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = {w["name"]: w for w in b["workloads"]}[CELL]
    conf = {c["name"]: c for c in b["configs"]}[CONFIG]
    config = json.loads((ROOT / conf["file"]).read_text())
    traffic = json.loads((BENCH / "traffic" / f"{TRAFFIC}.json").read_text())
    assert cell["config"] == CONFIG and cell["traffic"] == TRAFFIC
    assert conf["reduced"] == config["reduced"] == []
    assert conf["source"] == config["source"] and len(conf["source"]) <= 200
    assert "BASELINE.json config 5" in conf["source"]
    assert "examples/config5_multitenant_mesh.yaml" in conf["source"]
    assert cell["chips"] == 4 == traffic["chips"]
    assert len(cell["why"]) <= 200 >= len(conf["why"])
    assert NAME.match(cell["name"]) and NAME.match(cell["traffic"])
    assert NAME.match(conf["name"])
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert set(conf) == {"name", "source", "file", "reduced", "why"}
    # of the benchmark's cells at most half, rounded down, ask for 4 chips,
    # and one always may
    four = sum(w["chips"] == 4 for w in b["workloads"])
    assert four <= max(1, len(b["workloads"]) // 2)
    for kind, name in (("generators", config["generator"]),
                       ("references", config["reference"]),
                       ("engines", traffic["engine"])):
        assert (BENCH / kind / f"{name}.py").is_file()
    metrics = {m["name"]: m for m in b["per_layer"]}
    for name in NEW_METRICS:
        m = metrics[name]
        assert NAME.match(name) and UNIT.match(m["unit"])
        assert m["workloads"] == [CELL] and m["moves"] == "placements_per_s"
        assert m["layer"] == "scenario mesh" and m["source"] == "device_trace"
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves",
                          "workloads"}
        assert (BENCH / "layer_metrics" / f"{name}.py").is_file()
    # the six metrics with no list are read in this cell too
    assert all("workloads" not in metrics[name] for name in LISTLESS)
    # what was there before this cell keeps its place; this cell's entries
    # follow, and a later PR's follow these
    names = [m["name"] for m in b["per_layer"]]
    at = names.index(NEW_METRICS[0])
    assert names[at - 2:at] == EARLIER_LAST_METRICS
    assert names[at:at + len(NEW_METRICS)] == list(NEW_METRICS)
    assert [w["name"] for w in b["workloads"]][:4] == EARLIER_CELLS + [CELL]
    assert [c["name"] for c in b["configs"]][:4] == EARLIER_CONFIGS + [CONFIG]


def made_up_trace():
    """One traced batch, 0..3000 us, on four chips: two executions of the
    chunk program of two waves each on every chip, chip c starting 10 c us
    later and chip 3 running 40 us longer, then the hand-back program, 50
    us on chips 0-2 and 80 us on chip 3."""
    us = 1000
    devices = []
    for c in range(4):
        modules, ops = [], []
        for start in (100 + 10 * c, 400 + 10 * c):
            d = 200 + (40 if c == 3 else 0)
            modules.append(["jit_per_scenario_src(7)", start * us, d * us])
            ops.append(["%fusion.1 = f32[2,8,64]{2,1,0} fusion(%a, %b)",
                        start * us, d * us])
        d = 80 if c == 3 else 50
        modules.append(["jit_whatif_handback(9)", 800 * us, d * us])
        ops.append(["%fusion.2 = s32[2,32]{1,0} fusion(%p)", 800 * us, d * us])
        devices.append({"modules": modules, "ops": ops, "dropped": []})
    return {"devices": devices, "host": [["bench:batch:0", 0, 3000 * us]]}


def read_all(events):
    ctx = {"trace": trace_reduce.Reduced(events), "device_kind": "TPU v5 lite",
           "shape": {"scenarios_per_chip": 2, "nodes": 64, "resources": 4,
                     "wave_width": 8, "chunk_waves": 2, "planes": 1},
           "spans": {"encode_s": 0.5}, "compile": {"compile_s": 1.0, "lower_s": 0.5}}
    return {m: run.load_part("layer_metrics", m).read(ctx)
            for m in NEW_METRICS + LISTLESS}


def test_the_new_readers_on_a_made_up_trace():
    got = read_all(made_up_trace())
    # chip 3 is the last to start its first chunk program, at 130 us
    assert got["mesh_stage_ms_per_batch"] == pytest.approx(0.130)
    # chip 3's last chunk program ends last, at 430 + 240 = 670 us
    assert got["mesh_handback_ms_per_batch"] == pytest.approx(3.0 - 0.670)
    # the earliest end is chip 0's, 600 us: 70 us of a 3000 us batch
    assert got["mesh_device_skew_share"] == pytest.approx(100 * 70 / 3000)
    # a chip's 2 scenarios x 32 slots, 2 bytes read and 4 written each,
    # over the slowest chip's 80 us
    assert roofline_whatif_arrivals.handback_bytes(
        scenarios=2, slots=32, nodes=64) == 2 * 32 * 6
    assert got["mesh_handback_roofline"] == pytest.approx(
        100 * (2 * 32 * 6 / 819e9) / 80e-6)
    assert 0 < got["mesh_handback_roofline"] < 100
    # the six metrics with no list read a value under four device planes
    assert all(got[name] is not None for name in LISTLESS)
    assert got["chunk_ms_per_wave"] == pytest.approx((0.4 * 3 + 0.48) / 4 / 4)
    assert 0 < got["chunk_roofline"] < 100 and 0 < got["device_idle_share"] < 100


def test_the_new_readers_read_nothing_where_a_chip_ran_nothing():
    """A tree that runs the batch on one chip of the four (no mesh), or one
    that puts the placements into task order on the host: None, no raise."""
    one_chip = made_up_trace()
    for dev in one_chip["devices"][1:]:
        dev["modules"], dev["ops"] = [], []
    assert {m: read_all(one_chip)[m] for m in NEW_METRICS} == dict.fromkeys(
        NEW_METRICS)
    host_side = made_up_trace()
    for dev in host_side["devices"]:
        dev["modules"].pop()
    got = read_all(host_side)
    assert got["mesh_handback_roofline"] is None
    assert got["mesh_stage_ms_per_batch"] == pytest.approx(0.130)


class Stub:
    engine, release_path, chunk_waves = "v3", None, 66

    def __init__(self, *args, mesh=None, **kw):
        self.mesh = mesh
        vars(self).update(self.fault)

    def run(self):
        raise AssertionError("a batch ran")


@pytest.mark.parametrize("fault, says", [
    ({"engine": "v2"}, "fell back to 'v2'"),
    ({"release_path": "device"}, "releases on the 'device' path"),
    ({"chunk_waves": 8}, "a chunk of 8 waves"),
    ({"mesh": None}, "mesh holds 0 devices"),
])
def test_the_adapter_refuses_another_program_before_any_batch(
        monkeypatch, fault, says):
    """A v2 fallback, a release path, another chunk than the
    configuration's, or an engine that dropped its mesh: refused when the
    engine is built, and no batch runs."""
    from kubernetes_simulator_tpu.sim import whatif

    config, traffic, gen, _ = parts()
    trace = gen.generate(config, 64, 128, 1)
    ec, ep = gen.to_program(trace, config)
    adapter = run.load_part("engines", traffic["engine"])
    sound = adapter.Engine(ec, ep, config, traffic, 66)
    assert sound.engine.release_path is None
    assert sound.engine.mesh.devices.size == 4 == traffic["chips"]
    assert sound.scenarios_per_chip * 4 == traffic["rehearse"]["scenarios"]
    monkeypatch.setattr(Stub, "fault", fault, raising=False)
    monkeypatch.setattr(whatif, "WhatIfEngine", Stub)
    with pytest.raises(RuntimeError, match=says):
        adapter.Engine(ec, ep, config, traffic, 66)


def test_the_adapter_refuses_too_few_devices_and_a_count_that_does_not_divide(
        monkeypatch):
    """Fewer devices than the cell's chips: refused with the XLA_FLAGS line
    that gives a CPU rehearsal four; a scenario count that does not divide
    over them: refused."""
    import jax

    config, traffic, gen, _ = parts()
    trace = gen.generate(config, 64, 128, 1)
    ec, ep = gen.to_program(trace, config)
    adapter = run.load_part("engines", traffic["engine"])
    odd = {**traffic, "rehearse": {**traffic["rehearse"], "scenarios": 6}}
    with pytest.raises(RuntimeError, match="do not divide over 4"):
        adapter.Engine(ec, ep, config, odd, 66)
    have = jax.devices()
    monkeypatch.setattr(jax, "devices", lambda *a: have[:2])
    with pytest.raises(RuntimeError,
                       match="xla_force_host_platform_device_count=4"):
        adapter.Engine(ec, ep, config, traffic, 66)
