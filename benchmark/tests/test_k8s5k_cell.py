"""The default-plugin-set what-if cell's own tests, on the CPU, run by hand
like their siblings:

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_k8s5k_cell.py -q
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

import roofline_whatif_arrivals  # noqa: E402
import run  # noqa: E402
import trace_reduce  # noqa: E402
import whatif_scenarios  # noqa: E402
from kubernetes_simulator_tpu.utils import profiling  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELL = "k8s5k-whatif256"
CONFIG = "k8s5k-default-plugins"
STAGE_METRICS = ("chunk_interpod_affinity_ms_per_wave",
                 "chunk_topology_spread_ms_per_wave",
                 "chunk_count_planes_ms_per_wave")
# The rest of a wave's op time, which the accepted stage metrics read in
# the replay cell alone: with STAGE_METRICS they tile it.
REST_METRICS = ("whatif_chunk_reads_ms_per_wave",
                "whatif_chunk_filter_score_rest_ms_per_wave",
                "whatif_chunk_select_ms_per_wave",
                "whatif_chunk_unattributed_share")
NEW_METRICS = STAGE_METRICS + ("whatif_arrivals_handback_ms_per_batch",
                               "whatif_arrivals_handback_roofline"
                               ) + REST_METRICS
TERM_ROWS = ("ref.anti_affinity_terms_broken", "ref.zone_affinity_terms_broken",
             "ref.spread_skew_terms_broken")
# What BENCHMARK.json held before this cell, in its order: a later PR appends.
EARLIER_CELLS = ["borg10k-replay1", "borg10k-whatif128"]
EARLIER_CONFIGS = ["borg2019-10k-gangs", "borg2019-10k-whatif"]
EARLIER_LAST_METRICS = ["whatif_release_ms_per_boundary",
                        "whatif_release_roofline", "whatif_release_share",
                        "whatif_handback_ms_per_batch"]


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
    assert [checks[row] for row in TERM_ROWS] == [0, 0, 0]


@pytest.mark.parametrize("control, least", [
    ("bf16", 0.05), ("unperturbed", 0.1), ("no-interpod", 0.015),
    ("no-spread", 0.015)])
def test_a_control_is_not_correct(monkeypatch, capsys, control, least):
    """The reference in bfloat16 in the program's place; the reference of
    scenario 0 in every scenario's place, which only fails if the check sees
    the perturbations; the reference without InterPodAffinity, which only
    fails if the check sees pod affinity and anti-affinity; and the reference
    without the DoNotSchedule filter, which only fails if it sees that."""
    rc, checks, res = rehearse(monkeypatch, capsys, "--control", control)
    assert rc == 0 and res["correct"] is False
    assert checks["ref.choices_not_the_references_share"] > least
    # nothing else fails: the share is the limit that catches it
    assert checks["ref.placements_on_down_or_injected_taint_nodes"] == 0
    assert [checks[row] for row in TERM_ROWS] == [0, 0, 0]


def test_the_rows_over_every_placement_see_each_term_broken():
    """Placements made without a filter break the term that filter guards,
    and only that: the reference's own whole-trace schedule with
    InterPodAffinity left out (hostname pairs, affinity pods alone in a
    zone), with the DoNotSchedule filter left out (a zone over maxSkew),
    and sound (nothing). The sampled pods lean on the pods under a term."""
    config, _, gen = parts()
    ref = run.load_part("references", config["reference"])
    trace = gen.generate(config, 64, 1024, 5)
    nodes = ref.node_table(trace["nodes"], whatif_scenarios.sample(
        config, 64, 1)[0])
    pods, weights = trace["tasks"], config["scheduler"]["weights"]
    seq, _ = ref.order_tried(pods)
    sound = ref.schedule(nodes, pods, weights)
    assert ref.terms_broken(nodes, pods, seq, sound) == (0, 0, 0)
    hostname, zone, skew = ref.terms_broken(
        nodes, pods, seq, ref.schedule(nodes, pods, weights, interpod=False))
    assert hostname > 0 and zone > 0 and skew == 0
    hostname, zone, skew = ref.terms_broken(
        nodes, pods, seq, ref.schedule(nodes, pods, weights, spread=False))
    assert (hostname, zone) == (0, 0) and skew > 0
    # an affinity pod moved to a zone that held none of its app's pods then
    zone_of = lambda k: nodes["zone"][sound[k]]
    for at, k in enumerate(seq.tolist()):
        earlier = seq[:at][(pods["app"][seq[:at]] == pods["app"][k])
                           & (sound[seq[:at]] >= 0)]
        free = np.setdiff1d(np.arange(nodes["zones"]), zone_of(earlier))
        if (pods["kind"][k] == ref.AFFINITY and sound[k] >= 0
                and earlier.size and free.size):
            break
    moved = sound.copy()
    moved[k] = int(np.nonzero(nodes["zone"] == free[0])[0][0])
    assert ref.terms_broken(nodes, pods, seq, moved)[1] >= 1
    pairs = ref.draw(np.random.default_rng(3), 4, seq, 0, pods)
    under_a_term = ((pods["kind"] == ref.AFFINITY) | (pods["kind"] == ref.ANTI)
                    | pods["leader"]
                    | ((pods["spread_skew"] > 0) & pods["spread_dns"]))
    assert len(pairs) >= 4 * ref.PER_SCENARIO
    assert under_a_term[pairs[:, 1]].mean() > under_a_term.mean() + 0.15
    for s in range(4):
        mine = pairs[pairs[:, 0] == s, 1]
        assert (pods["kind"][mine] == ref.AFFINITY).sum() >= ref.PER_STRATUM
        assert (pods["kind"][mine] == ref.ANTI).sum() >= ref.PER_STRATUM


def parts():
    _, _, config, traffic = run.load_cell(CELL)
    return config, traffic, run.load_part("generators", config["generator"])


def test_every_seed_gets_the_same_work_in_another_deal():
    """Two seeds: the same cluster, arrival times and multiset of pods, the
    pods on other arrival slots; the scenario set does not know the seed."""
    config, _, gen = parts()
    a = gen.generate(config, 64, 512, 1)
    b = gen.generate(config, 64, 512, 2147483700)
    for k, v in a["nodes"].items():
        assert np.array_equal(v, b["nodes"][k]), k
    assert np.array_equal(a["tasks"]["arrival"], b["tasks"]["arrival"])
    cols = [k for k in a["tasks"] if k != "arrival"]
    assert sorted(cols) == sorted(gen.DEALT)
    rows = lambda t: sorted(zip(*(t["tasks"][k].tolist() for k in cols)))
    assert rows(a) == rows(b)
    assert any(not np.array_equal(a["tasks"][k], b["tasks"][k]) for k in cols)
    again = gen.generate(config, 64, 512, 1)
    assert all(np.array_equal(a["tasks"][k], again["tasks"][k]) for k in cols)
    x = whatif_scenarios.sample(config, 64, 4)
    y = whatif_scenarios.sample(config, 64, 4)
    assert all(np.array_equal(p[k], q[k]) for p, q in zip(x, y)
               for k in ("down", "scaled", "tainted"))


def test_the_generator_draws_as_the_programs():
    """A copy, so that the traffic cannot move; today the two agree: the
    undealt columns are ``config2()``'s pods, object for object."""
    from kubernetes_simulator_tpu.sim.synthetic import config2

    config, _, gen = parts()
    wl = config["workload"]
    trace = {"nodes": gen.node_table(40, wl["baseSeed"], config["cluster"]),
             "tasks": gen.pod_columns(600, wl["baseSeed"], wl)}
    cluster, pods = gen.program_objects(trace, config)
    theirs_cluster, theirs, _ = config2(40, 600, wl["baseSeed"])
    assert cluster.nodes == theirs_cluster.nodes
    assert pods == theirs
    kinds = np.bincount(trace["tasks"]["kind"], minlength=4)
    assert kinds.min() > 20 and trace["tasks"]["spread_dns"].sum() > 20


def test_the_deployment_at_the_cells_size():
    """Nothing is cut: 5,000 nodes, 50,000 pods in 6,250 full waves that the
    chunk divides, 256 scenarios with every kind of perturbation."""
    config, traffic, _ = parts()
    assert config["reduced"] == [] and config["cluster"]["nodes"] == 5000
    assert run.sizes(config, traffic, False) == {
        "nodes": 5000, "tasks": 50000, "chunkWaves": 625}
    waves = 50000 // config["engine"]["waveWidth"]
    assert waves % config["engine"]["chunkWaves"] == 0
    assert traffic["scenarios"] == 256 == config["scenarios"]["perChip"]
    scen = whatif_scenarios.sample(config, 5000, 256)
    n = {k: sum(bool(len(sc[k])) for sc in scen)
         for k in ("down", "scaled", "tainted")}
    assert n == {"down": 7, "scaled": 83, "tainted": 38}
    assert not any(len(scen[0][k]) for k in n)


def test_names_units_and_files():
    b = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = {w["name"]: w for w in b["workloads"]}[CELL]
    conf = {c["name"]: c for c in b["configs"]}[CONFIG]
    config = json.loads((ROOT / conf["file"]).read_text())
    assert cell["config"] == CONFIG and cell["traffic"] == "whatif-256"
    assert conf["reduced"] == config["reduced"] == []
    assert conf["source"] == config["source"] and len(conf["source"]) <= 200
    assert cell["chips"] == 1 and len(cell["why"]) <= 200 >= len(conf["why"])
    assert NAME.match(cell["name"]) and NAME.match(cell["traffic"])
    assert NAME.match(conf["name"])
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert set(conf) == {"name", "source", "file", "reduced", "why"}
    for kind, name in (("generators", config["generator"]),
                       ("references", config["reference"]),
                       ("engines", "whatif_arrivals")):
        assert (BENCH / kind / f"{name}.py").is_file()
    assert (BENCH / "traffic" / "whatif-256.json").is_file()
    metrics = {m["name"]: m for m in b["per_layer"]}
    for name in NEW_METRICS:
        m = metrics[name]
        assert NAME.match(name) and UNIT.match(m["unit"])
        assert m["workloads"] == [CELL] and m["moves"] == "placements_per_s"
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves",
                          "workloads"}
        assert (BENCH / "layer_metrics" / f"{name}.py").is_file()
    # what was there before this cell keeps its place; this cell's entries
    # follow, and a later PR's follow these
    names = [m["name"] for m in b["per_layer"]]
    at = names.index(NEW_METRICS[0])
    assert names[at - 4:at] == EARLIER_LAST_METRICS
    assert names[at:at + len(NEW_METRICS)] == list(NEW_METRICS)
    assert [w["name"] for w in b["workloads"]][:3] == EARLIER_CELLS + [CELL]
    assert [c["name"] for c in b["configs"]][:3] == EARLIER_CONFIGS + [CONFIG]


TABLE = {"jit_per_scenario_src": {
    "fusion.1": "ksim.reads", "fusion.2": "ksim.corrections",
    "fusion.3": "ksim.filter_score/InterPodAffinity",
    "fusion.4": "ksim.filter_score/PodTopologySpread",
    "fusion.5": "ksim.filter_score/NodeResourcesFit",
    "fusion.6": "ksim.select", "fusion.7": "ksim.commit",
}}


def made_up_trace():
    """One traced batch, 0..2000 us: two executions of the arrivals-only
    chunk program of two waves each, then utilization and the hand-back."""
    us = 1000
    body = [("fusion.1", 10), ("fusion.2", 20), ("fusion.3", 40),
            ("fusion.4", 30), ("fusion.5", 12), ("fusion.6", 15),
            ("fusion.7", 8)]
    modules, ops = [], []
    for start in (100, 400):
        modules.append(["jit_per_scenario_src(7)", start * us, 200 * us])
        t = start + 10
        ops.append(["%while.3 = (s32[], f32[3,64]) while(%tuple.1)",
                    t * us, 150 * us])
        for name, d in body:
            ops.append([f"%{name} = f32[4,8,64]{{2,1,0}} fusion(%a, %b)",
                        t * us, d * us])
            t += d + 1
    for name, start, d in (("jit__util(3)", 700, 5),
                           ("jit_whatif_handback(9)", 800, 50)):
        modules.append([name, start * us, d * us])
        ops.append(["%fusion.1 = s32[4,32]{1,0} fusion(%p)", start * us, d * us])
    return {"devices": [{"modules": modules, "ops": ops, "dropped": []}],
            "host": [["bench:batch:0", 0, 2000 * us]]}


def read_all(events):
    ctx = {"trace": trace_reduce.Reduced(events), "device_kind": "TPU v5 lite",
           "shape": {"scenarios_per_chip": 4, "nodes": 64, "resources": 3,
                     "wave_width": 8, "chunk_waves": 2}}
    return {m: run.load_part("layer_metrics", m).read(ctx) for m in NEW_METRICS}


def test_the_new_readers_on_a_made_up_trace(monkeypatch):
    monkeypatch.setattr(profiling, "stage_tables", lambda: TABLE, raising=False)
    got = read_all(made_up_trace())
    assert got["chunk_interpod_affinity_ms_per_wave"] == pytest.approx(2 * 0.040 / 4)
    assert got["chunk_topology_spread_ms_per_wave"] == pytest.approx(2 * 0.030 / 4)
    assert got["chunk_count_planes_ms_per_wave"] == pytest.approx(2 * 0.028 / 4)
    assert got["whatif_chunk_reads_ms_per_wave"] == pytest.approx(2 * 0.010 / 4)
    assert got["whatif_chunk_filter_score_rest_ms_per_wave"] == pytest.approx(
        2 * 0.012 / 4)
    assert got["whatif_chunk_select_ms_per_wave"] == pytest.approx(2 * 0.015 / 4)
    assert got["whatif_chunk_unattributed_share"] == 0.0
    # the seven stage metrics tile the op time of a wave
    assert sum(got[m] for m in STAGE_METRICS + REST_METRICS[:3]
               ) == pytest.approx(2 * 0.135 / 4)
    # from the end of the last chunk program (600 us) to the span's end
    assert got["whatif_arrivals_handback_ms_per_batch"] == pytest.approx(1.4)
    # 4 scenarios x 32 slots, 2 bytes read and 4 written each, in 50 us
    assert roofline_whatif_arrivals.handback_bytes(
        scenarios=4, slots=32, nodes=64) == 4 * 32 * 6
    assert got["whatif_arrivals_handback_roofline"] == pytest.approx(
        100 * (4 * 32 * 6 / 819e9) / 50e-6)
    assert 0 < got["whatif_arrivals_handback_roofline"] < 100


def test_the_new_readers_read_nothing_on_an_empty_trace(monkeypatch):
    """No chunk program ran (and a tree without stage tables): None, no
    raise; a tree that un-permutes on the host gives the roofline nothing."""
    monkeypatch.delattr(profiling, "stage_tables", raising=False)
    events = made_up_trace()
    assert read_all(events)["chunk_interpod_affinity_ms_per_wave"] is None
    host_side = made_up_trace()
    host_side["devices"][0]["modules"].pop()
    assert read_all(host_side)["whatif_arrivals_handback_roofline"] is None
    events["devices"][0]["modules"] = []
    assert read_all(events) == dict.fromkeys(NEW_METRICS)


@pytest.mark.parametrize("fault, says", [
    ({"engine": "v2"}, "fell back to 'v2'"),
    ({"release_path": "device"}, "releases on the 'device' path"),
    ({"release_path": "host"}, "releases on the 'host' path"),
    ({"chunk_waves": 8}, "a chunk of 8 waves"),
])
def test_the_adapter_refuses_another_program_before_any_batch(
        monkeypatch, fault, says):
    """A v2 fallback, a release path, or another chunk than the
    configuration's: refused when the engine is built, and no batch runs."""
    from kubernetes_simulator_tpu.sim import whatif

    class Stub:
        engine, release_path, chunk_waves = "v3", None, 16

        def __init__(self, *args, **kw):
            vars(self).update(fault)

        def run(self):
            raise AssertionError("a batch ran")

    config, traffic, gen = parts()
    trace = gen.generate(config, 64, 128, 1)
    ec, ep = gen.to_program(trace, config)
    adapter = run.load_part("engines", traffic["engine"])
    assert adapter.Engine(ec, ep, config, traffic, 16).engine.release_path is None
    monkeypatch.setattr(whatif, "WhatIfEngine", Stub)
    with pytest.raises(RuntimeError, match=says):
        adapter.Engine(ec, ep, config, traffic, 16)
