"""The gang-scheduled training cell's own tests, on the CPU, run by hand like
their siblings (tier-1 imports them through ``tests/test_benchmark_cases.py``):

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_gangs_cell.py -q
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

import roofline_gang  # noqa: E402
import run  # noqa: E402
import trace_reduce  # noqa: E402
import whatif_scenarios  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELL = "pai1800-whatif256"
CONFIG = "pai2020-1800-gangs"
TRAFFIC = "whatif-256-gangs"
NEW_METRICS = ("gang_txn_ms_per_wave", "gang_rollback_roofline",
               "gang_handback_ms_per_batch")
# the accepted readers of the cell's other layers, which list their cells and
# cannot be edited: each under a name of this cell's own
ALSO_HERE = ("chunk_select_ms_per_wave", "chunk_filter_score_ms_per_wave",
             "chunk_corrections_ms_per_wave", "chunk_commit_ms_per_wave",
             "chunk_reads_ms_per_wave", "chunk_unattributed_share",
             "idle_unattributed_share", "host_untraced_share",
             "host_stage_ms_per_batch", "host_dispatch_ms_per_batch",
             "host_gather_ms_per_batch")
LISTLESS = ("encode_s", "compile_s", "chunk_gap_ms", "chunk_ms_per_wave",
            "chunk_roofline", "device_idle_share")
# the rows over every placement of every scenario, limit 0
FULL_ROWS = ("ref.placements_on_down_or_injected_taint_nodes",
             "ref.nodes_over_allocatable_cpu_memory_pods",
             "ref.nodes_over_allocatable_extended_resource",
             "ref.pod_groups_partly_bound", "ref.placed_differs_from_answers_max")
# What BENCHMARK.json held before this cell, in its order: a later PR appends.
EARLIER_CELLS = ["borg10k-replay1", "borg10k-whatif128", "k8s5k-whatif256",
                 "multitenant-mesh4"]
EARLIER_CONFIGS = ["borg2019-10k-gangs", "borg2019-10k-whatif",
                   "k8s5k-default-plugins", "multitenant-1k-mesh"]
EARLIER_LAST_METRICS = ["host_handback_ms_per_batch", "mesh_fetch_ms_per_batch"]


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


def small(seed=7, scenario=0):
    """The rehearsal's trace and one scenario's node table."""
    config, traffic, gen, ref = parts()
    reh = traffic["rehearse"]
    trace = gen.generate(config, reh["nodes"], reh["tasks"], seed)
    scen = whatif_scenarios.sample(config, reh["nodes"], reh["scenarios"])
    return (config, gen, ref, trace, scen,
            ref.node_table(trace["nodes"], scen[scenario]))


def test_the_rehearsal_is_correct(monkeypatch, capsys):
    rc, checks, res = rehearse(monkeypatch, capsys)
    assert rc == 0 and res["correct"] is True and res["attempted"] >= 2
    assert set(res["metrics"]) == {"placements_per_s", "setup_s"}
    assert checks["ref.choices_not_the_references_share"] == 0.0
    assert checks["window.compiles"] == 0
    assert [checks[row] for row in FULL_ROWS] == [0] * len(FULL_ROWS)
    assert checks["ref.wide_groups_rolled_back"] >= 8 * 8
    assert checks["ref.wide_group_members_compared"] > 1000


@pytest.mark.parametrize("control, least", [
    ("bf16", 0.05), ("unperturbed", 0.15), ("no-gang", 0.02),
    ("wave-local-gang", 0.006)])
def test_a_control_is_not_correct(monkeypatch, capsys, control, least):
    """The reference in bfloat16 in the program's place; the reference of
    scenario 0 in every scenario's place; the reference without any rollback
    (a member stands or falls alone); and the reference that judges a wide
    group wave by wave, which only fails if the check sees that a group is
    rolled back WHOLE, the members of its clean waves too."""
    rc, checks, res = rehearse(monkeypatch, capsys, "--control", control)
    assert rc == 0 and res["correct"] is False
    assert checks["ref.choices_not_the_references_share"] > least
    # nothing over the whole batch fails: those rows read the program's
    # answers, and the share is the limit that catches a control
    assert [checks[row] for row in FULL_ROWS] == [0] * len(FULL_ROWS)


@functools.lru_cache(maxsize=None)
def program_answers():
    """The rehearsal-size trace through both device engines, the normal
    path: 8 scenarios in one ``WhatIfEngine.run()``, and the single replay.
    (Cached, not a fixture: tier-1 imports this file's cases by name.)"""
    from kubernetes_simulator_tpu.framework.framework import FrameworkConfig
    from kubernetes_simulator_tpu.sim.jax_runtime import JaxReplayEngine

    config, traffic, gen, ref = parts()
    reh = traffic["rehearse"]
    trace = gen.generate(config, reh["nodes"], reh["tasks"], 11)
    ec, ep = gen.to_program(trace, config)
    adapter = run.load_part("engines", traffic["engine"])
    engine = adapter.Engine(ec, ep, config, traffic, reh["chunkWaves"])
    result = engine.batch()
    single = JaxReplayEngine(ec, ep, FrameworkConfig(), wave_width=8,
                             chunk_waves=reh["chunkWaves"]).replay()
    return config, ref, trace, engine.answers(result), result, single


def test_the_program_is_the_reference_pod_for_pod():
    """Every pod's node in each of the 8 scenarios is the one the plain
    reference's whole-trace schedule gives on that scenario's own cluster
    (on the CPU the two float32 chains agree to the bit), the single replay
    is scenario 0, and the counters are the reference's counts."""
    config, ref, trace, answers, result, single = program_answers()
    pods, weights = trace["tasks"], config["scheduler"]["weights"]
    scen = whatif_scenarios.sample(config, len(trace["nodes"]["cpu"]), 8)
    rolled = undone = 0
    for s in range(8):
        stats = {}
        want = ref.schedule(ref.node_table(trace["nodes"], scen[s]), pods, 8,
                            weights, stats=stats)
        np.testing.assert_array_equal(answers["assignments"][s], want)
        rolled += stats["rolled_back"]
        undone += stats["binds_undone"]
    np.testing.assert_array_equal(single.assignments, answers["assignments"][0])
    gangs = result.fleet_telemetry.summary()["gangs"]
    order = ref.order_tried(pods, 8)
    assert gangs["wide_groups"] == int(order["wide"].sum())
    assert gangs["max_group"] == 64 and gangs["max_waves_spanned"] == 8
    assert gangs["rollback_form"] == "txn_plane"
    assert (gangs["wide_rolled_back"], gangs["pods_rolled_back"]) == (rolled, undone)
    assert rolled >= 8 * 8 and undone >= 8 * 50
    # different scenarios, different answers: the perturbations are seen
    assert len({a.tobytes() for a in answers["assignments"]}) >= 6


def test_the_rows_over_every_placement_see_the_rollback_left_out():
    """The reference's own whole-trace schedule with no rollback, and with a
    wide group judged wave by wave, leaves pod groups partly bound; the rule
    leaves none; nothing is over its allocatable either way. A wide group in
    its clean waves is what tells the last two apart."""
    config, gen, ref, trace, scen, nodes = small()
    pods, weights = trace["tasks"], config["scheduler"]["weights"]
    rows = lambda a: (*ref.over_allocatable(nodes, pods, a),
                      ref.gangs_partly_bound(pods, a))
    stats = {}
    sound = ref.schedule(nodes, pods, 8, weights, stats=stats)
    assert rows(sound) == (0, 0, 0)
    assert stats["rolled_back"] >= 8 and stats["rolled_back_after_a_bind"] >= 2
    assert stats["undone_in_waves_without_a_failure"] >= 16
    assert rows(ref.schedule(nodes, pods, 8, weights, gang="none"))[:2] == (0, 0)
    assert ref.gangs_partly_bound(
        pods, ref.schedule(nodes, pods, 8, weights, gang="none")) > 0
    local = ref.schedule(nodes, pods, 8, weights, gang="wave-local")
    assert rows(local)[:2] == (0, 0) and rows(local)[2] > 0
    order = ref.order_tried(pods, 8)
    members, bound = ref.groups_bound(pods, local)
    partly = (bound > 0) & (bound < members)
    assert partly[order["wide"]].sum() > 0 and partly[~order["wide"]].sum() == 0
    # every size of the job mix is there, a group of every width class
    assert {2, 4, 8, 16, 32, 64} <= set(np.unique(order["size"]).tolist())
    # a wide group starts on a wave's first slot and its waves are consecutive
    for g in np.nonzero(order["wide"])[0]:
        mine = np.nonzero(pods["gang"] == g)[0]
        waves = order["wave"][mine]
        assert order["idx"][waves.min(), 0] == mine[0]
        assert waves.max() - waves.min() + 1 == -(-len(mine) // 8)


def test_the_sample_leans_on_the_transaction():
    """Every scenario gives its last pod and a pod from each stratum: wide
    groups in their first, a middle and their last wave, a member of a
    rolled-back wide group, a GPU pod, the pod right after a rolled-back
    wide group, and, where a rolled-back wide group bound a whole wave before
    a member failed, members of such waves (the binds that only a carried
    transaction gives back), found by the reference's own rebuild."""
    config, ref, trace, answers, _, _ = program_answers()
    pods = trace["tasks"]
    order = ref.order_tried(pods, 8)
    assigns = np.asarray(answers["assignments"], np.int64)
    scen = whatif_scenarios.sample(config, len(trace["nodes"]["cpu"]), len(assigns))
    tables = [ref.node_table(trace["nodes"], sc) for sc in scen]
    wholes = [ref.whole_waves_bound(tables[s], pods, order, assigns[s],
                                    ref.groups_bound(pods, assigns[s])[1] == 0)
              for s in range(len(assigns))]
    pairs = ref.draw(np.random.default_rng(3), order, pods, assigns, 0, wholes)
    whole_seen = 0
    g = pods["gang"]
    gi = np.clip(g, 0, None)
    in_wide = (g != ref.PAD) & order["wide"][gi]
    rank = np.empty(len(g), np.int64)
    rank[order["seq"]] = np.arange(len(g))
    for s in range(8):
        mine = pairs[pairs[:, 0] == s, 1]
        w, a, b = order["wave"][mine], order["first"][gi[mine]], order["last"][gi[mine]]
        wide = in_wide[mine]
        assert (wide & (w == a)).any() and (wide & (w == b)).any()
        assert (wide & (w > a) & (w < b)).any()
        assert (pods["gpu"][mine] > 0).any() and order["seq"][-1] in mine
        _, bound = ref.groups_bound(pods, assigns[s])
        rolled = in_wide & (bound[gi] == 0)
        assert rolled[mine].any()
        ends = order["last_rank"][order["wide"] & (bound == 0)]
        assert np.isin(rank[mine] - 1, ends).any()
        # the stratum of whole waves: where it says a member's wave held no
        # failure, the wave-local control calls that member's "unplaced" wrong
        whole = wholes[s]
        assert not (whole & ~rolled).any()
        picked = mine[whole[mine]]
        assert len(picked) == min(ref.PER_WHOLE_WAVE, whole.sum()) or whole.sum() > len(picked) >= ref.PER_WHOLE_WAVE
        for k in picked:
            assert ref.judge_at(tables[s], pods, order, assigns[s], bound == 0,
                                int(k), config["scheduler"]["weights"],
                                "wave-local-gang") == 100.0
        whole_seen += len(picked)
    assert whole_seen >= 8


def test_a_rolled_back_group_is_rebuilt_by_the_references_own_picks():
    """Teacher-forced on the reference's own schedule every pod is sound: the
    members of rolled-back groups in every wave of theirs (the state is
    rebuilt from the group's first wave), the pod right after one (it sees
    the usage given back) and the pods behind one in its closing wave. A
    group rolled back though every member fitted is called wrong; so is one
    left partly bound; ``no-gang`` calls a rolled-back member that fits by
    itself wrong, ``wave-local-gang`` one whose own wave held no failure."""
    config, gen, ref, trace, scen, nodes = small(seed=7, scenario=1)
    pods, weights = trace["tasks"], config["scheduler"]["weights"]
    order = ref.order_tried(pods, 8)
    assign = ref.schedule(nodes, pods, 8, weights)
    members, bound = ref.groups_bound(pods, assign)
    rolled_g = bound == 0
    g = pods["gang"]
    gi = np.clip(g, 0, None)
    judge = lambda k, a=assign, r=rolled_g, c=None: ref.judge_at(
        nodes, pods, order, a, r, int(k), weights, c)
    everyone = [judge(k) for k in range(len(g))]
    assert everyone == [0.0] * len(g)
    wide_rolled = np.nonzero((g != ref.PAD) & order["wide"][gi] & rolled_g[gi])[0]
    assert len(wide_rolled) >= 100
    alone = [judge(k, c="no-gang") for k in wide_rolled]
    local = [judge(k, c="wave-local-gang") for k in wide_rolled]
    assert 0 < sum(x == 100.0 for x in local) <= sum(x == 100.0 for x in alone)
    assert sum(x == 100.0 for x in alone) < len(alone)  # some fit nowhere
    # roll back a wide group that was bound whole: every member fitted
    whole = next(q for q in np.nonzero(order["wide"])[0] if bound[q] == members[q])
    wrong = assign.copy()
    wrong[g == whole] = ref.PAD
    _, b2 = ref.groups_bound(pods, wrong)
    k = int(np.nonzero(g == whole)[0][9])  # a member of its second wave
    assert judge(k, wrong, b2 == 0) == 100.0
    # leave a rolled-back wide group partly bound, as a wave-local program
    # would: the row over all placements sees it
    local_answers = ref.schedule(nodes, pods, 8, weights, gang="wave-local")
    assert ref.gangs_partly_bound(pods, local_answers) > 0


def test_every_seed_gets_the_same_work_in_another_deal():
    """Two seeds: the same cluster, arrival times, job layout and GPU asks,
    and every pod's cpu and memory up to ``workload.dealFrom`` (scaled to the
    trace), where scenario 0 has bound its last GPU; behind it the (cpu,
    memory) pairs of the pods that ask for no GPU are dealt among them (the
    scheduler reads them), and the labels nothing reads among all such pods;
    the same waves; the scenario set does not know the seed."""
    config, _, gen, ref = parts()
    wl = config["workload"]
    a = gen.generate(config, 64, 512, 1)
    b = gen.generate(config, 64, 512, 2147483700)
    for k, v in a["nodes"].items():
        assert np.array_equal(v, b["nodes"][k]), k
    for k in ("arrival", "gang", "gpu"):
        assert np.array_equal(a["tasks"][k], b["tasks"][k])
    assert sorted(set(a["tasks"]) - {"arrival", "gang", "gpu"}) == sorted(
        gen.DEALT + gen.DEALT_LATE)
    asking = a["tasks"]["gpu"] > 0  # a GPU pod keeps its slot whole
    assert asking.sum() > 50
    early = np.arange(512) < wl["dealFrom"] * 512 // wl["tasks"]
    assert 100 < early.sum() < 400
    for cols, kept in ((gen.DEALT, asking), (gen.DEALT_LATE, asking | early)):
        assert all(np.array_equal(a["tasks"][k][kept], b["tasks"][k][kept])
                   for k in cols)
        rows = lambda t: sorted(zip(*(t["tasks"][k][~kept].tolist() for k in cols)))
        assert rows(a) == rows(b)
        assert any(not np.array_equal(a["tasks"][k], b["tasks"][k]) for k in cols)
    again = gen.generate(config, 64, 512, 1)
    assert all(np.array_equal(a["tasks"][k], again["tasks"][k]) for k in a["tasks"])
    assert np.array_equal(ref.order_tried(a["tasks"], 8)["idx"],
                          ref.order_tried(b["tasks"], 8)["idx"])


def test_what_a_seed_deals_moves_choices_and_not_what_is_placed():
    """At the cell's size, scenario 0, two seeds: the pods up to ``dealFrom``
    go where they went (no GPU is bound behind it, so the same pod groups
    start whole and the same pods are placed), and behind it the dealt cpu
    and memory send most pods to other nodes: the seed is read."""
    config, _, gen, ref = parts()
    wl, weights = config["workload"], config["scheduler"]["weights"]
    got = []
    for seed in (3, 2147483900):
        trace = gen.generate(config, 1800, wl["tasks"], seed)
        nodes = ref.node_table(trace["nodes"],
                               whatif_scenarios.sample(config, 1800, 1)[0])
        got.append((trace["tasks"], ref.schedule(nodes, trace["tasks"], 8, weights)))
    (ta, a), (tb, b) = got
    cut = wl["dealFrom"]
    assert np.array_equal(a[:cut], b[:cut])
    assert ((a >= 0) & (ta["gpu"] > 0))[cut:].sum() == 0
    assert ((a >= 0) & (ta["gpu"] > 0))[cut - 1]  # the last GPU bind
    assert np.array_equal(a >= 0, b >= 0)
    assert (a >= 0).sum() == config["counts"]["placed"]
    assert (a[cut:] != b[cut:]).mean() > 0.5


def test_the_generator_draws_as_the_programs():
    """A copy, so that the traffic cannot move; today the two agree: the
    undealt columns are ``make_cluster`` / ``make_job_workload``'s nodes and
    pods, object for object, as ``examples/config8_gpu_jobs_gangs.yaml``
    spells them for the CLI. The program numbers resources and gangs as the
    trace does."""
    import yaml

    from kubernetes_simulator_tpu.utils.config import SimConfig, build_case

    config, _, gen, _ = parts()
    wl, cl = config["workload"], config["cluster"]
    doc = yaml.safe_load((ROOT / "examples/config8_gpu_jobs_gangs.yaml").read_text())
    assert doc["cluster"]["synthetic"]["nodes"] == cl["nodes"]
    assert doc["workload"]["synthetic"]["pods"] == wl["tasks"]
    assert doc["waveWidth"] == config["engine"]["waveWidth"]
    assert doc["chunkWaves"] == config["engine"]["chunkWaves"]
    assert doc["whatIf"]["scenarios"] == config["scenarios"]["deployed"]
    sizes = {int(k): v for k, v in wl["jobSizes"].items()}
    assert doc["workload"]["synthetic"]["gangSizes"] == sizes
    jx = doc["workload"]["synthetic"]["jobExtendedResource"]
    assert {**jx, "counts": {str(k): v for k, v in jx["counts"].items()}} == wl["gpuJobs"]
    doc["cluster"]["synthetic"]["nodes"] = 40
    doc["workload"]["synthetic"]["pods"] = 600
    ext = doc["cluster"]["synthetic"]["extendedResources"]
    acc = cl["accelerator"]
    assert ext == {acc["resource"]: [acc["count"], round(acc["fraction"] * cl["nodes"])]}
    ext[acc["resource"]][1] = round(acc["fraction"] * 40)
    theirs_cluster, theirs = build_case(SimConfig.from_dict(doc))
    trace = {"nodes": gen.node_table(40, wl["baseSeed"], cl),
             "tasks": gen.pod_columns(600, wl["baseSeed"], wl)}
    cluster, pods = gen.program_objects(trace, config)
    assert cluster.nodes == theirs_cluster.nodes
    assert pods == theirs
    t = trace["tasks"]
    assert (t["gpu"] > 0).sum() > 60 and (t["gang"] != gen.PAD).sum() > 300
    ec, ep = gen.to_program(trace, config)
    assert list(ec.vocab.resources) == config["resources"]
    assert np.array_equal(np.asarray(ep.group_id), t["gang"])


def test_the_deployment_at_the_cells_size():
    """1,800 nodes, 810 with 8 nvidia.com/gpu, 32,768 pods (the one cut:
    ``tasks``) in 4,668 waves, 256 scenarios on one chip; and the counts the
    configuration's file records for scenario 0 are the reference's, inside
    the four ranges ISSUE 37 set."""
    config, traffic, gen, ref = parts()
    b = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = {w["name"]: w for w in b["workloads"]}[CELL]
    assert config["reduced"] == ["tasks"] and config["architecture"] is None
    assert run.sizes(config, traffic, False) == {
        "nodes": 1800, "tasks": 32768, "chunkWaves": 1167}
    assert cell["chips"] == config["scenarios"]["chips"] == 1
    assert traffic["scenarios"] == 256 == config["scenarios"]["deployed"]
    assert config["resources"] == ["cpu", "memory", "pods", "nvidia.com/gpu"]
    trace = gen.generate(config, 1800, 32768, 3)
    nodes = ref.node_table(trace["nodes"],
                           whatif_scenarios.sample(config, 1800, 1)[0])
    got = ref.deployment_counts(nodes, trace["tasks"], 8,
                                config["scheduler"]["weights"])
    c = config["counts"]
    assert {k: c[k] for k in got} == got
    assert (trace["nodes"]["gpu"] > 0).sum() == c["gpu_nodes"] == 810
    assert c["gpus_held"] == 8 * 810 and c["waves"] == 4668
    # four chunks of 1,167 pad no wave, and a wide group crosses a chunk edge
    chunk = config["engine"]["chunkWaves"]
    assert c["waves"] == 4 * chunk
    order = ref.order_tried(trace["tasks"], 8)
    crossing = order["wide"] & (order["first"] // chunk != order["last"] // chunk)
    assert crossing.sum() == 1
    # ISSUE 37's job mix, one share moved (config "assumed")
    g = config["workload"]["gpuJobs"]
    assert (g["wideFrom"], g["wideJobFraction"], g["smallJobFraction"]) == (8, 0.5, 0.015)
    assert c["pods_in_wide_groups"] >= c["pods"] / 2  # (a)
    assert 2 <= c["gpus_asked"] / c["gpus_held"] <= 3.5  # (b)
    assert 0.15 <= c["wide_rolled_back"] / c["wide_groups"] <= 0.45  # (c)
    assert c["wide_rolled_back"] >= 100  # (d)
    assert c["wide_rolled_back_after_a_bind"] >= 30
    assert sorted(c["group_sizes"]) == sorted(["2", "4", "8", "12", "16", "32", "64"])
    reh = traffic["rehearse"]
    tiny = gen.generate(config, reh["nodes"], reh["tasks"], 3)["tasks"]
    assert len(ref.order_tried(tiny, 8)["idx"]) % reh["chunkWaves"] == 0
    scen = whatif_scenarios.sample(config, 1800, 256)
    n = {k: sum(bool(len(sc[k])) for sc in scen)
         for k in ("down", "scaled", "tainted")}
    assert min(n.values()) >= 3 and not any(len(scen[0][k]) for k in n)


def test_names_units_and_files():
    b = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = {w["name"]: w for w in b["workloads"]}[CELL]
    conf = {c["name"]: c for c in b["configs"]}[CONFIG]
    config = json.loads((ROOT / conf["file"]).read_text())
    traffic = json.loads((BENCH / "traffic" / f"{TRAFFIC}.json").read_text())
    assert cell["config"] == CONFIG and cell["traffic"] == TRAFFIC
    assert conf["reduced"] == config["reduced"] == ["tasks"]
    assert conf["source"] == config["source"] and len(conf["source"]) <= 200
    assert "cluster-trace-gpu-v2020" in conf["source"]
    assert cell["chips"] == 1 and traffic["engine"] == "whatif_arrivals"
    assert len(cell["why"]) <= 200 >= len(conf["why"])
    assert NAME.match(cell["name"]) and NAME.match(cell["traffic"])
    assert NAME.match(conf["name"])
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert set(conf) == {"name", "source", "file", "reduced", "why"}
    for kind, name in (("generators", config["generator"]),
                       ("references", config["reference"]),
                       ("engines", traffic["engine"])):
        assert (BENCH / kind / f"{name}.py").is_file()
    # the reference imports nothing of the program
    text = (BENCH / "references" / f"{config['reference']}.py").read_text()
    assert "kubernetes_simulator_tpu" not in text and "import jax" not in text
    metrics = {m["name"]: m for m in b["per_layer"]}
    for name in NEW_METRICS:
        m = metrics[name]
        assert NAME.match(name) and UNIT.match(m["unit"])
        assert m["workloads"] == [CELL] and m["moves"] == "placements_per_s"
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves",
                          "workloads"}
        assert (BENCH / "layer_metrics" / f"{name}.py").is_file()
    assert metrics["gang_rollback_roofline"]["unit"] == "%"
    for name in ALSO_HERE:
        m, was = metrics[f"gang_{name}"], metrics[name]
        assert CELL not in was["workloads"] and m["workloads"] == [CELL]
        assert {k: v for k, v in m.items() if k not in ("name", "workloads")} == {
            k: v for k, v in was.items() if k not in ("name", "workloads")}
        read = run.load_part("layer_metrics", f"gang_{name}").read
        assert read.__module__ == f"layer_metrics.{name}"  # the accepted reader
    assert all("workloads" not in metrics[name] for name in LISTLESS)
    # what was there before this cell keeps its place; this cell's entries
    # follow, and a later PR's follow these
    names = [m["name"] for m in b["per_layer"]]
    at = names.index(NEW_METRICS[0])
    assert names[at - 2:at] == EARLIER_LAST_METRICS
    assert names[at:] == list(NEW_METRICS) + [f"gang_{n}" for n in ALSO_HERE]
    assert [w["name"] for w in b["workloads"]][:5] == EARLIER_CELLS + [CELL]
    assert [c["name"] for c in b["configs"]][:5] == EARLIER_CONFIGS + [CONFIG]


def made_up_ctx(stages=True, spans=True):
    """One traced batch, 0..3000 us, on one chip: two executions of the chunk
    program, the stage pass's result as if 0.5 ms of them lay under
    ``ksim.gang_txn`` and 20 ms under ``ksim.gang_rollback``, and the
    program's ``handback`` span of 5 ms."""
    us = 1000
    modules = [["jit_per_scenario_src(7)", s * us, 1000 * us] for s in (100, 1400)]
    ops = [["%fusion.1 = f32[2,8,64]{2,1,0} fusion(%a, %b)", s * us, 1000 * us]
           for s in (100, 1400)]
    events = {"devices": [{"modules": modules, "ops": ops, "dropped": []}],
              "host": [["bench:batch:0", 0, 3000 * us]]}
    ctx = {"trace": trace_reduce.Reduced(events), "device_kind": "TPU v5 lite",
           "shape": {"scenarios_per_chip": 256, "nodes": 1800, "resources": 4,
                     "wave_width": 8, "chunk_waves": 1167, "planes": 1},
           "spans": {"encode_s": 0.5}, "compile": {"compile_s": 1.0, "lower_s": 0.5}}
    seconds = {"ksim.commit": 1e-3}
    if stages:
        seconds.update({"ksim.gang_txn": 0.5e-3, "ksim.gang_rollback": 20e-3})
    ctx["stage_seconds"] = {"waves": 2 * 1167, "ops": {}, "seconds": seconds}
    ctx["program_spans"] = {"batches": [{"children": [
        ["handback", 0, 5_000_000, 1, {}]] if spans else []}]}
    return ctx


def test_the_new_readers_on_a_made_up_trace():
    config = parts()[0]
    ctx = made_up_ctx()
    got = {m: run.load_part("layer_metrics", m).read(ctx) for m in NEW_METRICS}
    assert got["gang_txn_ms_per_wave"] == pytest.approx(20.5 / 2334)
    assert got["gang_handback_ms_per_batch"] == pytest.approx(5.0)
    c = config["counts"]
    closing = 2334 * c["closing_waves"] / c["waves"]
    # used and the group's plane, read and written once each; no list of binds
    least_bytes = 256 * 4 * 4 * 1800 * 4
    assert roofline_gang.rollback_bytes(
        scenarios=256, nodes=1800, resources=4) == least_bytes
    assert got["gang_rollback_roofline"] == pytest.approx(
        100 * (least_bytes / 819e9) / (20.5e-3 / closing))
    assert 0 < got["gang_rollback_roofline"] < 100
    # the counts are those of the listed cell that has the run's shape
    other = made_up_ctx()
    other["shape"]["nodes"] = 1000
    assert run.load_part("layer_metrics", "gang_rollback_roofline").read(other) is None
    # the six metrics with no list read a value in this cell's shape
    listless = {m: run.load_part("layer_metrics", m).read(ctx) for m in LISTLESS}
    assert all(v is not None for v in listless.values())
    assert listless["chunk_roofline"] > 0  # (made-up times: no share)


def test_the_new_readers_read_nothing_from_a_tree_without_the_scopes():
    """The parent: no ``ksim.gang_*`` scope in its stage tables, no root
    span, no stage tables at all: None, no raise."""
    ctx = made_up_ctx(stages=False, spans=False)
    assert {m: run.load_part("layer_metrics", m).read(ctx)
            for m in NEW_METRICS} == dict.fromkeys(NEW_METRICS)
    ctx["stage_seconds"] = ctx["program_spans"] = None
    assert {m: run.load_part("layer_metrics", m).read(ctx)
            for m in NEW_METRICS} == dict.fromkeys(NEW_METRICS)
