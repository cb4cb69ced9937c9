"""The default-plugin-set what-if deployment (``k8s5k-default-plugins``):
the benchmark's plain reference held to the CPU event engine pod for pod
over the plugin combinations config 2 holds; what-if scenario 0 held to the
single replay; the counters a batch reports; the arrivals-only chunk
program's stage tables.

The event engine (``sim/runtime.py`` + ``framework/``) tries a pod again
after later binds and preempts by priority; the deployment does neither (one
cycle a pod, PostFilter off), so it runs here with preemption off, on traces
in which a pod that fails once fails for good (one can never fit; the count of
unschedulable pods is held to the reference's): its later tries change nothing.
"""

import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "benchmark")]

import run as bench  # noqa: E402

from kubernetes_simulator_tpu.framework.framework import FrameworkConfig  # noqa: E402
from kubernetes_simulator_tpu.models.encode import PAD  # noqa: E402
from kubernetes_simulator_tpu.ops import tpu3 as V3  # noqa: E402
from kubernetes_simulator_tpu.sim.jax_runtime import JaxReplayEngine  # noqa: E402
from kubernetes_simulator_tpu.sim.runtime import CpuReplayEngine  # noqa: E402
from kubernetes_simulator_tpu.sim.telemetry import PHASE_NAMES  # noqa: E402
from kubernetes_simulator_tpu.sim.whatif import WhatIfEngine  # noqa: E402
from kubernetes_simulator_tpu.utils import profiling  # noqa: E402

CELL = "k8s5k-whatif256"
OFF = {"affinityFraction": 0.0, "antiAffinityFraction": 0.0,
       "nodeAffinityFraction": 0.0, "spreadFraction": 0.0}
COMBINATIONS = {
    "affinity": {**OFF, "affinityFraction": 0.4},
    "anti": {**OFF, "antiAffinityFraction": 0.4},
    "spread-DoNotSchedule": {**OFF, "spreadFraction": 0.6,
                             "doNotScheduleFraction": 1.0},
    "spread-ScheduleAnyway": {**OFF, "spreadFraction": 0.6,
                              "doNotScheduleFraction": 0.0},
    "all": {},
}


def cell():
    _, _, config, _ = bench.load_cell(CELL)
    return (config, bench.load_part("generators", config["generator"]),
            bench.load_part("references", config["reference"]))


def case(nodes: int, pods: int, seed: int, workload=None, never_fits=None):
    """(config, trace, ec, ep): the cell's generator at a small size, the
    workload's fractions overridden; ``never_fits`` gives that pod a request
    no node holds."""
    config, gen, _ = cell()
    config = {**config, "workload": {**config["workload"], **(workload or {})}}
    trace = gen.generate(config, nodes, pods, seed)
    if never_fits is not None:
        trace["tasks"]["cpu"][never_fits] = 1000.0
    return (config, trace) + gen.to_program(trace, config)


def base_table(ref, trace):
    """The unperturbed cluster as the reference holds it."""
    return ref.node_table(trace["nodes"], {
        "down": [], "scaled": [], "factor": 1.0, "tainted": []})


@pytest.mark.parametrize("seed", [1, 2])
@pytest.mark.parametrize("combination", sorted(COMBINATIONS))
def test_the_reference_is_the_event_engine_pod_for_pod(combination, seed):
    config, trace, ec, ep = case(24, 400, seed, COMBINATIONS[combination],
                                 never_fits=200)
    _, _, ref = cell()
    ours = ref.schedule(base_table(ref, trace), trace["tasks"],
                        config["scheduler"]["weights"])
    theirs = CpuReplayEngine(
        ec, ep, FrameworkConfig(enable_preemption=False)).replay()
    np.testing.assert_array_equal(ours, theirs.assignments)
    assert ours[200] == PAD and theirs.unschedulable == (ours == PAD).sum()
    kind = trace["tasks"]["kind"]
    if combination in ("affinity", "anti", "all"):
        assert (kind == (2 if combination == "anti" else 1)).sum() > 20
    # the mechanism decides something: without it the reference parts
    if combination in ("affinity", "anti"):
        without = ref.schedule(base_table(ref, trace), trace["tasks"],
                               config["scheduler"]["weights"], interpod=False)
        assert (without != ours).any()


def test_a_full_cluster_leaves_pods_unschedulable_in_both():
    """More pods than the nodes hold, affinity and spread filters among
    them: the pods the reference cannot place are the device program's (one
    cycle a pod, as the what-if engine runs)."""
    config, trace, ec, ep = case(8, 600, 3)
    _, _, ref = cell()
    ours = ref.schedule(base_table(ref, trace), trace["tasks"],
                        config["scheduler"]["weights"])
    single = JaxReplayEngine(ec, ep, FrameworkConfig(), wave_width=8,
                             chunk_waves=16).replay()
    assert 0 < (ours == PAD).sum() < 400
    np.testing.assert_array_equal(ours, single.assignments)


@pytest.fixture(scope="module")
def batch():
    """Four scenarios of 136 nodes (hostname is then a host-scale topology,
    as at 5,000) taking 512 pods, arrivals only, placements asked for."""
    import whatif_scenarios

    config, trace, ec, ep = case(136, 512, 5)
    adapter = bench.load_part("engines", "whatif")
    scen = adapter.program_scenarios(
        config, whatif_scenarios.sample(config, 136, 4))
    eng = WhatIfEngine(ec, ep, scen, FrameworkConfig(), wave_width=8,
                       chunk_waves=16, collect_assignments=True,
                       telemetry="summary")
    return config, trace, ec, ep, eng, eng.run()


def test_scenario_0_is_the_single_replay(batch):
    _, _, ec, ep, eng, res = batch
    single = JaxReplayEngine(ec, ep, FrameworkConfig(), wave_width=8,
                             chunk_waves=16).replay()
    np.testing.assert_array_equal(res.assignments[0], single.assignments)
    assert int(res.placed[0]) == single.placed
    assert eng.release_path is None and eng.engine == "v3"


def test_every_scenario_is_the_references(batch):
    config, trace, _, _, _, res = batch
    import whatif_scenarios

    _, _, ref = cell()
    for s, sc in enumerate(whatif_scenarios.sample(config, 136, 4)):
        ours = ref.schedule(ref.node_table(trace["nodes"], sc), trace["tasks"],
                            config["scheduler"]["weights"])
        np.testing.assert_array_equal(ours, res.assignments[s], err_msg=str(s))


def test_the_summary_reports_the_count_planes_the_form_and_the_handback(batch):
    _, _, _, ep, eng, res = batch
    got = res.fleet_telemetry.summary()
    assert got["select_form"] == "two_pass"
    st = eng.static3
    assert got["count_planes"] == V3.count_planes(st, scenario_axis=True) == {
        "domain_rows": int((~st.is_host).sum()),
        "host_rows": len(st.mc_h_ids) + len(st.anti_h_ids),
        "dcap": 8, "spread_rows": 1, "term_rows": 4,
        "host_read_positions": 2, "expand_positions": 2,
        "host_commit": {
            "rows": len(st.mc_h_ids) + len(st.anti_h_ids), "elementwise": 0,
            "dot": 0}}
    assert got["count_planes"]["host_rows"] > 0 and st.has_host_rows
    assert got["handback_bytes"] == 4 * 4 * ep.num_pods == res.assignments.nbytes
    assert got["scenarios"] == 4 and got["chunk_waves"] == 16
    phases = {k.split("/")[-1] for k in got["phases"]}
    assert {"handback", "gather", "dispatch"} <= phases <= set(PHASE_NAMES)


def test_later_runs_compile_nothing_and_phases_cover_the_call(batch):
    import time

    import jax

    *_, eng, first = batch
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, dur, **kw: compiles.append(name)
        if name == "/jax/core/compile/backend_compile_duration" else None)
    t = time.perf_counter()
    res = eng.run()
    wall = time.perf_counter() - t
    assert not compiles
    np.testing.assert_array_equal(res.assignments, first.assignments)
    phases = res.fleet_telemetry.summary()["phases"]
    assert sum(phases.values()) >= 0.95 * wall


def test_stage_tables_of_the_arrivals_only_chunk_program(batch, tmp_path,
                                                        monkeypatch):
    *_, eng, _ = batch
    profiling._PROGRAMS.clear()
    monkeypatch.setenv("KSIM_PROFILE_DIR", str(tmp_path))
    eng.run()
    monkeypatch.delenv("KSIM_PROFILE_DIR")
    assert set(profiling._PROGRAMS) == {"jit_per_scenario_src"}
    table = profiling.stage_tables()["jit_per_scenario_src"]
    staged = set(table.values())
    assert {"ksim.filter_score/InterPodAffinity",
            "ksim.filter_score/PodTopologySpread",
            "ksim.corrections", "ksim.commit"} <= staged
    for path in ("ksim.filter_score/InterPodAffinity", "ksim.corrections",
                 "ksim.commit"):
        assert sum(v == path for v in table.values()) >= 3, path
    profiling._PROGRAMS.clear()


def test_another_deal_of_the_same_pods_finds_the_same_program(tmp_path,
                                                              monkeypatch):
    """Seeds deal one multiset of pods onto the arrival slots. What the
    pods' specs name (keys, values, expressions, count groups:
    models/encode.py ``_intern_sorted``) and the toleration and node-affinity
    classes (ops/tpu3.py ``_row_classes``) are numbered by what they are, not
    by the pod that names one first, so every deal lowers the chunk program
    to the same text: one executable and one compile-cache entry."""
    import whatif_scenarios

    vocabs = set()
    for seed in range(12):
        ec = case(136, 128, seed)[2]
        vocabs.add((tuple(ec.vocab.keys), tuple(ec.vocab.kvs),
                    tuple(ec.group_keys)))
    assert len(vocabs) == 1

    adapter = bench.load_part("engines", "whatif")
    monkeypatch.setenv("KSIM_PROFILE_DIR", str(tmp_path))
    texts, groups = [], []
    for seed in (5, 2147483700):
        config, trace, ec, ep = case(136, 128, seed)
        scen = adapter.program_scenarios(
            config, whatif_scenarios.sample(config, 136, 2))
        profiling._PROGRAMS.clear()
        WhatIfEngine(ec, ep, scen, FrameworkConfig(), wave_width=8,
                     chunk_waves=16, collect_assignments=True).run()
        texts.append(profiling._PROGRAMS["jit_per_scenario_src"]().as_text())
        groups.append(ec.group_keys)
    profiling._PROGRAMS.clear()
    assert not np.array_equal(*(t["tasks"]["app"] for t in (
        case(136, 128, 5)[1], case(136, 128, 2147483700)[1])))
    assert groups[0] == groups[1] and len(groups[0]) > 4
    assert texts[0] == texts[1]


def test_the_device_normalize_divides_exactly():
    """The score rows' float32 floordiv (ops.tpu.floor_div_f32) is the integer
    division on a grid of the sizes config 2 reaches, and its correction
    takes back a quotient that a division wrong in its last bits left one
    off, in either direction: the TPU's is (chip_smoke.py holds it there)."""
    import jax.numpy as jnp

    from kubernetes_simulator_tpu.ops import tpu as T

    a, b = np.meshgrid(np.arange(0, 60000, 7), np.arange(1, 700), indexing="ij")
    af, bf = jnp.asarray(a, jnp.float32), jnp.asarray(b, jnp.float32)
    want = a // b
    np.testing.assert_array_equal(np.asarray(T.floor_div_f32(af, bf)), want)
    for off in (-1.0, 1.0):
        got = T._fix_quotient(jnp.asarray(want + off, jnp.float32), af, bf)
        np.testing.assert_array_equal(np.asarray(got), want)
    w = jnp.arange(1, 100, dtype=jnp.float32)
    for x in np.asarray(w):
        row = T._normalize_row(jnp.asarray([x, 0.0]), None, x, None,
                               minmax=False, reverse=False)
        assert row.tolist() == [100.0, 0.0]
