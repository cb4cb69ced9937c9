"""The multi-tenant accelerator deployment on a device mesh
(``multitenant-1k-mesh``, BASELINE config 5): the program held to the
benchmark's plain reference pod for pod in every scenario (extended resource
in the fit, gangs rolled back whole), the meshed run held to the unmeshed
one, which ties a device's share to the whole, and what a meshed
``WhatIfEngine.run()`` reports: nothing compiled after the first, phases that
cover the call, the ``summary()["mesh"]`` counters and the ``mesh_put`` /
``mesh_fetch`` spans. On the CPU's virtual devices (conftest gives eight; the
cell uses four)."""

import sys
import time
from pathlib import Path

import jax
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "benchmark")]

import run as bench  # noqa: E402
import whatif_scenarios  # noqa: E402

from kubernetes_simulator_tpu.framework.framework import FrameworkConfig  # noqa: E402
from kubernetes_simulator_tpu.models.encode import PAD  # noqa: E402
from kubernetes_simulator_tpu.parallel.mesh import make_mesh  # noqa: E402
from kubernetes_simulator_tpu.sim import whatif as W  # noqa: E402
from kubernetes_simulator_tpu.sim.telemetry import PHASE_NAMES  # noqa: E402

CELL = "multitenant-mesh4"
NODES, PODS, SCENARIOS, CHUNK = 64, 1024, 8, 33


def cell():
    _, _, config, traffic = bench.load_cell(CELL)
    return (config, traffic,
            bench.load_part("generators", config["generator"]),
            bench.load_part("references", config["reference"]))


def engine(ec, ep, config, mesh):
    adapter = bench.load_part("engines", "whatif")
    scen = adapter.program_scenarios(
        config, whatif_scenarios.sample(config, NODES, SCENARIOS))
    return W.WhatIfEngine(ec, ep, scen, FrameworkConfig(), wave_width=8,
                          chunk_waves=CHUNK, mesh=mesh,
                          collect_assignments=True, telemetry="summary")


@pytest.fixture(scope="module")
def batch():
    """Eight scenarios of 64 nodes (14 with the device plugin) taking 1,024
    pods, 2 scenarios a device over a mesh of 4, arrivals only, placements
    asked for: the accelerators and then cpu run out, gangs are rolled back."""
    config, _, gen, _ = cell()
    trace = gen.generate(config, NODES, PODS, 11)
    ec, ep = gen.to_program(trace, config)
    eng = engine(ec, ep, config, make_mesh(4))
    assert eng.waves.idx.shape[0] % CHUNK == 0
    return config, trace, ec, ep, eng, eng.run()


def test_every_scenario_is_the_references_pod_for_pod(batch):
    """Fit over all four resources, the taint filter, LeastAllocated over cpu
    and memory, a gang with a member that fits nowhere rolled back at the end
    of its wave: the reference's whole-trace schedule on each scenario's own
    node table is the program's, and both mechanisms decide something."""
    config, trace, _, ep, eng, res = batch
    *_, ref = cell()
    pods, weights = trace["tasks"], config["scheduler"]["weights"]
    assert eng.engine == "v3" and eng.release_path is None
    assert ep.requests.shape[1] == 4 and eng.spec.has_gangs
    scen = whatif_scenarios.sample(config, NODES, SCENARIOS)
    assert all(any(len(sc[k]) for sc in scen) for k in ("down", "scaled", "tainted"))
    for s, sc in enumerate(scen):
        nodes = ref.node_table(trace["nodes"], sc)
        ours = ref.schedule(nodes, pods, 8, weights)
        np.testing.assert_array_equal(ours, res.assignments[s], err_msg=str(s))
        assert int(res.placed[s]) == (ours >= 0).sum()
        assert ref.over_allocatable(nodes, pods, ours) == (0, 0)
        assert ref.gangs_partly_bound(pods, ours) == 0
    base = ref.node_table(trace["nodes"], scen[0])
    got = res.assignments[0]
    gang, tpu = pods["gang"], pods["tpu"]
    rolled = np.unique(gang[(gang != PAD) & (got < 0)])
    assert len(rolled) >= 5 and (got[np.isin(gang, rolled)] < 0).all()
    assert ((got < 0) & (tpu > 0)).sum() > 50 < ((got >= 0) & (tpu > 0)).sum()
    assert (base["tpu"][got[(got >= 0) & (tpu > 0)]] > 0).all()
    for without in ({"extended": False}, {"gang": False}):
        assert (ref.schedule(base, pods, 8, weights, **without) != got).any()


def test_the_meshed_run_is_the_unmeshed_run_pod_for_pod(batch):
    """A device's share is the whole's: the same eight scenarios on one
    device, no mesh, give every pod of every scenario the same node."""
    config, _, ec, ep, _, res = batch
    whole = engine(ec, ep, config, None).run()
    np.testing.assert_array_equal(whole.assignments, res.assignments)
    np.testing.assert_array_equal(whole.placed, res.placed)
    np.testing.assert_array_equal(whole.unschedulable, res.unschedulable)
    assert "mesh" not in whole.fleet_telemetry.summary()


def test_later_meshed_runs_compile_nothing_and_phases_cover_the_call():
    """``collect_assignments=True`` on the arrivals-only branch under a mesh:
    after the first ``run()`` none compiles anything (the sharded state
    broadcast, the chunk program under ``shard_map``, utilization, the
    hand-back, the gather and the count), each answers the same, and the
    phases cover the call: the best of five. At the rehearsal's nodes and
    scenarios with four times its pods: some 0.4 ms of a call lie outside
    every phase at any size (building the result), 6% of the rehearsal's
    7 ms run on the CPU and nothing of a batch on the chip."""
    config, traffic, gen, _ = cell()
    trace = gen.generate(config, NODES, 4 * traffic["rehearse"]["tasks"], 11)
    ec, ep = gen.to_program(trace, config)
    eng = engine(ec, ep, config, make_mesh(4))
    eng.chunk_waves = CHUNK
    first = eng.run()
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, dur, **kw: compiles.append(name)
        if name == "/jax/core/compile/backend_compile_duration" else None)
    shares = []
    for _ in range(5):
        t = time.perf_counter()
        res = eng.run()
        wall = time.perf_counter() - t
        phases = res.fleet_telemetry.summary()["phases"]
        assert {k.split("/")[-1] for k in phases} <= set(PHASE_NAMES)
        assert {"stage", "dispatch", "device_wait", "gather", "handback"} <= {
            k.split("/")[-1] for k in phases}
        shares.append(sum(phases.values()) / wall)
        np.testing.assert_array_equal(res.assignments, first.assignments)
    assert not compiles
    assert max(shares) >= 0.95, shares


def test_the_summary_counts_what_crosses_to_the_devices_and_back(batch):
    """``summary()["mesh"]``: the devices and the scenarios each holds; the
    bytes put on the devices (at the engine's first run only: the scenario
    tables sharded, the one initial state and the chunks' indices replicated,
    all static and kept) and fetched from them (every pod's node, once); no
    collective in the chunk or the hand-back program and the one all-gather
    of the gather program, read once."""
    config, _, ec, ep, eng, first = batch
    got = first.fleet_telemetry.summary()["mesh"]
    assert got["devices"] == 4 and got["scenarios_per_device"] == 2
    assert got["collectives"] == {"chunk": 0, "handback": 0, "gather": 1}
    assert got["fetch_bytes"] == first.assignments.nbytes == 8 * PODS * 4
    tables = W.tree_bytes(eng.sset.dc)
    idx = 4 * eng.waves.idx.size * 4
    assert got["put_bytes"] > tables + idx
    again = eng.run().fleet_telemetry.summary()["mesh"]
    assert again["collectives"] == got["collectives"]
    # nothing of it changes from batch to batch (no fork checkpoint): the
    # tables, the one initial state and the indices are kept on the devices
    assert again["put_bytes"] == 0 == again["put_s"]
    assert again["fetch_bytes"] == got["fetch_bytes"]
    assert got["put_s"] > 0 and got["fetch_s"] > 0 and again["fetch_s"] > 0
    assert eng._mesh_programs is None  # read once, then let go


def test_the_mesh_spans_nest_in_stage_and_handback(batch, monkeypatch, tmp_path):
    """With profiling armed an engine's first batch writes ``mesh_put``
    spans inside ``stage`` (later batches put nothing) and every batch one
    ``mesh_fetch`` span inside ``handback``."""
    config, _, ec, ep, _, _ = batch
    eng = engine(ec, ep, config, make_mesh(4))
    opened, stack = [], []

    class Span:
        def __init__(self, name, **counts):
            self.name, self.counts = name, counts

        def __enter__(self):
            opened.append((self.name, tuple(stack), self.counts))
            stack.append(self.name)

        def __exit__(self, *exc):
            stack.pop()

    # every span of the program is the factory's (profiling.make_span),
    # which takes jax.profiler.TraceAnnotation when armed
    monkeypatch.setenv("KSIM_PROFILE_DIR", str(tmp_path))
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Span)
    from kubernetes_simulator_tpu.utils import profiling

    monkeypatch.setattr(profiling, "register_call", lambda fn, args: None)
    monkeypatch.setattr(W, "_register_call", lambda fn, args: None)
    first = eng.run().fleet_telemetry.summary()["mesh"]
    inside = {name: {outer for n, outer, _ in opened if n == name}
              for name in ("mesh_put", "mesh_fetch")}
    assert inside["mesh_put"] == {("whatif_run:0", "stage")}
    assert inside["mesh_fetch"] == {("whatif_run:0", "handback")}
    assert not stack
    # each carries what it counts as the event's stats
    put = sum(c["bytes"] for n, _, c in opened if n == "mesh_put")
    fetch = [c for n, _, c in opened if n == "mesh_fetch"]
    assert put == first["put_bytes"] and fetch == [{"bytes": first["fetch_bytes"]}]
    del opened[:]
    eng.run()
    assert [n for n, *_ in opened if n.startswith("mesh_")] == ["mesh_fetch"]
    assert opened[0][:2] == ("whatif_run:1", ())


def test_a_new_scenario_batch_is_put_on_the_devices_again(batch):
    """``set_scenarios`` drops the kept tables: the next run shards the new
    batch's, and answers for it."""
    config, trace, ec, ep, eng, first = batch
    *_, ref = cell()
    adapter = bench.load_part("engines", "whatif")
    plain = whatif_scenarios.sample(config, NODES, SCENARIOS)[::-1]
    kept = eng._dc_mesh
    eng.set_scenarios(adapter.program_scenarios(config, plain))
    try:
        res = eng.run()
        assert eng._dc_mesh is not kept
        np.testing.assert_array_equal(res.assignments, first.assignments[::-1])
    finally:
        eng.set_scenarios(adapter.program_scenarios(config, plain[::-1]))


def test_the_example_file_is_the_deployment():
    """``examples/config5_multitenant_mesh.yaml`` as the CLI builds it is
    ``config5_multitenant()``, pods asking for ``google.com/tpu`` among them
    (the workload's ``extendedResource`` key), which the cell's generator
    copies; it runs meshed at the configuration's scenario count."""
    from kubernetes_simulator_tpu.sim.synthetic import config5_multitenant
    from kubernetes_simulator_tpu.utils.config import SimConfig, build_case

    cfg = SimConfig.load(str(ROOT / "examples" / "config5_multitenant_mesh.yaml"))
    cluster, pods = build_case(cfg)
    theirs, their_pods, _ = config5_multitenant()
    assert cluster.nodes == theirs.nodes and pods == their_pods
    assert sum("google.com/tpu" in p.requests for p in pods) == 1924
    config, traffic, *_ = cell()
    assert cfg.whatif.mesh and cfg.whatif.scenarios == traffic["scenarios"]
    assert (cfg.cluster.nodes, cfg.workload.pods) == (
        config["cluster"]["nodes"], config["workload"]["tasks"])
