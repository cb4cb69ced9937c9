"""Simulator-as-a-service (round 22): resident engines serving batched
multi-tenant what-if queries.

The serving contract under test:

- **Bit-parity by construction** — a batched multi-tenant defrag query
  must answer byte-identically to a fresh one-off S=1 engine run of the
  SAME synthesized scenario (base-state perturbations + drain/recover
  timeline), including the per-scenario telemetry series. The service's
  ``query_scenario``/``base_scenario`` are the single source of truth
  shared with the oracles here.
- **Warm queries recompile nothing** — the pool engine's compiled-
  executable count stays pinned at 1 across batches (the same
  ``_chunk_fn._cache_size()`` pin the round-9 tuner uses), and
  ``api.Simulator.what_if`` reuses its resident engine the same way.
- **Bad input never tears down the pool** — a torn/malformed NDJSON
  line becomes a structured ``query-error`` row and the loop keeps
  serving; everything emitted validates as schema v7.
"""

import io
import json
import os
import sys

import numpy as np
import pytest

from kubernetes_simulator_tpu.framework.framework import FrameworkConfig
from kubernetes_simulator_tpu.models.core import Cluster, Node, Pod
from kubernetes_simulator_tpu.models.encode import encode
from kubernetes_simulator_tpu.sim.jax_runtime import compiled_cache_size
from kubernetes_simulator_tpu.sim.service import (
    QueryService,
    max_engines_cap,
    serve_lines,
)
from kubernetes_simulator_tpu.sim.whatif import (
    Perturbation,
    Scenario,
    WhatIfEngine,
)

_SCRIPTS = os.path.join(os.path.dirname(__file__), "..", "scripts")
sys.path.insert(0, os.path.abspath(_SCRIPTS))

from check_metrics_schema import validate_file  # noqa: E402

FIT_ONLY = lambda: FrameworkConfig(plugins=[{"name": "NodeResourcesFit"}])

# Queue-trivial shape (the documented parity envelope, as in
# test_chaos._light_trace but smaller): strictly-increasing integer
# arrivals, load that fits even with the drained nodes down.
ENGINE_KW = dict(wave_width=1, chunk_waves=1)


def _tiny_trace(num_pods=12, num_nodes=4):
    nodes = [Node(f"n{i}", {"cpu": 8.0}) for i in range(num_nodes)]
    pods = [
        Pod(f"p{i}", requests={"cpu": 1.0}, arrival_time=float(i),
            duration=30.0)
        for i in range(num_pods)
    ]
    return encode(Cluster(nodes=nodes), pods)


def _service(ec, ep, **kw):
    kw.setdefault("max_batch", 3)
    kw.setdefault("batch_deadline_s", 0.05)
    kw.setdefault("retry_buffer", 64)
    return QueryService(ec, ep, FIT_ONLY(), **kw, **ENGINE_KW)


class _ListWriter:
    def __init__(self):
        self.rows = []

    def write(self, row):
        self.rows.append(dict(row))


# ---------------------------------------------------------------------------
# admission / validation (no engine builds — cheap)


def test_parse_query_refusals():
    ec, ep = _tiny_trace(num_pods=2, num_nodes=2)
    svc = _service(ec, ep)
    with pytest.raises(ValueError, match="unknown query family"):
        svc.parse_query({"op": "repack", "nodes": [0]})
    with pytest.raises(ValueError, match="JSON object"):
        svc.parse_query(["defrag"])
    with pytest.raises(ValueError, match="nodes"):
        svc.parse_query({"op": "defrag"})
    with pytest.raises(ValueError, match="out of range"):
        svc.parse_query({"op": "defrag", "nodes": [99]})
    with pytest.raises(ValueError, match="unknown node name"):
        svc.parse_query({"op": "defrag", "nodes": ["nope"]})
    with pytest.raises(ValueError, match="drainAt"):
        svc.parse_query({"op": "defrag", "nodes": [0], "drainAt": -1.0})
    with pytest.raises(ValueError, match="recoverAt"):
        svc.parse_query(
            {"op": "defrag", "nodes": [0], "drainAt": 5.0, "recoverAt": 5.0}
        )
    with pytest.raises(ValueError, match="granularity"):
        svc.parse_query(
            {"op": "defrag", "nodes": [0], "granularity": "verbose"}
        )
    # Node names resolve, dedupe, and sort — the synthesized timeline is
    # deterministic regardless of request order.
    dq = svc.parse_query({"op": "defrag", "nodes": ["n1", 0, 1],
                          "drainAt": 5.0})
    assert dq.nodes == [0, 1]
    assert dq.tenant == "default" and dq.qid  # auto id
    # Duplicate in-flight ids are refused at submit.
    svc.submit({"op": "defrag", "tenant": "a", "id": "q1", "nodes": [0],
                "drainAt": 5.0})
    with pytest.raises(ValueError, match="duplicate query id"):
        svc.submit({"op": "defrag", "tenant": "a", "id": "q1",
                    "nodes": [1], "drainAt": 5.0})


def test_ctor_refusals_and_engine_cap():
    ec, ep = _tiny_trace(num_pods=2, num_nodes=2)
    with pytest.raises(ValueError, match="max_batch"):
        QueryService(ec, ep, FIT_ONLY(), max_batch=0)
    with pytest.raises(ValueError, match="batch_deadline_s"):
        QueryService(ec, ep, FIT_ONLY(), batch_deadline_s=0.0)
    with pytest.raises(ValueError, match="retry_buffer"):
        QueryService(ec, ep, FIT_ONLY(), retry_buffer=0)
    assert max_engines_cap(4) == 4
    os.environ["KSIM_SERVICE_MAX_ENGINES"] = "2"
    try:
        assert max_engines_cap(4) == 2  # operator env beats config
        assert _service(ec, ep, max_engines=8).max_engines == 2
    finally:
        del os.environ["KSIM_SERVICE_MAX_ENGINES"]


def test_base_state_mirror():
    """bind/release/evict deltas surface as synthesized scale_capacity
    perturbations — never a trace rebuild."""
    ec, ep = _tiny_trace(num_pods=2, num_nodes=3)
    svc = _service(ec, ep)
    assert svc.base_perturbations() == []
    svc.apply_bind("b1", "n0", {"cpu": 2.0})
    svc.apply_bind("b2", 0, {"cpu": 2.0})
    svc.apply_bind("b3", 1, {"cpu": 4.0})
    perts = svc.base_perturbations()
    assert [int(p.nodes[0]) for p in perts] == [0, 1]
    assert all(p.op == "scale_capacity" and p.resource == "cpu"
               for p in perts)
    # n0: 4 of 8 cpu committed -> factor 0.5; n1: 4 of 8 -> 0.5.
    assert perts[0].factor == pytest.approx(0.5)
    assert perts[1].factor == pytest.approx(0.5)
    assert svc.base_state() == {"binds": 3, "nodes_used": 2}
    svc.apply_release("b2")
    assert svc.base_perturbations()[0].factor == pytest.approx(0.75)
    assert svc.apply_evict("n1") == ["b3"]  # insertion order
    perts = svc.base_perturbations()
    assert len(perts) == 1 and int(perts[0].nodes[0]) == 0
    with pytest.raises(ValueError, match="already active"):
        svc.apply_bind("b1", 0, {"cpu": 1.0})
    with pytest.raises(ValueError, match="unknown bind"):
        svc.apply_release("b2")
    with pytest.raises(ValueError, match="unknown resource"):
        svc.apply_bind("b9", 0, {"unobtainium": 1.0})


def test_validate_config_refusals():
    from kubernetes_simulator_tpu.cli import _service_errors, validate_config
    from kubernetes_simulator_tpu.utils.config import SimConfig

    ok = SimConfig.from_dict({
        "strategy": "jax", "devicePreemption": "kube",
        "whatIf": {"retryBuffer": 64},
        "service": {"maxBatch": 2, "batchDeadlineS": 0.1,
                    "granularity": "series"},
    })
    assert _service_errors(ok) == []
    assert ok.service.max_batch == 2
    assert ok.service.batch_deadline_s == pytest.approx(0.1)
    bad = SimConfig.from_dict({
        "strategy": "jax", "devicePreemption": "kube",
        "whatIf": {"retryBuffer": 64},
        "service": {"batchDeadlineS": 0, "maxEngines": 0,
                    "granularity": "verbose"},
    })
    errs = "\n".join(_service_errors(bad))
    assert "batchDeadlineS: must be > 0" in errs
    assert "maxEngines" in errs
    assert "granularity" in errs
    # The kube-mirror requirement: defrag drains ride chaos eviction.
    no_kube = SimConfig.from_dict({"strategy": "jax", "service": {}})
    errs = "\n".join(_service_errors(no_kube))
    assert "devicePreemption: kube" in errs and "retryBuffer" in errs
    # And the section rides the full validate_config chain.
    assert any("service" in e for e in validate_config(bad))
    # A config without the section stays untouched.
    assert _service_errors(SimConfig.from_dict({"strategy": "jax"})) == []


# ---------------------------------------------------------------------------
# serving parity + warm path (engine builds — the expensive half)


def test_batched_multitenant_parity_bitmatch():
    """Satellite 3 + tentpole acceptance: K coalesced defrag queries from
    multiple tenants — on a LIVE base state, at series telemetry — answer
    byte-identically to K sequential one-off S=1 engines running the same
    synthesized scenarios."""
    ec, ep = _tiny_trace()
    svc = _service(ec, ep, granularity="series")
    svc.apply_bind("web-1", 0, {"cpu": 3.0})
    svc.apply_bind("web-2", 2, {"cpu": 2.0})
    wire = [
        {"op": "defrag", "tenant": "team-a", "id": "q1", "nodes": [3],
         "drainAt": 4.0, "recoverAt": 12.0},
        {"op": "defrag", "tenant": "team-b", "id": "q1", "nodes": [0, 1],
         "drainAt": 2.0},
        {"op": "defrag", "tenant": "team-a", "id": "q2", "nodes": ["n2"],
         "drainAt": 6.0, "recoverAt": 20.0},
    ]
    # Oracle scenarios BEFORE submit (same base state; parse_query is
    # side-effect-free on the mirror).
    oracle_scens = [svc.query_scenario(svc.parse_query(dict(q)))
                    for q in wire]
    for q in wire:
        svc.submit(q)  # 3rd submit fills max_batch=3 -> auto-flush
    rows_a = svc.poll("team-a")
    rows_b = svc.poll("team-b")
    assert [r["query"] for r in rows_a] == ["q1", "q2"]
    assert [r["query"] for r in rows_b] == ["q1"]
    by_wire = [rows_a[0], rows_b[0], rows_a[1]]
    for row in by_wire:
        assert row["warm"] is False and row["batch"] == 1
        assert row["batch_occupancy"] == 1.0
    for row, scen in zip(by_wire, oracle_scens):
        one = WhatIfEngine(
            ec, ep, [scen], FIT_ONLY(), preemption="kube",
            retry_buffer=64, telemetry="series", **ENGINE_KW,
        ).run()
        assert row["placed"] == int(one.placed[0])
        assert row["unschedulable"] == int(one.unschedulable[0])
        assert row["evictions"] == int(one.evictions[0])
        assert row["evict_rescheduled"] == int(one.evict_rescheduled[0])
        assert row["evict_stranded"] == int(one.evict_stranded[0])
        assert row["evict_latency_mean"] == float(one.evict_latency_mean[0])
        for k, arr in (("stranded_cpu", one.stranded_cpu),
                       ("frag_index_cpu", one.frag_index_cpu),
                       ("packing_efficiency", one.packing_efficiency)):
            if row[k] is not None:
                assert row[k] == float(arr[0])
        # Telemetry series: bit-identical per-scenario virtual-time
        # trajectories (granularity rides the pool key).
        view = one.scenario_telemetry[0].query_view()
        assert row["telemetry"]["series"] == view["series"]
    # The baseline slot sees the SAME live base state as the queries.
    assert by_wire[0]["baseline_stranded_cpu"] is not None
    st = svc.stats()
    assert st["queries"] == 3 and st["batches"] == 1
    assert st["cold_builds"] == 1 and st["warm_hits"] == 0
    assert st["compile_counts"] == {"defrag/series": 1}


def test_warm_queries_zero_recompile():
    """Tentpole acceptance: the second query against an identical-shape
    pool engine swaps scenario values only — the compiled-executable
    count stays 1 and the engine object is reused (no cold build)."""
    ec, ep = _tiny_trace()
    writer = _ListWriter()
    svc = _service(ec, ep, writer=writer)
    svc.submit({"op": "defrag", "tenant": "a", "id": "q1", "nodes": [1],
                "drainAt": 3.0})
    assert svc.flush() == 1  # partial batch: padded to the fixed shape
    (r1,) = svc.poll("a")
    assert r1["warm"] is False and r1["batch_occupancy"] < 1.0
    eng = next(iter(svc._pool.values()))
    svc.submit({"op": "defrag", "tenant": "a", "id": "q2",
                "nodes": [0, 2], "drainAt": 5.0, "recoverAt": 15.0})
    svc.flush()
    (r2,) = svc.poll("a")
    assert r2["warm"] is True
    assert next(iter(svc._pool.values())) is eng  # same resident engine
    st = svc.stats()
    assert st["cold_builds"] == 1 and st["warm_hits"] == 1
    assert st["compile_counts"] == {"defrag/summary": 1}
    assert compiled_cache_size(eng._chunk_fn) == 1
    # Writer saw admission + result rows, wall fields scrubbed-safe keys
    # present for the schema (values stay real without deterministic
    # mode).
    kinds = [r["kind"] for r in writer.rows]
    assert kinds.count("query") == 2 and kinds.count("query-result") == 2
    assert svc.close() == []  # nothing undelivered
    with pytest.raises(ValueError, match="closed"):
        svc.submit({"op": "defrag", "nodes": [0]})


def test_simulator_what_if_engine_reuse():
    """Satellite 1: repeated same-shape ``api.Simulator.what_if`` calls
    reuse ONE resident engine — compile count pinned at 1 — and the
    swapped-value answer bit-matches a fresh one-off build."""
    from kubernetes_simulator_tpu.api import Simulator

    nodes_l = [Node(f"n{i}", {"cpu": 8.0}) for i in range(3)]
    pods_l = [Pod(f"p{i}", requests={"cpu": 1.0}, arrival_time=float(i),
                  duration=20.0) for i in range(8)]

    def _scens(factor):
        return [
            Scenario(),
            Scenario(perturbations=[Perturbation(
                op="scale_capacity", nodes=np.array([0]),
                resource="cpu", factor=factor,
            )]),
        ]

    sim = Simulator(nodes_and_pods := Cluster(nodes=nodes_l), pods_l,
                    strategy="jax",
                    plugins=[{"name": "NodeResourcesFit"}])
    res1 = sim.what_if(scenarios=_scens(0.5), **ENGINE_KW)
    eng = sim._whatif_cache[1]
    res2 = sim.what_if(scenarios=_scens(0.125), **ENGINE_KW)
    assert sim._whatif_cache[1] is eng  # resident, not rebuilt
    assert compiled_cache_size(eng._chunk_fn) == 1
    fresh = Simulator(nodes_and_pods, pods_l, strategy="jax",
                      plugins=[{"name": "NodeResourcesFit"}]).what_if(
        scenarios=_scens(0.125), **ENGINE_KW)
    np.testing.assert_array_equal(res2.placed, fresh.placed)
    np.testing.assert_array_equal(res2.unschedulable, fresh.unschedulable)
    assert res1.placed[1] >= res2.placed[1]  # tighter cap, fewer fits
    # A different batch shape misses the cache and rebuilds.
    res3 = sim.what_if(scenarios=_scens(0.5) + [Scenario()], **ENGINE_KW)
    assert sim._whatif_cache[1] is not eng
    assert len(res3.placed) == 3


def test_serve_lines_and_schema_v7(tmp_path):
    """Satellite 2 + v7 envelope: the serve loop turns torn/malformed
    NDJSON into ``query-error`` rows and keeps serving; every emitted
    row (admission, result, error, flight query events) validates as
    schema v7."""
    from kubernetes_simulator_tpu.sim.flight import (
        FlightRecorder,
        FlightRecorderConfig,
    )
    from kubernetes_simulator_tpu.utils.metrics import JsonlWriter

    ec, ep = _tiny_trace()
    out_path = str(tmp_path / "serve.jsonl")
    fl_path = str(tmp_path / "flight.jsonl")
    lines = io.StringIO(
        "\n".join([
            '{"op": "defrag", "tenant": "a", "id": "q1", "nodes": [1], '
            '"drainAt": 3.0}',
            '{"op": "defrag", "tenant": "a", "id": "q2", "nodes": [',  # torn
            "not json at all",
            '{"op": "warp", "nodes": [0]}',  # unknown family
            '{"op": "defrag", "nodes": [99]}',  # out of range
            "",  # blank lines are skipped, not errors
            '{"op": "defrag", "tenant": "b", "id": "q9", "nodes": [0, 2], '
            '"drainAt": 2.0, "recoverAt": 9.0}',
        ]) + "\n"
    )
    flight = FlightRecorder(FlightRecorderConfig(path=fl_path),
                            meta={"mode": "serve"})
    with JsonlWriter(out_path, context={"seed": 0, "engine": "jax",
                                        "config_hash": "t" * 12}) as out:
        svc = _service(ec, ep, max_batch=1, writer=out, flight=flight)
        stats = serve_lines(svc, lines, out)
    flight.close()
    assert stats["queries"] == 2 and stats["errors"] == 4
    assert stats["batches"] == 2  # max_batch=1: every valid line flushes
    rows = [json.loads(l) for l in open(out_path)]
    kinds = [r["kind"] for r in rows]
    assert kinds.count("query") == 2
    assert kinds.count("query-result") == 2
    assert kinds.count("query-error") == 4
    # The good query AFTER the bad lines was served — pool survived.
    assert kinds[-1] == "query-result"
    last = rows[-1]
    assert last["tenant"] == "b" and last["query"] == "q9"
    assert last["schema"] == 7
    errs = [r for r in rows if r["kind"] == "query-error"]
    assert all("error" in r and "raw" in r for r in errs)
    assert any("nodes" in r["raw"] for r in errs)  # torn line echoed
    # Everything written validates, including the flight 'query' events.
    assert validate_file(out_path) == []
    assert validate_file(fl_path) == []
    fl_rows = [json.loads(l) for l in open(fl_path)]
    q_events = [r for r in fl_rows if r.get("event") == "query"]
    assert len(q_events) == 2
    assert q_events[0]["warm"] is False and q_events[1]["warm"] is True
    assert q_events[1]["engines"] == 1


@pytest.mark.slow
def test_engine_pool_lru_soak():
    """Satellite 5 (slow-marked): a multi-granularity query mix under a
    capped pool — LRU eviction churns engines, every answer keeps
    bit-stable against its own re-ask, and the pool never exceeds the
    cap."""
    ec, ep = _tiny_trace()
    svc = _service(ec, ep, max_engines=1)
    first = {}
    for round_i in range(2):
        for gran in ("summary", "series"):
            svc.submit({"op": "defrag", "tenant": "t", "id": f"{gran}-{round_i}",
                        "nodes": [1], "drainAt": 3.0, "recoverAt": 10.0,
                        "granularity": gran})
            svc.flush()
            (row,) = svc.poll("t")
            assert len(svc._pool) <= 1
            key = (row["placed"], row["unschedulable"], row["evictions"],
                   row["evict_stranded"])
            if gran in first:
                assert first[gran] == key  # re-ask answers identically
            else:
                first[gran] = key
    st = svc.stats()
    assert st["cold_builds"] == 4  # every switch re-cold-builds at cap 1
    assert st["evicted_engines"] >= 3
    assert st["engines"] == 1
    svc.close()
