"""Worker + shared case builders for the round-11 DCN parity suite
(tests/test_dcn.py).

Each builder constructs a deterministic workload, runs it, and reduces the
result to a JSON-serializable dict of exact values and content hashes. The
PARENT TEST imports the same builders to compute the single-process oracle,
so any drift between a 2-process DCN run and the single-process mesh run is
a bit-level diff of identical code paths — the parity bar of ISSUE round
11 (process-local folds, one end-of-replay gather).

As a script it is one of KSIM_DCN_NPROC worker processes: it joins the
coordinator through the PRODUCTION entry point (``dcn.maybe_init_from_env``
— the same enable-cache-then-initialize path scripts/dcn_launch.py
children take), runs the cases named in KSIM_DCN_CASES, pins the round-11
counters (zero ``_fetch`` replications, exactly ONE gather per what-if
replay) and prints everything as one JSON line.

Platform env (JAX_PLATFORMS=cpu, --xla_force_host_platform_device_count)
must be set by the parent BEFORE jax import.
"""

import hashlib
import json
import os
import sys
import tempfile


def _sha(data: bytes) -> str:
    return hashlib.sha256(data).hexdigest()


def _arr_sha(a) -> str:
    """Content hash of an array: dtype + shape + raw little-endian bytes —
    equal hashes ⇔ bit-identical arrays."""
    import numpy as np

    a = np.ascontiguousarray(a)
    return _sha(
        f"{a.dtype.str}:{a.shape}:".encode() + a.tobytes()
    )


def _normalize_jsonl(data: bytes) -> bytes:
    """Strip the round-12 DCN process stamp (``process_id`` /
    ``process_count``) from every row so worker and oracle bytes compare.
    Single-process files have no stamp and round-trip byte-identically
    (JsonlWriter serializes with ``json.dumps`` defaults, as here)."""
    out = []
    for line in data.splitlines():
        row = json.loads(line)
        row.pop("process_id", None)
        row.pop("process_count", None)
        out.append(json.dumps(row).encode())
    return b"\n".join(out) + (b"\n" if out else b"")


def _assert_process_stamp(jsonl: bytes) -> None:
    """Every row of a fleet-written file must carry THIS worker's stamp;
    single-process rows must carry none (byte-compat with pre-round-12)."""
    from kubernetes_simulator_tpu.parallel import dcn

    nproc, pid = dcn.process_info()
    for line in jsonl.splitlines():
        row = json.loads(line)
        if nproc > 1:
            assert row.get("process_id") == pid, row
            assert row.get("process_count") == nproc, row
        else:
            assert "process_id" not in row and "process_count" not in row, row


def _deterministic_jsonl():
    """Context manager forcing KSIM_DETERMINISTIC_JSONL=1 (builders run it
    on BOTH sides so worker and oracle bytes are comparable)."""
    import contextlib

    @contextlib.contextmanager
    def _cm():
        old = os.environ.get("KSIM_DETERMINISTIC_JSONL")
        os.environ["KSIM_DETERMINISTIC_JSONL"] = "1"
        try:
            yield
        finally:
            if old is None:
                del os.environ["KSIM_DETERMINISTIC_JSONL"]
            else:
                os.environ["KSIM_DETERMINISTIC_JSONL"] = old

    return _cm()


# -- case builders (importable by the oracle) ------------------------------


def case_plain():
    """Mesh-sharded what-if with collected assignments, plus the full
    JSONL surface written under KSIM_DETERMINISTIC_JSONL — placed counts,
    assignment matrix, and the JSONL file bytes must all match the
    single-process mesh run (modulo the round-12 process stamp, which is
    asserted in-worker and stripped before hashing). (Boundary retry
    rides the kube chaos case — it is exclusive with
    collect_assignments.)"""
    from kubernetes_simulator_tpu.framework.framework import FrameworkConfig
    from kubernetes_simulator_tpu.models.encode import encode
    from kubernetes_simulator_tpu.parallel.mesh import make_mesh
    from kubernetes_simulator_tpu.sim.synthetic import (
        make_cluster,
        make_workload,
    )
    from kubernetes_simulator_tpu.sim.whatif import (
        WhatIfEngine,
        uniform_scenarios,
    )
    from kubernetes_simulator_tpu.utils.metrics import JsonlWriter, whatif_rows

    cluster = make_cluster(12, seed=21, taint_fraction=0.2)
    pods, _ = make_workload(
        48, seed=21, with_affinity=True, with_spread=True,
        with_tolerations=True,
    )
    ec, ep = encode(cluster, pods)
    scenarios = uniform_scenarios(ec, 8, seed=21, p_capacity=0.5, p_taint=0.3)
    eng = WhatIfEngine(
        ec, ep, scenarios, FrameworkConfig(), mesh=make_mesh(),
        chunk_waves=4, collect_assignments=True,
    )
    res = eng.run()

    with _deterministic_jsonl():
        fd, path = tempfile.mkstemp(suffix=".jsonl")
        os.close(fd)
        try:
            ctx = {"seed": 21, "engine": "v3", "config_hash": "dcn-parity"}
            with JsonlWriter(path, context=ctx) as out:
                for row in whatif_rows(res, {"mesh": True}):
                    out.write(row)
            jsonl = open(path, "rb").read()
        finally:
            os.unlink(path)

    _assert_process_stamp(jsonl)
    return eng, {
        "placed": res.placed.tolist(),
        "unschedulable": res.unschedulable.tolist(),
        "total_placed": int(res.total_placed),
        "assignments_sha": _arr_sha(res.assignments),
        "jsonl_sha": _sha(_normalize_jsonl(jsonl)),
        "jsonl_rows": len(jsonl.splitlines()),
    }


def case_chaos():
    """Kube boundary mode with per-scenario chaos timelines and series
    telemetry on the no-mesh path — exercises the process-LOCAL host
    mirrors and the telemetry leg of the gather payload (per-scenario
    ReplayTelemetry instances ride the pickle; only their
    virtual-time-derived fields are compared — phase timers are
    wall-clock)."""
    import math

    import numpy as np

    from kubernetes_simulator_tpu.framework.framework import FrameworkConfig
    from kubernetes_simulator_tpu.models.core import Cluster, Node, Pod
    from kubernetes_simulator_tpu.models.encode import encode
    from kubernetes_simulator_tpu.sim.runtime import NodeEvent
    from kubernetes_simulator_tpu.sim.whatif import Scenario, WhatIfEngine

    nodes = [Node(f"n{i}", {"cpu": 8.0}) for i in range(5)]
    pods = [
        Pod(f"p{i}", requests={"cpu": 1.0}, arrival_time=float(i),
            duration=30.0)
        for i in range(28)
    ]
    ec, ep = encode(Cluster(nodes=nodes), pods)
    cfg = FrameworkConfig(plugins=[{"name": "NodeResourcesFit"}])
    evs = [
        NodeEvent(time=8.0, kind="node_down", node=0),
        NodeEvent(time=18.0, kind="node_up", node=0),
        NodeEvent(time=24.0, kind="node_down", node=1),
    ]
    scenarios = [
        Scenario(),
        Scenario(events=evs),
        Scenario(events=[NodeEvent(time=25.0, kind="node_down", node=0)]),
        Scenario(events=[NodeEvent(time=4.0, kind="node_down", node=2)]),
    ]
    eng = WhatIfEngine(
        ec, ep, scenarios, cfg, wave_width=1, chunk_waves=1,
        preemption="kube", retry_buffer=64, collect_assignments=True,
        telemetry="series",
    )
    res = eng.run()
    tel = [
        None if t is None else {
            "granularity": t.granularity,
            "latency": t.latency,
            "reasons": t.reasons,
            "rejection_attempts": t.rejection_attempts,
            "zero_latency_binds": t.zero_latency_binds,
            "bind_latency": {
                str(k): v for k, v in (t.bind_latency or {}).items()
            },
        }
        for t in (res.scenario_telemetry or [])
    ]
    return eng, {
        "placed": res.placed.tolist(),
        "evictions": res.evictions.tolist(),
        "evict_rescheduled": res.evict_rescheduled.tolist(),
        "evict_stranded": res.evict_stranded.tolist(),
        "evict_latency_mean": [
            float(x) for x in np.asarray(res.evict_latency_mean)
        ],
        "latency_p50": [
            None if math.isnan(x) else float(x)
            for x in np.asarray(res.latency_p50, np.float64)
        ],
        "assignments_sha": _arr_sha(res.assignments),
        "scenario_count": len(tel),
        "telemetry_sha": _sha(
            json.dumps(tel, sort_keys=True).encode()
        ),
    }


def case_tuner():
    """A small CEM policy search over the mesh — every sweep is a what-if
    replay that gathers objectives once, so the full trajectory (every
    candidate score, every round) must be process-count-independent."""
    from kubernetes_simulator_tpu.framework.framework import FrameworkConfig
    from kubernetes_simulator_tpu.models.core import Cluster, Node, Pod
    from kubernetes_simulator_tpu.models.encode import encode
    from kubernetes_simulator_tpu.parallel.mesh import make_mesh
    from kubernetes_simulator_tpu.sim.tuner import PolicyTuner

    nodes = [Node(f"n{i}", capacity={"cpu": 4.0, "memory": 16.0})
             for i in range(4)]
    pods = [
        Pod(f"small-{i}", requests={"cpu": 1.0, "memory": 1.0},
            arrival_time=float(i))
        for i in range(8)
    ] + [
        Pod(f"large-{i}", requests={"cpu": 4.0, "memory": 4.0},
            arrival_time=float(8 + i))
        for i in range(2)
    ]
    ec, ep = encode(Cluster(nodes=nodes), pods)
    res = PolicyTuner(
        ec, ep, FrameworkConfig(),
        algo="cem", population=4, rounds=2, seed=0,
        # Flat axes must divide the mesh: train = 4x2 = 8 rows, held-out
        # = 4x2 (winner + default) = 8 rows — both divide 8 devices
        # single-process and 4 local devices per DCN process.
        train_scenarios=2, heldout_scenarios=4, scenario_seed=1,
        p_node_down=0.0, p_capacity=0.25, p_taint=0.0,
        chunk_waves=4, mesh=make_mesh(), cpu_oracle=False,
    ).run()
    return None, {
        "best_policy": res.best_policy,
        "best_vector_sha": _arr_sha(res.best_vector),
        "train_objective": float(res.train_objective),
        "heldout_objective": float(res.heldout_objective),
        "default_heldout_objective": float(res.default_heldout_objective),
        "evaluations": int(res.evaluations),
        "trajectory_sha": _sha(
            json.dumps(res.trajectory, sort_keys=True).encode()
        ),
    }


def case_ckpt():
    """Single-replay kube/chaos run with mid-trace checkpointing: the
    checkpoint BLOB CONTENT (every array, bit-for-bit) and the final
    assignments must match the single-process run. Content hashes rather
    than file bytes: .npz is a zip whose member headers carry wall-clock
    mtimes."""
    import numpy as np

    from kubernetes_simulator_tpu.framework.framework import FrameworkConfig
    from kubernetes_simulator_tpu.models.core import Cluster, Node, Pod
    from kubernetes_simulator_tpu.models.encode import encode
    from kubernetes_simulator_tpu.sim.jax_runtime import JaxReplayEngine
    from kubernetes_simulator_tpu.sim.runtime import NodeEvent

    nodes = [Node(f"n{i}", {"cpu": 8.0}) for i in range(5)]
    pods = [
        Pod(f"p{i}", requests={"cpu": 1.0}, arrival_time=float(i),
            duration=30.0)
        for i in range(28)
    ]
    ec, ep = encode(Cluster(nodes=nodes), pods)
    cfg = FrameworkConfig(plugins=[{"name": "NodeResourcesFit"}])
    evs = [
        NodeEvent(time=8.0, kind="node_down", node=0),
        NodeEvent(time=18.0, kind="node_up", node=0),
    ]
    fd, ck = tempfile.mkstemp(suffix=".npz")
    os.close(fd)
    os.unlink(ck)
    try:
        res = JaxReplayEngine(
            ec, ep, cfg, wave_width=1, chunk_waves=1, preemption="kube",
            retry_buffer=64,
        ).replay(node_events=evs, checkpoint_path=ck, checkpoint_every=8)
        with np.load(ck) as z:
            blob_sha = _sha(
                b"".join(
                    k.encode() + b":" + _arr_sha(z[k]).encode()
                    for k in sorted(z.files)
                )
            )
    finally:
        if os.path.exists(ck):
            os.unlink(ck)
    return None, {
        "checkpoint_sha": blob_sha,
        "placed": int(res.placed),
        "evictions": int(res.evictions),
        "assignments_sha": _arr_sha(res.assignments),
    }


def case_odd():
    """A batch that does NOT divide over the processes (S=7, nproc=2):
    the engine warns and runs fully replicated — every process computes
    all scenarios, no gather fires, ``process_count`` stays 1 — and the
    results still match the single-process run."""
    from kubernetes_simulator_tpu.framework.framework import FrameworkConfig
    from kubernetes_simulator_tpu.models.encode import encode
    from kubernetes_simulator_tpu.sim.synthetic import (
        make_cluster,
        make_workload,
    )
    from kubernetes_simulator_tpu.sim.whatif import (
        WhatIfEngine,
        uniform_scenarios,
    )

    cluster = make_cluster(8, seed=5)
    pods, _ = make_workload(32, seed=5)
    ec, ep = encode(cluster, pods)
    scenarios = uniform_scenarios(ec, 7, seed=5, p_capacity=0.5, p_taint=0.2)
    eng = WhatIfEngine(ec, ep, scenarios, FrameworkConfig(), chunk_waves=4)
    res = eng.run()
    assert not eng._dcn_sliced
    assert eng._replicate_count == 0
    assert res.process_count == 1
    return None, {
        "placed": res.placed.tolist(),
        "unschedulable": res.unschedulable.tolist(),
        "total_placed": int(res.total_placed),
    }


def case_fleetmerge():
    """Round-12 fleet telemetry: kube+series what-if on the no-mesh DCN
    path. The MERGED ``WhatIfResult.fleet_telemetry`` rides the single
    end-of-replay gather, and every virtual-time-derived field — latency
    histogram over the union of first binds, key-wise rejection-counter
    sums, series concatenated in global scenario order — must bit-match
    the single-process oracle. Phase timers are wall-clock, so only their
    key STRUCTURE is pinned in-process: exactly one ``p<pid>/`` namespace
    per fleet member (``p0`` alone on the oracle side)."""
    from kubernetes_simulator_tpu.framework.framework import FrameworkConfig
    from kubernetes_simulator_tpu.models.core import Cluster, Node, Pod
    from kubernetes_simulator_tpu.models.encode import encode
    from kubernetes_simulator_tpu.parallel import dcn
    from kubernetes_simulator_tpu.sim.runtime import NodeEvent
    from kubernetes_simulator_tpu.sim.whatif import Scenario, WhatIfEngine

    nodes = [Node(f"n{i}", {"cpu": 4.0}) for i in range(4)]
    pods = [
        Pod(f"p{i}", requests={"cpu": 1.0}, arrival_time=float(i),
            duration=20.0)
        for i in range(24)
    ]
    ec, ep = encode(Cluster(nodes=nodes), pods)
    cfg = FrameworkConfig(plugins=[{"name": "NodeResourcesFit"}])
    scenarios = [
        Scenario(),
        Scenario(events=[
            NodeEvent(time=6.0, kind="node_down", node=0),
            NodeEvent(time=14.0, kind="node_up", node=0),
        ]),
        Scenario(events=[NodeEvent(time=10.0, kind="node_down", node=1)]),
        Scenario(),
    ]
    eng = WhatIfEngine(
        ec, ep, scenarios, cfg, wave_width=1, chunk_waves=1,
        preemption="kube", retry_buffer=32, telemetry="series",
    )
    res = eng.run()
    ft = res.fleet_telemetry
    assert ft is not None, "fleet_telemetry missing from what-if result"
    nproc, _ = dcn.process_info()
    prefixes = {k.split("/", 1)[0] for k in ft.phases}
    assert prefixes == {f"p{i}" for i in range(max(nproc, 1))}, prefixes
    return eng, {
        "granularity": ft.granularity,
        "latency": ft.latency,
        "reasons": ft.reasons,
        "rejection_attempts": ft.rejection_attempts,
        "zero_latency_binds": int(ft.zero_latency_binds),
        "bind_values": [float(v) for v in ft.bind_latency.values()],
        "series_sha": _sha(
            json.dumps(ft.series, sort_keys=True).encode()
        ),
        "events_len": len(ft.events),
    }


def case_wqmerge():
    """Round-18 work-queue merge case: kube+series what-if on the no-mesh
    DCN path with S=6 — divisible by 1-, 2- and 3-worker fleets and by
    the uneven block sizes the parity suite sweeps. Under the work queue
    the merged fleet telemetry keeps the EXECUTING processes' ``p<pid>/``
    phase namespaces (whoever won each block) with ``wq_block`` markers;
    statically it is exactly one namespace per process. Either way every
    virtual-time-derived payload field must bit-match the
    single-process oracle."""
    from kubernetes_simulator_tpu.framework.framework import FrameworkConfig
    from kubernetes_simulator_tpu.models.core import Cluster, Node, Pod
    from kubernetes_simulator_tpu.models.encode import encode
    from kubernetes_simulator_tpu.parallel import dcn
    from kubernetes_simulator_tpu.sim.runtime import NodeEvent
    from kubernetes_simulator_tpu.sim.whatif import Scenario, WhatIfEngine

    nodes = [Node(f"n{i}", {"cpu": 4.0}) for i in range(4)]
    pods = [
        Pod(f"p{i}", requests={"cpu": 1.0}, arrival_time=float(i),
            duration=20.0)
        for i in range(24)
    ]
    ec, ep = encode(Cluster(nodes=nodes), pods)
    cfg = FrameworkConfig(plugins=[{"name": "NodeResourcesFit"}])
    scenarios = []
    for s in range(6):
        if s % 3 == 1:
            scenarios.append(Scenario(events=[
                NodeEvent(time=4.0 + s, kind="node_down", node=s % 4),
                NodeEvent(time=12.0 + s, kind="node_up", node=s % 4),
            ]))
        elif s % 3 == 2:
            scenarios.append(Scenario(events=[
                NodeEvent(time=6.0 + s, kind="node_down", node=(s + 1) % 4),
            ]))
        else:
            scenarios.append(Scenario())
    eng = WhatIfEngine(
        ec, ep, scenarios, cfg, wave_width=1, chunk_waves=1,
        preemption="kube", retry_buffer=32, telemetry="series",
    )
    res = eng.run()
    ft = res.fleet_telemetry
    assert ft is not None, "fleet_telemetry missing from what-if result"
    nproc, _ = dcn.process_info()
    prefixes = {k.split("/", 1)[0] for k in ft.phases}
    if nproc > 1 and dcn.wq_enabled():
        # Phase timers keep the EXECUTING process's namespace (whoever
        # won each block) — a subset of the fleet when one process
        # drains several blocks — and the block executors stamp
        # wq_block markers.
        assert any(k.endswith("/wq_block") for k in ft.phases), (
            "work-queue run lost its wq_block phase attribution"
        )
        assert prefixes and prefixes <= {
            f"p{i}" for i in range(nproc)
        }, prefixes
    else:
        assert prefixes == {f"p{i}" for i in range(max(nproc, 1))}, prefixes
    return eng, {
        "granularity": ft.granularity,
        "latency": ft.latency,
        "reasons": ft.reasons,
        "rejection_attempts": ft.rejection_attempts,
        "zero_latency_binds": int(ft.zero_latency_binds),
        "bind_values": [float(v) for v in ft.bind_latency.values()],
        "series_sha": _sha(
            json.dumps(ft.series, sort_keys=True).encode()
        ),
        "events_len": len(ft.events),
    }


def case_wqfork():
    """Round-18 work-queue over the fork leg: every scenario forks from
    a checkpoint written by a ``JaxReplayEngine`` replay, then the S=6 what-if batch
    runs (under the queue when enabled) — placements and the collected
    assignment matrix must bit-match the single-process oracle."""
    from kubernetes_simulator_tpu.framework.framework import FrameworkConfig
    from kubernetes_simulator_tpu.models.encode import encode
    from kubernetes_simulator_tpu.sim.jax_runtime import JaxReplayEngine
    from kubernetes_simulator_tpu.sim.synthetic import (
        make_cluster,
        make_workload,
    )
    from kubernetes_simulator_tpu.sim.whatif import (
        Scenario,
        WhatIfEngine,
        uniform_scenarios,
    )

    cluster = make_cluster(10, seed=18)
    pods, _ = make_workload(80, seed=18, with_affinity=True, with_spread=True)
    ec, ep = encode(cluster, pods)
    cfg = FrameworkConfig()
    fd, ck = tempfile.mkstemp(suffix=".npz")
    os.close(fd)
    os.unlink(ck)
    try:
        JaxReplayEngine(
            ec, ep, cfg, chunk_waves=5,
        ).replay(checkpoint_path=ck, checkpoint_every=2)
        scenarios = [Scenario()] + list(
            uniform_scenarios(ec, 5, seed=18, p_capacity=0.5, p_taint=0.2)
        )
        eng = WhatIfEngine(
            ec, ep, scenarios, cfg, chunk_waves=5,
            collect_assignments=True, fork_checkpoint=ck,
        )
        res = eng.run()
    finally:
        if os.path.exists(ck):
            os.unlink(ck)
    return eng, {
        "placed": res.placed.tolist(),
        "unschedulable": res.unschedulable.tolist(),
        "total_placed": int(res.total_placed),
        "assignments_sha": _arr_sha(res.assignments),
    }


CASES = {
    "plain": case_plain,
    "chaos": case_chaos,
    "tuner": case_tuner,
    "ckpt": case_ckpt,
    "odd": case_odd,
    "fleetmerge": case_fleetmerge,
    "wqmerge": case_wqmerge,
    "wqfork": case_wqfork,
}


def run_cases(names, expect_dcn: bool):
    """Run the named cases in order, pinning the round-11 counters:
    zero cross-process ``_fetch`` replications ever, and under DCN exactly
    ONE gather per what-if replay (the tuner runs one replay per sweep)."""
    from kubernetes_simulator_tpu.parallel import dcn

    out = {}
    for name in names:
        g0 = dcn.GATHER_COUNT
        eng, payload = CASES[name]()
        delta = dcn.GATHER_COUNT - g0
        if eng is not None:
            assert eng._replicate_count == 0, (
                f"{name}: cross-process _fetch replication in chunk loop"
            )
            want = 1 if expect_dcn else 0
            assert delta == want, (
                f"{name}: {delta} gathers per replay, want {want}"
            )
        elif not expect_dcn:
            assert delta == 0, f"{name}: gathered in single-process run"
        out[name] = payload
    return out


def _arm_selfkill() -> None:
    """KSIM_DCN_SELFKILL_AT_CHUNK=<n> (round-12 killed-worker test): die
    with SIGKILL right after publishing the first heartbeat whose chunk
    cursor reaches <n>, simulating a worker lost mid-replay. Survivors
    must then fail FAST out of the gather with an attributed
    DcnGatherTimeout naming this pid and its last completed chunk."""
    at = os.environ.get("KSIM_DCN_SELFKILL_AT_CHUNK")
    if at is None:
        return
    import signal

    from kubernetes_simulator_tpu.parallel import dcn

    threshold = int(at)
    real = dcn.heartbeat

    def _hb(chunk, *a, **kw):
        ok = real(chunk, *a, **kw)
        if int(chunk) >= threshold:
            os.kill(os.getpid(), signal.SIGKILL)
        return ok

    dcn.heartbeat = _hb


def main() -> None:
    import jax

    from kubernetes_simulator_tpu.parallel import dcn

    assert dcn.maybe_init_from_env(), "KSIM_DCN_* env not set"
    _arm_selfkill()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.3)
    nproc, pid = dcn.process_info()
    assert nproc == int(os.environ["KSIM_DCN_NPROC"]), nproc
    assert jax.device_count() == len(jax.local_devices()) * nproc

    names = os.environ["KSIM_DCN_CASES"].split(",")
    out = run_cases(names, expect_dcn=True)
    print("DCN_CASES_RESULT " + json.dumps(out), flush=True)


if __name__ == "__main__":
    sys.exit(main())
