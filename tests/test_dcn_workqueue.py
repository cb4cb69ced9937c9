"""Round-18 work-stealing scenario-block queue (ISSUE round 18).

A DCN what-if fleet draining the KV-backed block queue must be
indistinguishable from the static-slicing run — which test_dcn.py
already pins against the single-process oracle — for ANY interleaving
of leases, steals and speculative re-executions. The suite sweeps
1/2/3-process fleets, uneven block sizes, the kube+series merge leg and
the node-sharded fork leg (tests/dcn_case_worker.py builders), plus the
robustness drills: an injected straggler resolved by speculative
re-execution (with the lease/speculate/block-done events pinned in the
fleet telemetry mirror) and a worker joining mid-replay.

The quick 2-process queue and uneven-block parity runs are tier-1; the
3-process sweep, the straggler drill and the late joiner ride slow
fleets. validate_config refusals for the ``dcn.workQueue`` YAML section
are pinned here too (single-process, fast).
"""

import functools
import json
import os
import socket
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.dirname(__file__))

import dcn_case_worker as W  # noqa: E402

_WORKER = os.path.join(os.path.dirname(__file__), "dcn_case_worker.py")

# Heartbeats every chunk (lease renewals ride them), generous stall so
# XLA compile never looks like a dead holder, fast poll so Phase B picks
# up pending blocks promptly.
WQ_ENV = {
    "KSIM_DCN_WORKQUEUE": "1",
    "KSIM_DCN_HEARTBEAT_EVERY": "1",
    "KSIM_DCN_STALL_S": "120",
    "KSIM_DCN_POLL_S": "0.3",
}


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _launch(cases, nproc: int = 2, extra_env=None, per_pid_env=None,
            timeout: int = 600) -> dict:
    """Spawn an nproc fleet over ``cases``; every process must exit 0
    and print an identical gathered result. ``extra_env`` applies to the
    whole fleet, ``per_pid_env`` ({pid: {...}}) to single members (the
    late-joiner knob)."""
    port = _free_port()
    env_base = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": f"--xla_force_host_platform_device_count={8 // nproc}",
        "KSIM_DCN_COORD": f"127.0.0.1:{port}",
        "KSIM_DCN_NPROC": str(nproc),
        "KSIM_DCN_CASES": ",".join(cases),
        "PYTHONPATH": os.pathsep.join(
            [os.path.dirname(os.path.dirname(__file__))]
            + [
                p
                for p in os.environ.get("PYTHONPATH", "").split(os.pathsep)
                if p
            ]
        ),
        **(extra_env or {}),
    }
    procs = []
    for pid in range(nproc):
        env = dict(env_base, KSIM_DCN_PID=str(pid))
        env.update((per_pid_env or {}).get(pid, {}))
        procs.append(
            subprocess.Popen(
                [sys.executable, _WORKER],
                env=env,
                stdout=subprocess.PIPE,
                stderr=subprocess.PIPE,
                text=True,
            )
        )
    outs = []
    try:
        for p in procs:
            try:
                out, err = p.communicate(timeout=timeout)
            except subprocess.TimeoutExpired:
                pytest.fail("DCN work-queue worker timed out")
            if "Multiprocess computations aren't implemented" in (out + err):
                for q in procs:
                    if q.poll() is None:
                        q.kill()
                        q.wait()
                pytest.skip("jaxlib CPU backend lacks multiprocess execution")
            assert p.returncode == 0, f"worker failed:\n{out}\n{err}"
            lines = [
                l for l in out.splitlines()
                if l.startswith("DCN_CASES_RESULT ")
            ]
            assert lines, f"no result line:\n{out}\n{err}"
            outs.append(json.loads(lines[-1][len("DCN_CASES_RESULT "):]))
    finally:
        for q in procs:
            if q.poll() is None:
                q.kill()
                q.wait()
    for o in outs[1:]:
        assert o == outs[0], "processes disagree on the gathered result"
    return outs[0]


@functools.lru_cache(maxsize=None)
def _oracle(case: str):
    """Single-process reference (== the static-slicing gather, which
    test_dcn.py pins against this same oracle), through the JSON
    round-trip the worker results take."""
    out = W.run_cases([case], expect_dcn=False)
    return json.loads(json.dumps(out[case]))


def _events(hb_dir: str):
    path = os.path.join(hb_dir, "events.jsonl")
    if not os.path.exists(path):
        return []
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


# -- queue-vs-static byte parity ---------------------------------------------


def test_wq_two_process_parity():
    """2-process fleet draining the queue (auto block size: one block
    per worker) on the kube+series merge leg — gather byte-identical to
    the static-slicing oracle, exactly ONE gather per replay (pinned
    in-worker)."""
    res = _launch(("wqmerge",), extra_env=WQ_ENV)
    assert res["wqmerge"] == _oracle("wqmerge")


def test_wq_uneven_block_parity():
    """blockSize=4 over S=6 leaves a ragged tail block of 2 — block
    boundaries that match no static slice. Concatenating blocks in block
    order must still reproduce the global scenario order bit-for-bit."""
    res = _launch(
        ("wqmerge",), extra_env=dict(WQ_ENV, KSIM_DCN_WQ_BLOCK="4"),
    )
    assert res["wqmerge"] == _oracle("wqmerge")


def test_wq_env_inert_single_process(monkeypatch):
    """KSIM_DCN_WORKQUEUE=1 without a DCN fleet (the 1-process 'fleet')
    is inert: the engine never slices, never gathers, and the result is
    the plain single-process run."""
    oracle = _oracle("wqmerge")  # computed BEFORE the env flips
    monkeypatch.setenv("KSIM_DCN_WORKQUEUE", "1")
    out = W.run_cases(["wqmerge"], expect_dcn=False)
    assert json.loads(json.dumps(out["wqmerge"])) == oracle


@pytest.mark.slow
def test_wq_three_process_parity():
    """3-process fleet over S=6 (two scenarios per block) on both the
    kube+series merge leg and the node-sharded fork leg."""
    res = _launch(("wqmerge", "wqfork"), nproc=3, extra_env=WQ_ENV)
    assert res["wqmerge"] == _oracle("wqmerge")
    assert res["wqfork"] == _oracle("wqfork")


@pytest.mark.slow
def test_wq_small_blocks_parity():
    """blockSize=1 over S=6 with 2 workers: three queue hand-offs per
    process beyond the static partition — maximal contention on the
    lease CAS — and the mesh-free gather still bit-matches."""
    res = _launch(
        ("wqmerge",), extra_env=dict(WQ_ENV, KSIM_DCN_WQ_BLOCK="1"),
    )
    assert res["wqmerge"] == _oracle("wqmerge")


# -- straggler resolved by speculation ---------------------------------------


@pytest.mark.slow
def test_wq_straggler_resolved_by_speculation(tmp_path):
    """Process 1 is slowed 4s per heartbeat from chunk 1 on (faultline
    ``slow`` class); the lease stall is pushed out of reach so only
    SPECULATIVE re-execution can resolve it. The fleet must finish with
    the straggler's own late result discarded as a duplicate — the
    direct witness that static slicing (which must wait for process 1's
    slice) would still be blocked at that point — and the gather must
    stay byte-identical to the no-straggler oracle. The lease /
    speculate / block-done(spec) / dup-discard chain is pinned in the
    fleet telemetry mirror (events.jsonl), attributed to the stolen
    block."""
    hb = tmp_path / "hb"
    hb.mkdir()
    res = _launch(
        ("wqmerge",),
        extra_env=dict(
            WQ_ENV,
            KSIM_DCN_SPECULATE="1",
            KSIM_DCN_RECOVER="1",
            KSIM_DCN_CKPT_EVERY="1",
            KSIM_DCN_STRAGGLER_S="1",
            KSIM_DCN_STALL_S="600",
            KSIM_DCN_HB_DIR=str(hb),
            KSIM_FAULTLINE="1",
            KSIM_FAULTLINE_SEED="18",
            KSIM_FAULTLINE_SLOW="1@1:4",
        ),
    )
    assert res["wqmerge"] == _oracle("wqmerge")
    evs = _events(str(hb))
    kinds = [e.get("event") for e in evs]
    assert kinds.count("lease") == 2, evs  # one gen-0 lease per block
    spec = [e for e in evs if e.get("event") == "speculate"]
    assert len(spec) == 1, evs  # one-shot election per (block, gen)
    assert spec[0]["from"] == 1, spec  # attributed to the straggler
    assert spec[0]["pid"] != 1, spec
    stolen = spec[0]["block"]
    done = [
        e for e in evs
        if e.get("event") == "block_done" and e.get("block") == stolen
    ]
    assert done and done[0]["spec"] is True, evs  # speculative win
    assert done[0]["pid"] == spec[0]["pid"], evs
    # The straggler finished AFTER the fleet already had its block: its
    # duplicate was discarded — under static slicing the replay would
    # still have been waiting on it.
    dup = [e for e in evs if e.get("event") == "dup_discard"]
    assert [e["pid"] for e in dup] == [1], evs
    assert dup[0]["block"] == stolen, evs
    assert "steal" not in kinds, evs  # resolved by speculation, not expiry


# -- true elastic join --------------------------------------------------------


@pytest.mark.slow
def test_wq_late_join_parity(tmp_path):
    """A third process registered as a joiner (KSIM_DCN_SPARES=1 — it
    owns no static block) defers its contribution by
    KSIM_DCN_JOIN_DELAY_S, then leases pending blocks from the queue.
    blockSize=1 leaves 6 blocks for 2 workers, so pending work exists
    when it wakes; the gather (assembled identically on all three
    processes, joiner included) stays byte-identical and the join event
    lands in the fleet telemetry mirror."""
    hb = tmp_path / "hb"
    hb.mkdir()
    res = _launch(
        ("wqmerge",),
        nproc=3,
        extra_env=dict(
            WQ_ENV,
            KSIM_DCN_WQ_BLOCK="1",
            KSIM_DCN_SPARES="1",
            KSIM_DCN_HB_DIR=str(hb),
        ),
        per_pid_env={2: {"KSIM_DCN_JOIN_DELAY_S": "1"}},
    )
    assert res["wqmerge"] == _oracle("wqmerge")
    evs = _events(str(hb))
    joins = [e for e in evs if e.get("event") == "join"]
    assert [e["pid"] for e in joins] == [2], evs
    leases = [e for e in evs if e.get("event") == "lease"]
    assert len(leases) == 6, evs  # every block leased exactly once at gen 0
    done = [e for e in evs if e.get("event") == "block_done"]
    assert sorted(e["block"] for e in done) == list(range(6)), evs


# -- validate_config refusals -------------------------------------------------


def _cfg(yaml_text, tmp_path):
    from kubernetes_simulator_tpu.utils.config import SimConfig

    p = tmp_path / "c.yaml"
    p.write_text(yaml_text)
    return SimConfig.load(str(p))


_BASE = """
strategy: jax
cluster: {synthetic: {nodes: 4, seed: 1}}
workload: {synthetic: {pods: 8, seed: 1}}
whatIf: {scenarios: 2, seed: 1}
"""


def test_validate_refuses_workqueue_without_fleet(tmp_path, monkeypatch):
    from kubernetes_simulator_tpu.cli import validate_config

    monkeypatch.delenv("KSIM_DCN_NPROC", raising=False)
    cfg = _cfg(_BASE + "dcn: {workQueue: {enable: true}}\n", tmp_path)
    errors = "\n".join(validate_config(cfg))
    assert "dcn.workQueue.enable" in errors
    assert "dcn_launch" in errors  # actionable: points at the launcher


def test_validate_refuses_speculation_without_checkpoints(tmp_path,
                                                          monkeypatch):
    from kubernetes_simulator_tpu.cli import validate_config

    monkeypatch.setenv("KSIM_DCN_NPROC", "2")
    cfg = _cfg(
        _BASE + "dcn: {workQueue: {enable: true, speculate: true}}\n",
        tmp_path,
    )
    errors = "\n".join(validate_config(cfg))
    assert "dcn.workQueue.speculate" in errors
    assert "checkpointEvery" in errors
    # With checkpoints on, the same config is clean.
    cfg2 = _cfg(
        _BASE
        + "dcn: {recovery: {enable: true, checkpointEvery: 2},\n"
        + "  workQueue: {enable: true, speculate: true}}\n",
        tmp_path,
    )
    assert not [
        e for e in validate_config(cfg2) if "workQueue" in e
    ], validate_config(cfg2)


def test_validate_refuses_bad_block_size(tmp_path, monkeypatch):
    from kubernetes_simulator_tpu.cli import validate_config

    monkeypatch.setenv("KSIM_DCN_NPROC", "2")
    cfg = _cfg(
        _BASE + "dcn: {workQueue: {enable: true, blockSize: -3}}\n",
        tmp_path,
    )
    errors = "\n".join(validate_config(cfg))
    assert "dcn.workQueue.blockSize" in errors


def test_validate_refuses_workqueue_without_heartbeats(tmp_path, monkeypatch):
    from kubernetes_simulator_tpu.cli import validate_config

    monkeypatch.setenv("KSIM_DCN_NPROC", "2")
    monkeypatch.setenv("KSIM_DCN_HEARTBEAT_EVERY", "0")
    cfg = _cfg(_BASE + "dcn: {workQueue: {enable: true}}\n", tmp_path)
    errors = "\n".join(validate_config(cfg))
    assert "heartbeat" in errors.lower()


def test_workqueue_knobs_without_enable_warn_only(tmp_path, caplog):
    import logging

    from kubernetes_simulator_tpu.cli import validate_config

    cfg = _cfg(
        _BASE + "dcn: {workQueue: {enable: false, blockSize: 2}}\n",
        tmp_path,
    )
    with caplog.at_level(logging.WARNING):
        errors = validate_config(cfg)
    assert not [e for e in errors if "workQueue" in e]
    assert any("workQueue" in r.message for r in caplog.records)


def test_validate_accepts_example_config17():
    from kubernetes_simulator_tpu.cli import validate_config
    from kubernetes_simulator_tpu.utils.config import SimConfig

    path = os.path.join(
        os.path.dirname(__file__), "..", "examples",
        "config17_workqueue.yaml",
    )
    cfg = SimConfig.load(path)
    assert cfg.dcn_workqueue is not None and cfg.dcn_workqueue.enable
    assert cfg.dcn_workqueue.speculate
    os.environ["KSIM_DCN_NPROC"] = "3"
    try:
        errors = [e for e in validate_config(cfg) if "workQueue" in e]
    finally:
        del os.environ["KSIM_DCN_NPROC"]
    assert errors == []
