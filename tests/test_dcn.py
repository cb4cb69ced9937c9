"""Round-11 multi-host DCN parity suite (ISSUE round 11: process-local
folds, local-shard result fetch, ONE end-of-replay gather).

A 2-process CPU DCN replay must be indistinguishable from the
single-process mesh run: per-scenario results, collected assignment
matrices, deterministic JSONL bytes, checkpoint blob content and tuner
trajectories are all compared EXACTLY against a single-process oracle
computed in this test process from the SAME case builders
(tests/dcn_case_worker.py). The worker additionally pins the round-11
counters in-process: ``WhatIfEngine._replicate_count == 0`` (no
cross-process ``_fetch`` replication — the chunk loop is process-local)
and ``dcn.GATHER_COUNT`` advancing by exactly ONE per what-if replay.

The quick 2-process "plain" split is tier-1; the kube/chaos, tuner and
checkpoint cases plus the replicated-fallback batch ride one slow fleet.
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


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _launch(cases, nproc: int = 2, timeout: int = 300) -> dict:
    """Spawn the nproc-worker fleet over ``cases``; every worker must
    exit 0 and print an identical full result (the gather replicates the
    assembled batch to every process)."""
    port = _free_port()
    env_base = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": f"--xla_force_host_platform_device_count={8 // nproc}",
        "KSIM_DCN_COORD": f"127.0.0.1:{port}",
        "KSIM_DCN_NPROC": str(nproc),
        "KSIM_DCN_CASES": ",".join(cases),
        # Workers import the repo package from the checkout.
        "PYTHONPATH": os.pathsep.join(
            [os.path.dirname(os.path.dirname(__file__))]
            + [
                p
                for p in os.environ.get("PYTHONPATH", "").split(os.pathsep)
                if p
            ]
        ),
    }
    procs = []
    for pid in range(nproc):
        env = dict(env_base, KSIM_DCN_PID=str(pid))
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
                pytest.fail("DCN case worker timed out")
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
    """Single-process reference, through the same JSON round-trip the
    worker results take (so int/float/None representations match)."""
    out = W.run_cases([case], expect_dcn=False)
    return json.loads(json.dumps(out[case]))


def test_two_process_plain_parity():
    """Mesh what-if with device boundary-retry + collected assignments +
    deterministic JSONL: the 2-process run's gathered result — including
    the JSONL file BYTES — equals the single-process mesh run's."""
    res = _launch(("plain",))
    assert res["plain"] == _oracle("plain")


@pytest.mark.slow
def test_two_process_kube_tuner_ckpt_parity():
    """One slow fleet over the remaining round-11 parity cases:
    kube/chaos timelines with series telemetry through the host mirrors,
    a CEM tuner whose per-sweep gathers make the trajectory
    process-count-independent, checkpoint blob content from the
    single-replay engine, the loud replicated fallback for a batch that
    does not divide over the processes, plus the round-12 merged fleet
    telemetry (2-process ReplayTelemetry.merge == 1-process oracle)."""
    cases = ("chaos", "tuner", "ckpt", "odd", "fleetmerge")
    res = _launch(cases, timeout=600)
    for c in cases:
        assert res[c] == _oracle(c), f"case {c} diverged"


@pytest.mark.slow
def test_killed_worker_fails_fast_attributed():
    """Round-12 liveness bar: SIGKILL one worker mid-replay (the worker
    self-kills after its chunk-0 heartbeat) and the SURVIVOR must abort
    the gather with an attributed error naming the dead process and its
    last completed chunk — well before KSIM_DCN_TIMEOUT_S (here 600s),
    because the dead worker's beacon goes stale past KSIM_DCN_STALL_S."""
    import time

    port = _free_port()
    env_base = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
        "KSIM_DCN_COORD": f"127.0.0.1:{port}",
        "KSIM_DCN_NPROC": "2",
        "KSIM_DCN_CASES": "fleetmerge",
        # Fast-fail knobs: the full timeout is deliberately huge so the
        # test proves the STALL detector (not the deadline) fired.
        "KSIM_DCN_TIMEOUT_S": "600",
        "KSIM_DCN_STALL_S": "2",
        "KSIM_DCN_POLL_S": "0.3",
        "KSIM_DCN_HEARTBEAT_EVERY": "1",
        "PYTHONPATH": os.pathsep.join(
            [os.path.dirname(os.path.dirname(__file__))]
            + [
                p
                for p in os.environ.get("PYTHONPATH", "").split(os.pathsep)
                if p
            ]
        ),
    }
    t0 = time.monotonic()
    procs = []
    for pid in range(2):
        env = dict(env_base, KSIM_DCN_PID=str(pid))
        if pid == 1:
            env["KSIM_DCN_SELFKILL_AT_CHUNK"] = "0"
        procs.append(
            subprocess.Popen(
                [sys.executable, _WORKER],
                env=env,
                stdout=subprocess.PIPE,
                stderr=subprocess.PIPE,
                text=True,
            )
        )
    try:
        out0, err0 = procs[0].communicate(timeout=300)
        procs[1].wait(timeout=60)
    except subprocess.TimeoutExpired:
        for q in procs:
            if q.poll() is None:
                q.kill()
                q.wait()
        pytest.fail("survivor did not fail fast on a killed worker")
    elapsed = time.monotonic() - t0
    blob = out0 + err0
    if "Multiprocess computations aren't implemented" in blob:
        pytest.skip("jaxlib CPU backend lacks multiprocess execution")
    assert procs[1].returncode == -9, "worker 1 should have SIGKILLed itself"
    assert procs[0].returncode != 0, f"survivor exited 0:\n{blob}"
    assert "process 1" in blob, f"error does not name the dead process:\n{blob}"
    assert "last completed chunk" in blob, blob
    assert "looks DEAD" in blob, blob
    # Attributed failure must come from the stall detector, not the 600s
    # deadline (generous bound: replay + compile + stall window).
    assert elapsed < 240, f"survivor took {elapsed:.0f}s to fail"
