"""Round-15 elastic recovery suite: SIGKILL one of 2 DCN workers
mid-replay WITH recovery enabled and the survivor must claim the dead
process's scenario block, resume it from the newest published
checkpoint, and complete the single end-of-replay gather with results
BYTE-IDENTICAL to a no-failure run (compared against the same
single-process oracles the round-11 parity suite uses).

Kill timing is chosen so a true checkpoint RESUME is exercised, not
just a from-scratch re-run: with KSIM_DCN_CKPT_EVERY=1 the victim
publishes its chunk-1 checkpoint BEFORE the heartbeat that triggers
the self-kill (publication is ordered first in the chunk loop), so the
survivor restores cursor 1 of 2 and replays only the remaining chunk.
The second case rides the kube host-mirror path, where checkpoints
don't apply and the claimed block deterministically re-executes from
chunk 0 — both recovery envelopes in one fleet.

The recovery-DISABLED behavior (round-12 attributed DcnGatherTimeout)
is pinned by tests/test_dcn.py::test_killed_worker_fails_fast_attributed,
which runs without KSIM_DCN_RECOVER — the default.
"""

import functools
import json
import os
import socket
import subprocess
import sys
import time

import pytest

sys.path.insert(0, os.path.dirname(__file__))

import dcn_case_worker as W  # noqa: E402
import dcn_recovery_worker  # noqa: E402,F401  (registers recovery_fleet)

_WORKER = os.path.join(os.path.dirname(__file__), "dcn_recovery_worker.py")


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


@functools.lru_cache(maxsize=None)
def _oracle(case: str):
    """Single-process reference through the same JSON round-trip the
    worker results take (int/float/None representations match)."""
    out = W.run_cases([case], expect_dcn=False)
    return json.loads(json.dumps(out[case]))


@pytest.mark.slow
def test_survivor_recovers_killed_worker_byte_identical(tmp_path):
    """Worker 1 SIGKILLs itself after its chunk-0 heartbeat (its chunk-1
    checkpoint is already published); worker 0 must claim the block,
    resume the checkpoint, finish the replay, and return EXACTLY the
    no-failure gathered result for every case — plus mirror the claim
    and recovery events for dcn_launch --watch."""
    cases = ("plain", "recovery_fleet")
    port = _free_port()
    hb_dir = tmp_path / "hb"
    env_base = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
        "KSIM_DCN_COORD": f"127.0.0.1:{port}",
        "KSIM_DCN_NPROC": "2",
        "KSIM_DCN_CASES": ",".join(cases),
        # Round-15 recovery knobs: checkpoint every chunk, claim fast.
        "KSIM_DCN_RECOVER": "1",
        "KSIM_DCN_CKPT_EVERY": "1",
        "KSIM_DCN_TIMEOUT_S": "600",
        "KSIM_DCN_STALL_S": "2",
        "KSIM_DCN_POLL_S": "0.3",
        "KSIM_DCN_HEARTBEAT_EVERY": "1",
        "KSIM_DCN_HB_DIR": str(hb_dir),
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
        out0, err0 = procs[0].communicate(timeout=600)
        procs[1].wait(timeout=60)
    except subprocess.TimeoutExpired:
        for q in procs:
            if q.poll() is None:
                q.kill()
                q.wait()
        pytest.fail("recovery fleet timed out")
    blob = out0 + err0
    if "Multiprocess computations aren't implemented" in blob:
        pytest.skip("jaxlib CPU backend lacks multiprocess execution")
    assert procs[1].returncode == -9, "worker 1 should have SIGKILLed itself"
    assert procs[0].returncode == 0, f"survivor failed:\n{blob}"

    # Byte-identical recovery: the survivor's gathered payloads equal
    # the single-process no-failure oracles for EVERY case, including
    # the deterministic JSONL hash inside case "plain".
    lines = [
        l for l in out0.splitlines() if l.startswith("DCN_CASES_RESULT ")
    ]
    assert lines, f"no result line:\n{blob}"
    res = json.loads(lines[-1][len("DCN_CASES_RESULT "):])
    for c in cases:
        assert res[c] == _oracle(c), f"case {c} diverged after recovery"

    # Claim protocol + checkpoint resume actually fired (not a silent
    # fall-through to some other path): worker 0 claimed worker 1's
    # block in both gathers, and the mesh case resumed mid-replay from
    # the published checkpoint.
    assert "claims dead process 1" in blob, blob
    assert "resumed process 1's block" in blob, blob
    assert "resumed and republished process 1's block" in blob, blob

    # The KV mirror carries the operator-visible rebalance trail
    # (dcn_launch --watch renders these live).
    events_path = hb_dir / "events.jsonl"
    assert events_path.exists(), "no events.jsonl in KSIM_DCN_HB_DIR"
    events = [
        json.loads(l)
        for l in events_path.read_text().splitlines()
        if l.strip()
    ]
    kinds = [(e.get("event"), e.get("claimant"), e.get("for"))
             for e in events]
    assert kinds.count(("claim", 0, 1)) == len(cases), kinds
    assert kinds.count(("recovered", 0, 1)) == len(cases), kinds
