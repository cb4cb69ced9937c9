#!/usr/bin/env python
"""Seeded crash-schedule fuzzer for the faultline plane (rounds 17-18).

Samples adversarial fault schedules — SIGKILL schedules (always
including the double-kill and the recovering-claimant-kill), transient
KV errors, added latency, torn checkpoint writes, stale reads, and the
round-18 work-queue drills (a deterministic straggler resolved by
speculative re-execution, and a speculator killed mid-speculation with
the block completing via the lease-expiry steal), plus the round-19
mid-publish kill (a worker SIGKILLed between its device→host snapshot
and the background publisher's KV publication, recovered from the prior
complete cursor) — runs each against a
3-worker DCN fleet with recovery enabled, and asserts the surviving
workers' end gathers are BYTE-IDENTICAL to a no-failure single-process
oracle.  The injector only ever touches the coordination plane or the
holder's wall-clock, so any divergence is a real semantics bug, not
noise.

Round 20 adds the two SUPERVISED drills of the durable-ground
acceptance bar, run through ``scripts/dcn_launch.py --supervise`` over
a durability journal: the coordinator SIGKILLed by name (``0@run:1`` —
previously the canonical unsurvivable death) and the whole fleet killed
mid-publish (``all@run:1`` under a 50% torn-write rate).  Both must end
with the supervisor relaunching the fleet with ``--resume`` and the
restarted fleet's gather byte-identical to the no-failure oracle.

Usage (also importable — tests/test_faultline_fuzz.py drives the same
functions from the pytest slow slice):

    python scripts/faultline_fuzz.py --schedules 5 --seed 17
    python scripts/faultline_fuzz.py --worker    # internal: fleet child
    python scripts/faultline_fuzz.py --oracle    # internal: oracle child

Both child modes print one ``FAULTLINE_RESULT <json>`` line; the worker
joins the coordinator through the production ``dcn.maybe_init_from_env``
path first.  Schedules are pure functions of ``--seed`` — a failure
reproduces with the same seed and schedule index.
"""

import argparse
import hashlib
import json
import os
import random
import socket
import subprocess
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SELF = os.path.abspath(__file__)

NPROC = 3
SCENARIOS = 12  # divisible by NPROC and by 1 (the oracle)
CHUNKS_PER_WORKER = SCENARIOS // NPROC  # wave_width=1, chunk_waves=1

SKIP_MARKER = "Multiprocess computations aren't implemented"


def _sha(data: bytes) -> str:
    return hashlib.sha256(data).hexdigest()


# -- the workload (identical on worker and oracle sides) ---------------------


def build_payload() -> dict:
    """Run the fuzz workload and reduce the result to exact values and
    content hashes.  Kube boundary mode + series telemetry on the no-mesh
    DCN path — the same recovery-capable leg tests/test_dcn_recovery.py
    pins — sized so each of the 3 workers owns 4 single-scenario chunks
    (kill thresholds 0..3 all exercise a mid-block death).  Only
    virtual-time-derived fields ride the payload: phase timers are
    wall-clock and recovery legitimately re-namespaces them under the
    claimant's pid."""
    from kubernetes_simulator_tpu.framework.framework import FrameworkConfig
    from kubernetes_simulator_tpu.models.core import Cluster, Node, Pod
    from kubernetes_simulator_tpu.models.encode import encode
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
    for s in range(SCENARIOS):
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
    return {
        "placed": res.placed.tolist(),
        "evictions": res.evictions.tolist(),
        "evict_rescheduled": res.evict_rescheduled.tolist(),
        "total_placed": int(res.total_placed),
        "granularity": ft.granularity,
        "latency": ft.latency,
        "reasons": ft.reasons,
        "rejection_attempts": ft.rejection_attempts,
        "zero_latency_binds": int(ft.zero_latency_binds),
        "bind_values": [float(v) for v in ft.bind_latency.values()],
        "series_sha": _sha(json.dumps(ft.series, sort_keys=True).encode()),
        "events_len": len(ft.events),
    }


def _emit(payload: dict) -> None:
    print("FAULTLINE_RESULT " + json.dumps(payload, sort_keys=True),
          flush=True)


def main_worker() -> int:
    from kubernetes_simulator_tpu.parallel import dcn

    assert dcn.maybe_init_from_env(), "KSIM_DCN_* env not set"
    _emit(build_payload())
    return 0


def main_oracle() -> int:
    _emit(build_payload())
    return 0


# -- schedule sampling -------------------------------------------------------

# The mandatory schedules of the acceptance bar: ≥2 concurrent worker
# deaths; a claimant killed at its first recovery beacon (the ``*``
# CAS entry — whichever survivor claims first dies, the other hands off
# via claim generation 1); two round-18 work-queue drills — a
# deterministic straggler resolved purely by speculative re-execution
# (lease expiry pushed out of reach), and a speculator SIGKILLed at its
# first ``spec`` beacon, after which the straggler's block still
# completes via the lease-expiry steal at generation 1; and the
# round-19 mid-publish kill — with checkpoint publication running on
# the background publisher thread, whichever worker first finishes its
# second chunk is SIGKILLed in the window between the synchronous
# device→host snapshot and the (possibly still in-flight) KV
# publication, under a 50% torn-write rate. The survivor must recover
# from the prior COMPLETE cursor (the manifest is written last, so a
# half-published epoch is invisible) and still gather byte-identical.
#
# Round 20 appends the two SUPERVISED durable-ground drills, which run
# under ``dcn_launch.py --supervise`` with a durability journal instead
# of a hand-rolled Popen fleet: the coordinator SIGKILLed by name
# (``0@run:1``), and the whole fleet killed at once (``all@run:1``)
# under a 50% torn-write rate that also tears journal files.  Both end
# only when the supervisor's relaunched fleet gathers byte-identical to
# the oracle — whole-fleet death is now inside the bar, not outside it.
MANDATORY = (
    {"name": "double-kill", "kill": "1@run:0,2@run:0", "seed": 1701},
    {"name": "claimant-kill", "kill": "2@run:0,*@recover:-1", "seed": 1702},
    {"name": "wq-straggler", "wq": 1, "slow": "1@1:4",
     "stall_s": 600, "straggler_s": 1.0, "seed": 1801},
    {"name": "wq-spec-kill", "wq": 1, "slow": "1@1:4",
     "kill": "*@spec:-1", "stall_s": 2, "straggler_s": 1.0, "seed": 1802},
    {"name": "mid-publish-kill", "kill": "*@run:1", "torn_rate": 0.5,
     "seed": 1901},
    {"name": "coord-kill-restart", "kill": "0@run:1", "supervised": 1,
     "seed": 2001},
    {"name": "fleet-kill-restart", "kill": "all@run:1", "torn_rate": 0.5,
     "supervised": 1, "seed": 2002},
)


def sample_schedules(seed: int, n: int):
    """``n`` fault schedules, a pure function of ``seed``.  The first
    seven are always the mandatory double-kill, claimant-kill,
    wq-straggler, wq-spec-kill, mid-publish-kill and the two supervised
    durable-ground drills (coord-kill-restart, fleet-kill-restart); the
    rest mix a random named kill (or none) with KV error/latency/torn/
    stale rates low enough that the bounded retries absorb them."""
    rng = random.Random(int(seed) * 9176 + 5)
    out = [dict(s) for s in MANDATORY]
    while len(out) < n:
        sch = {"name": f"rand{len(out)}", "seed": rng.randrange(1, 10 ** 6)}
        # Killable pids exclude 0: the coordinator hosts the
        # jax.distributed coordination service, whose death is
        # unsurvivable by construction (outside this fuzzer's bar).
        roll = rng.random()
        if roll < 0.45:
            pid = rng.randrange(1, NPROC)
            chunk = rng.randrange(CHUNKS_PER_WORKER - 1)
            sch["kill"] = f"{pid}@run:{chunk}"
        elif roll < 0.6:
            a, b = rng.sample(range(1, NPROC), 2)
            sch["kill"] = (
                f"{a}@run:{rng.randrange(2)},{b}@run:{rng.randrange(2)}"
            )
        sch["kv_error_rate"] = rng.choice([0.0, 0.02, 0.05])
        sch["kv_delay_rate"] = rng.choice([0.0, 0.05])
        sch["torn_rate"] = rng.choice([0.0, 0.25, 0.5])
        sch["stale_rate"] = rng.choice([0.0, 0.05])
        out.append(sch)
    return out


def named_kill_pids(sched: dict):
    """Pids a schedule kills unconditionally (named run-state entries
    with a reachable chunk threshold), and the count of ``*`` entries
    (each kills exactly one process, identity schedule-dependent)."""
    from kubernetes_simulator_tpu.parallel import faultline

    named, wildcard = set(), 0
    for pid_s, state, chunk in faultline.parse_kill_schedule(
        sched.get("kill", "")
    ):
        if pid_s == "*":
            wildcard += 1
        elif pid_s == "all":
            if state == "run" and chunk < CHUNKS_PER_WORKER:
                named.update(range(NPROC))
        elif state == "run" and chunk < CHUNKS_PER_WORKER:
            named.add(int(pid_s))
    return named, wildcard


# -- fleet orchestration -----------------------------------------------------


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _child_env(extra: dict) -> dict:
    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
        "PYTHONPATH": os.pathsep.join(
            [_REPO]
            + [
                p
                for p in os.environ.get("PYTHONPATH", "").split(os.pathsep)
                if p
            ]
        ),
    }
    env.update({k: str(v) for k, v in extra.items()})
    return env


def run_oracle(timeout_s: float = 600.0) -> dict:
    """The no-failure reference payload, computed in a clean subprocess
    (no DCN env, no faultline) through the same JSON round-trip the
    worker results take."""
    env = _child_env({})
    for k in list(env):
        if k.startswith("KSIM_DCN") or k.startswith("KSIM_FAULTLINE"):
            del env[k]
    p = subprocess.run(
        [sys.executable, _SELF, "--oracle"],
        env=env, capture_output=True, text=True, timeout=timeout_s,
    )
    assert p.returncode == 0, f"oracle failed:\n{p.stdout}\n{p.stderr}"
    lines = [
        l for l in p.stdout.splitlines()
        if l.startswith("FAULTLINE_RESULT ")
    ]
    assert lines, f"oracle printed no result:\n{p.stdout}\n{p.stderr}"
    return json.loads(lines[-1][len("FAULTLINE_RESULT "):])


def run_supervised_schedule(sched: dict, hb_dir: str,
                            timeout_s: float = 600.0) -> dict:
    """Run one schedule through ``scripts/dcn_launch.py --supervise``
    over a durability journal.  The supervisor owns ports, pids and
    relaunch-with-``--resume``; the fault env rides through untouched
    (``maybe_kill`` self-disarms on KSIM_DCN_RESTART_COUNT > 0, so the
    kill fires only in the first life).  Worker 0 inherits the
    supervisor's stdout, so its FAULTLINE_RESULT lines — one per life —
    land in the captured blob; the LAST one is the restarted fleet's
    gather."""
    durable = os.path.join(hb_dir, "journal")
    os.makedirs(durable, exist_ok=True)
    env = _child_env({
        "KSIM_DCN_RECOVER": "1",
        "KSIM_DCN_CKPT_EVERY": "1",
        "KSIM_DCN_TIMEOUT_S": "600",
        "KSIM_DCN_STALL_S": sched.get("stall_s", 2),
        "KSIM_DCN_POLL_S": "0.3",
        "KSIM_DCN_HEARTBEAT_EVERY": "1",
        "KSIM_DCN_MAX_CLAIMS": "2",
        "KSIM_DCN_RETRY_BASE_S": "0.01",
        "KSIM_DCN_HB_DIR": hb_dir,
        "KSIM_FAULTLINE": "1",
        "KSIM_FAULTLINE_SEED": sched.get("seed", 0),
        "KSIM_FAULTLINE_KV_ERROR_RATE": sched.get("kv_error_rate", 0.0),
        "KSIM_FAULTLINE_KV_DELAY_RATE": sched.get("kv_delay_rate", 0.0),
        "KSIM_FAULTLINE_KV_DELAY_S": "0.01",
        "KSIM_FAULTLINE_TORN_RATE": sched.get("torn_rate", 0.0),
        "KSIM_FAULTLINE_STALE_RATE": sched.get("stale_rate", 0.0),
        "KSIM_FAULTLINE_KILL": sched.get("kill", ""),
        "KSIM_FAULTLINE_SLOW": sched.get("slow", ""),
    })
    # The supervisor assigns coordinator address, pids and nproc itself;
    # stray values from an outer fleet would poison its children.
    for k in ("KSIM_DCN_COORD", "KSIM_DCN_PID", "KSIM_DCN_NPROC",
              "KSIM_DCN_DURABLE_DIR", "KSIM_DCN_RESUME",
              "KSIM_DCN_RESTART_COUNT"):
        env.pop(k, None)
    cmd = [
        sys.executable, os.path.join(_REPO, "scripts", "dcn_launch.py"),
        "--nproc", str(NPROC), "--devices-per-proc", "2",
        "--supervise", "--durable", durable,
        "--max-restarts", "2", "--restart-backoff", "0.2",
        "--timeout", str(max(min(timeout_s / 2.0, 240.0), 60.0)),
        "--", sys.executable, _SELF, "--worker",
    ]
    try:
        p = subprocess.run(cmd, env=env, capture_output=True, text=True,
                           timeout=timeout_s)
    except subprocess.TimeoutExpired as e:
        blob = "\n".join(
            str(s or "") for s in (e.stdout, e.stderr)
        ) or "supervised fleet timed out"
        return {"skip": SKIP_MARKER in blob, "timeout": True,
                "supervised": True, "rcs": {}, "results": {}, "blob": blob}
    blob = (p.stdout or "") + "\n" + (p.stderr or "")
    results = {}
    lines = [
        l for l in (p.stdout or "").splitlines()
        if l.startswith("FAULTLINE_RESULT ")
    ]
    if p.returncode == 0 and lines:
        results[0] = json.loads(lines[-1][len("FAULTLINE_RESULT "):])
    return {
        "skip": SKIP_MARKER in blob,
        "timeout": False,
        "supervised": True,
        "rcs": {0: p.returncode},
        "results": results,
        "blob": blob,
    }


def run_schedule(sched: dict, hb_dir: str, timeout_s: float = 600.0) -> dict:
    """Run one schedule against a fresh 3-worker fleet.  Returns
    ``{"skip": bool, "rcs": {pid: rc}, "results": {pid: payload},
    "blob": str}`` — ``results`` holds every surviving worker's gathered
    payload.  Supervised schedules are delegated to
    ``run_supervised_schedule``."""
    if sched.get("supervised"):
        return run_supervised_schedule(sched, hb_dir, timeout_s=timeout_s)
    port = _free_port()
    base = _child_env({
        "KSIM_DCN_COORD": f"127.0.0.1:{port}",
        "KSIM_DCN_NPROC": NPROC,
        # Recovery knobs: checkpoint every chunk, claim fast, two
        # generations so a killed claimant hands off exactly once.
        "KSIM_DCN_RECOVER": "1",
        "KSIM_DCN_CKPT_EVERY": "1",
        "KSIM_DCN_TIMEOUT_S": "600",
        "KSIM_DCN_STALL_S": sched.get("stall_s", 2),
        "KSIM_DCN_POLL_S": "0.3",
        "KSIM_DCN_HEARTBEAT_EVERY": "1",
        "KSIM_DCN_MAX_CLAIMS": "2",
        "KSIM_DCN_RETRY_BASE_S": "0.01",
        "KSIM_DCN_HB_DIR": hb_dir,
        # The schedule itself.
        "KSIM_FAULTLINE": "1",
        "KSIM_FAULTLINE_SEED": sched.get("seed", 0),
        "KSIM_FAULTLINE_KV_ERROR_RATE": sched.get("kv_error_rate", 0.0),
        "KSIM_FAULTLINE_KV_DELAY_RATE": sched.get("kv_delay_rate", 0.0),
        "KSIM_FAULTLINE_KV_DELAY_S": "0.01",
        "KSIM_FAULTLINE_TORN_RATE": sched.get("torn_rate", 0.0),
        "KSIM_FAULTLINE_STALE_RATE": sched.get("stale_rate", 0.0),
        "KSIM_FAULTLINE_KILL": sched.get("kill", ""),
        "KSIM_FAULTLINE_SLOW": sched.get("slow", ""),
    })
    if sched.get("wq"):
        # Round-18 work-queue drills: leases + speculation ride the same
        # fleet; straggler_s far below the (possibly unreachable) lease
        # stall so speculation — not expiry — is what gets exercised.
        base.update({
            "KSIM_DCN_WORKQUEUE": "1",
            "KSIM_DCN_SPECULATE": "1",
            "KSIM_DCN_STRAGGLER_S": str(sched.get("straggler_s", 1.0)),
        })
    procs = []
    for pid in range(NPROC):
        procs.append(subprocess.Popen(
            [sys.executable, _SELF, "--worker"],
            env=dict(base, KSIM_DCN_PID=str(pid)),
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        ))
    outs = {}
    try:
        for pid, p in enumerate(procs):
            outs[pid] = p.communicate(timeout=timeout_s)
    except subprocess.TimeoutExpired:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()
        for pid, p in enumerate(procs):
            outs.setdefault(pid, ("", "fleet timed out"))
        return {
            "skip": False,
            "timeout": True,
            "rcs": {pid: p.returncode for pid, p in enumerate(procs)},
            "results": {},
            "blob": "\n".join(o + e for o, e in outs.values()),
        }
    blob = "\n".join(o + e for o, e in outs.values())
    results = {}
    for pid, p in enumerate(procs):
        if p.returncode == 0:
            lines = [
                l for l in outs[pid][0].splitlines()
                if l.startswith("FAULTLINE_RESULT ")
            ]
            if lines:
                results[pid] = json.loads(
                    lines[-1][len("FAULTLINE_RESULT "):]
                )
    return {
        "skip": SKIP_MARKER in blob,
        "timeout": False,
        "rcs": {pid: p.returncode for pid, p in enumerate(procs)},
        "results": results,
        "blob": blob,
    }


def check_supervised(sched: dict, out: dict, oracle: dict):
    """Assertions for a supervised drill: the kill must actually have
    forced a relaunch-with-``--resume``, the supervisor must end clean
    within its restart budget, and the restarted fleet's gather must be
    byte-identical to the no-failure oracle."""
    name = sched["name"]
    if out.get("timeout"):
        return [f"{name}: supervised fleet timed out"]
    fails = []
    rc = out["rcs"].get(0)
    if rc != 0:
        fails.append(f"{name}: supervisor exited {rc}")
    if "relaunching with --resume" not in out["blob"]:
        fails.append(
            f"{name}: the kill fired but no supervised relaunch "
            "appeared in the logs"
        )
    got = out["results"].get(0)
    if got is None:
        if rc == 0:
            fails.append(f"{name}: restarted fleet printed no result")
    elif got != oracle:
        diff = [k for k in oracle if got.get(k) != oracle[k]]
        fails.append(
            f"{name}: restarted fleet diverged from the no-failure "
            f"oracle in {diff}"
        )
    return fails


def check_schedule(sched: dict, out: dict, oracle: dict):
    """Byte-parity + liveness assertions for one schedule run.  Returns
    a list of failure strings (empty ⇒ the schedule passed)."""
    if sched.get("supervised"):
        return check_supervised(sched, out, oracle)
    fails = []
    if out.get("timeout"):
        return [f"{sched['name']}: fleet timed out"]
    named, wildcard = named_kill_pids(sched)
    rcs = out["rcs"]
    for pid in named:
        if rcs.get(pid) != -9:
            fails.append(
                f"{sched['name']}: pid {pid} should have been SIGKILLed "
                f"(rc {rcs.get(pid)})"
            )
    killed = sum(1 for rc in rcs.values() if rc == -9)
    if killed > len(named) + wildcard:
        fails.append(
            f"{sched['name']}: {killed} processes died, schedule allows "
            f"at most {len(named) + wildcard}"
        )
    survivors = [pid for pid, rc in rcs.items() if rc == 0]
    if not survivors:
        fails.append(f"{sched['name']}: no surviving worker (rcs {rcs})")
    if wildcard and killed > len(named):
        # A ``*`` entry fired — which hand-off marker to demand depends
        # on WHERE the wildcard struck. Work queue: the speculator died,
        # so the straggler's block must have completed via the
        # lease-expiry STEAL at the next lease generation. Static
        # slicing at a ``recover`` beacon: a claimant died mid-recovery,
        # so a survivor must have opened the next claim generation (the
        # fenced hand-off). Static slicing at a ``run`` beacon (the
        # round-19 mid-publish drill): an ordinary worker died, so a
        # survivor must have CLAIMED the dead process's block from its
        # last COMPLETE published cursor.
        from kubernetes_simulator_tpu.parallel import faultline

        wild_states = {
            state
            for pid_s, state, _ in faultline.parse_kill_schedule(
                sched.get("kill", "")
            )
            if pid_s == "*"
        }
        if sched.get("wq"):
            marker, what = "steals block", "lease steal"
        elif "recover" in wild_states:
            marker, what = "opening generation", "claim generation"
        else:
            marker, what = "claims dead process", "dead-process claim"
        if marker not in out["blob"]:
            fails.append(
                f"{sched['name']}: wildcard kill fired but no "
                f"{what} hand-off appeared in the logs"
            )
    if sched.get("wq") and sched.get("slow") and not sched.get("kill"):
        # Pure-straggler drill: with lease expiry out of reach, only a
        # speculative re-execution can have resolved the slowed holder.
        if "speculates block" not in out["blob"]:
            fails.append(
                f"{sched['name']}: straggler injected but no speculative "
                "re-execution appeared in the logs"
            )
    for pid in survivors:
        got = out["results"].get(pid)
        if got is None:
            fails.append(
                f"{sched['name']}: survivor {pid} printed no result"
            )
        elif got != oracle:
            diff = [k for k in oracle if got.get(k) != oracle[k]]
            fails.append(
                f"{sched['name']}: survivor {pid} diverged from the "
                f"no-failure oracle in {diff}"
            )
    return fails


_PM_MOD = [None]


def _postmortem_mod():
    """Load scripts/fleet_postmortem.py by path (scripts/ is not a
    package) and cache it — the fuzz loop audits every drill."""
    if _PM_MOD[0] is None:
        import importlib.util

        path = os.path.join(_REPO, "scripts", "fleet_postmortem.py")
        spec = importlib.util.spec_from_file_location(
            "fleet_postmortem", path
        )
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _PM_MOD[0] = mod
    return _PM_MOD[0]


def run_blackbox_audit(sched: dict, hb_dir: str):
    """Round-21 cap: after every drill, reconstruct the fleet's black
    box from its heartbeat-mirror directory (events.jsonl + beacons +
    the durable journal when the drill was supervised) and run the
    protocol-invariant audit.  Any violation is a drill failure — the
    post-mortem must hold even on runs that SIGKILLed processes
    mid-write.  Returns failure strings (empty ⇒ audit passed)."""
    pm = _postmortem_mod()
    try:
        report = pm.run_postmortem(hb_dir, quiet=True)
    except Exception as e:  # the tool must never crash on drill debris
        return [f"{sched['name']}: post-mortem crashed: {e!r}"]
    fails = [
        f"{sched['name']}: post-mortem invariant "
        f"{v['invariant']} violated [{v['trace']}]: {v['detail']}"
        for v in report["violations"]
    ]
    print(
        f"faultline fuzz: post-mortem {sched['name']}: "
        f"{report['events_ingested']} events, "
        f"{report['links_resolved']} causal links, audit "
        f"{'FAILED' if fails else 'ok'} "
        f"({report['audit_wall_s'] * 1000.0:.1f}ms)",
        flush=True,
    )
    return fails


def main_fuzz(seed: int, n: int, timeout_s: float) -> int:
    import tempfile

    print("faultline fuzz: oracle run (no failures) ...", flush=True)
    oracle = run_oracle(timeout_s=timeout_s)
    scheds = sample_schedules(seed, n)
    failures = []
    skipped = 0
    for i, sched in enumerate(scheds):
        desc = {k: v for k, v in sched.items() if k != "name"}
        print(f"faultline fuzz: [{i + 1}/{n}] {sched['name']} {desc}",
              flush=True)
        with tempfile.TemporaryDirectory() as hb:
            out = run_schedule(sched, hb, timeout_s=timeout_s)
            pm_fails = []
            if not out.get("skip") and not out.get("timeout"):
                pm_fails = run_blackbox_audit(sched, hb)
        if out["skip"]:
            skipped += 1
            print(
                f"faultline fuzz: [{i + 1}/{n}] SKIP (no multiprocess "
                "CPU backend)", flush=True,
            )
            continue
        fails = check_schedule(sched, out, oracle) + pm_fails
        if fails:
            failures.extend(fails)
            print(f"faultline fuzz: [{i + 1}/{n}] FAIL: {fails}",
                  flush=True)
            tail = "\n".join(out["blob"].splitlines()[-40:])
            print(tail, flush=True)
        else:
            survivors = [p for p, rc in out["rcs"].items() if rc == 0]
            print(
                f"faultline fuzz: [{i + 1}/{n}] ok — rcs {out['rcs']}, "
                f"{len(survivors)} survivor(s) byte-identical to oracle",
                flush=True,
            )
    if failures:
        print(f"faultline fuzz: {len(failures)} failure(s)", flush=True)
        return 1
    print(
        f"faultline fuzz: all {n - skipped} schedule(s) byte-identical "
        f"to the no-failure oracle ({skipped} skipped)", flush=True,
    )
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--worker", action="store_true",
                    help="internal: run as one fleet worker")
    ap.add_argument("--oracle", action="store_true",
                    help="internal: run the no-failure oracle")
    ap.add_argument("--schedules", type=int, default=8,
                    help="number of fault schedules to sample (>= 7 "
                         "includes the mandatory double-kill, "
                         "claimant-kill, wq-straggler, wq-spec-kill, "
                         "mid-publish-kill and the supervised "
                         "coord-kill-restart / fleet-kill-restart)")
    ap.add_argument("--seed", type=int, default=17)
    ap.add_argument("--timeout", type=float, default=600.0,
                    help="per-run timeout in seconds")
    args = ap.parse_args()
    if args.worker:
        return main_worker()
    if args.oracle:
        return main_oracle()
    return main_fuzz(args.seed, max(args.schedules, len(MANDATORY)),
                     args.timeout)


if __name__ == "__main__":
    sys.exit(main())
