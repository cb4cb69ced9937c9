#!/usr/bin/env python
"""Multi-host DCN harness (round 11): spawn N coordinator+worker
processes ON ONE MACHINE and run the same command in each.

    python scripts/dcn_launch.py --nproc 2 -- \
        python -m kubernetes_simulator_tpu what-if examples/whatif.yaml

Each child gets ``KSIM_DCN_COORD`` / ``KSIM_DCN_NPROC`` / ``KSIM_DCN_PID``
(consumed by ``parallel.dcn.maybe_init_from_env`` — the CLI calls it on
startup), plus
``--xla_force_host_platform_device_count`` so every process exposes
``--devices-per-proc`` virtual CPU devices — the same mechanism real
multi-host TPU uses, minus the hardware, so the DCN code path runs in CI.
Process 0's output streams through; siblings are captured and replayed on
failure. Any child failing kills the rest (a DCN replay cannot complete
with a hole in the scenario axis).

CPU ONLY. Every child runs with ``JAX_PLATFORMS=cpu`` whatever the
caller's environment says: a TPU chip belongs to one process at a time,
so N processes on one machine cannot share it (the second one fails or
hangs). The fleet layer has never run on a chip; on-chip runs are one
process — ``python chip_smoke.py``, ``benchmark/run.py`` or the CLI directly.

``--watch`` (round 12) tails the workers' liveness heartbeats
(parallel.dcn.heartbeat mirrors each beacon to ``$KSIM_DCN_HB_DIR``) and
prints fleet progress to stderr every couple of seconds: last completed
chunk and chunks/sec per process, a live-buffer gauge, and a straggler
flag for any process whose beacon went stale or whose chunk cursor trails
the fleet.

``--elastic N`` (round 15) launches N SPARE processes at the tail of the
pid range and turns survivor recovery on (``KSIM_DCN_RECOVER=1`` unless
already set): spares own no scenario block — they sit in the gather as
claim-eligible capacity — and a worker dying mid-replay no longer kills
the fleet; a survivor claims the dead block, resumes its newest
checkpoint (``KSIM_DCN_CKPT_EVERY``), and the launcher succeeds as long
as ANY process completes the gathered replay. ``--watch`` surfaces the
rebalance live: claim/recovered events from the KV mirror's
``events.jsonl`` plus ``recovering-p<dead>`` beacon states.

``--join N`` (round 18) launches N JOINER processes at the tail of the
pid range and turns the work-stealing scenario-block queue on
(``KSIM_DCN_WORKQUEUE=1`` unless already set). The jax.distributed
runtime barriers until every process CONNECTS, so a joiner connects at
launch like everyone else — what joins mid-replay is its CONTRIBUTION:
each joiner sleeps ``--join-delay`` seconds (staggered per joiner)
inside the queue driver, publishing a live ``join``-state beacon, then
leases whatever blocks are still pending. Unlike round-15 spares,
joiners (and every worker) can relieve a LIVE straggler, not just a
dead process. ``--watch`` renders the queue live: per-block lease
owners from the beacons, plus lease / steal / speculate / block-done /
join events.

``--supervise`` (round 20) closes the one hole every in-fleet mechanism
shares: whole-fleet death, coordinator included — the jax.distributed
KV store dies with process 0 and takes every lease, checkpoint and
result with it. With ``--durable DIR`` (or ``KSIM_DCN_DURABLE_DIR``)
the fleet mirrors all of that to a filesystem journal, and the
supervisor watches the launch: any attempt that ends without a single
completed process is relaunched — fresh coordination port, same
journal — with ``KSIM_DCN_RESUME=1`` and ``KSIM_DCN_RESTART_COUNT``
exported, under a bounded exponential-backoff restart budget
(``--max-restarts`` / ``--restart-backoff``). The resumed fleet adopts
completed work-queue blocks from the journal and resumes in-flight
blocks from their newest complete durable cursor; its end gather is
byte-identical to an uninterrupted run. ``--resume`` alone runs one
attempt seeded from an existing journal (no supervision loop).
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import socket
import subprocess
import sys
import tempfile
import threading
import time


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def child_env(
    pid: int,
    nproc: int,
    port: int,
    devices_per_proc: int,
    hb_dir: str = "",
    join_delay: float = 0.0,
    durable: str = "",
    resume: bool = False,
    restart_count: int = 0,
) -> dict:
    env = dict(os.environ)
    env["KSIM_DCN_COORD"] = f"127.0.0.1:{port}"
    env["KSIM_DCN_NPROC"] = str(nproc)
    env["KSIM_DCN_PID"] = str(pid)
    if hb_dir:
        env["KSIM_DCN_HB_DIR"] = hb_dir
    if durable:
        # Round 20 durable ground: the fleet mirrors checkpoints, queue
        # results and the done/lease ledger to this journal directory.
        env["KSIM_DCN_DURABLE_DIR"] = durable
    if resume:
        env["KSIM_DCN_RESUME"] = "1"
    if restart_count > 0:
        # Consumed by faultline (kill schedules fire only in the
        # original fleet) and visible to anything attributing restarts.
        env["KSIM_DCN_RESTART_COUNT"] = str(restart_count)
    if join_delay > 0:
        # Round 18 joiner: defer this process's work-queue contribution
        # (the coordination connect still happens at launch — the
        # runtime barriers on it; parallel.dcn.wq_run sleeps instead).
        env["KSIM_DCN_JOIN_DELAY_S"] = str(join_delay)
    env["JAX_PLATFORMS"] = "cpu"  # CPU harness: N children cannot share a chip
    flags = [
        f for f in env.get("XLA_FLAGS", "").split()
        if not f.startswith("--xla_force_host_platform_device_count")
    ]
    flags.append(
        f"--xla_force_host_platform_device_count={devices_per_proc}"
    )
    env["XLA_FLAGS"] = " ".join(flags)
    return env


class FleetWatch:
    """Heartbeat tail for ``--watch``: reads the ``p<pid>.json`` beacon
    mirrors, derives chunks/sec from consecutive samples, and flags
    stragglers (stale beacon, or a chunk cursor trailing the fleet leader
    by more than ``lag_frac`` of the replay)."""

    def __init__(
        self,
        hb_dir: str,
        nproc: int,
        stall_s: float = 60.0,
        lag_frac: float = 0.25,
        flight_path: str = "",
    ):
        self.hb_dir = hb_dir
        self.nproc = nproc
        self.stall_s = stall_s
        self.lag_frac = lag_frac
        self.flight_path = flight_path
        self._prev: dict = {}  # pid -> (chunk, t) of the last rate sample
        self._ev_pos = 0  # bytes of events.jsonl already surfaced
        self._fl_pos: dict = {}  # flight stream path -> byte cursor

    def flight_lines(self) -> list:
        """Round 16: recorder lines for live runs. Tails the flight
        stream at ``flight_path`` (process 0) and its ``.p<pid>``
        siblings with a byte cursor per file, and renders the newest
        chunk row of each as a one-line gauge: rolling placements/sec,
        pager stalls. Tolerant of a missing/partial stream
        — the recorder is off by default, and a mid-write tail just
        waits for the next interval."""
        if not self.flight_path:
            return []
        out = []
        for pid in range(self.nproc):
            path = (
                self.flight_path if pid == 0
                else f"{self.flight_path}.p{pid}"
            )
            try:
                with open(path) as f:
                    f.seek(self._fl_pos.get(path, 0))
                    blob = f.read()
                    self._fl_pos[path] = f.tell()
            except OSError:
                continue
            last = None
            stalls = None
            for line in blob.splitlines():
                try:
                    row = json.loads(line)
                except json.JSONDecodeError:
                    continue  # torn tail of a mid-write line
                if not isinstance(row, dict) or row.get("kind") != "flight":
                    continue
                if row.get("event") == "chunk":
                    last = row
                if row.get("pager_stalls") is not None:
                    stalls = int(row["pager_stalls"])
            if last is None:
                continue
            seg = (
                f"p{pid} flight chunk {last.get('chunk', '?')}"
                f" {float(last.get('rolling_pps', 0.0)):.0f}pps"
            )
            if stalls is not None:
                seg += f" stalls={stalls}"
            if last.get("rss_peak_mib"):
                seg += f" rss={float(last['rss_peak_mib']):.0f}MiB"
            out.append(f"dcn_launch[watch]: {seg}")
        return out

    def events(self) -> list:
        """New claim/recovery events from the KV mirror's append-only
        ``events.jsonl`` (round 15: parallel.dcn._mirror_event) since the
        last call — the operator-visible trail of a live rebalance.
        Round 21: tolerant of a supervisor relaunch truncating the file
        mid-tail (a shrink resets the byte cursor to the new epoch's
        head) and of a mid-write partial final line (only complete
        lines are consumed; the tail waits for the next interval)."""
        path = os.path.join(self.hb_dir, "events.jsonl")
        try:
            with open(path, "rb") as f:
                f.seek(0, os.SEEK_END)
                if f.tell() < self._ev_pos:
                    self._ev_pos = 0  # truncated underneath the tail
                f.seek(self._ev_pos)
                blob = f.read()
        except OSError:
            return []
        cut = blob.rfind(b"\n")
        if cut < 0:
            return []  # no complete line yet — keep the cursor put
        self._ev_pos += cut + 1
        out = []
        for line in blob[:cut].split(b"\n"):
            line = line.strip()
            if not line:
                continue
            try:
                row = json.loads(line.decode("utf-8", "replace"))
            except ValueError:
                continue
            if isinstance(row, dict):
                out.append(row)
        return out

    @staticmethod
    def event_line(e: dict) -> str:
        kind = e.get("event", "?")
        who = f"p{e.get('claimant', '?')}"
        dead = f"p{e.get('for', '?')}"
        wp = f"p{e.get('pid', '?')}"
        blk = f"block {e.get('block', '?')}"
        if kind == "claim":
            msg = (
                f"{who} CLAIMS dead {dead}'s block "
                f"(gen {e.get('gen', '?')})"
            )
        elif kind == "recovered":
            msg = (
                f"{who} RECOVERED {dead}'s block "
                f"in {float(e.get('wall_s', 0.0)):.1f}s"
            )
        # Round 18 work-queue trail (parallel.dcn.wq_run):
        elif kind == "lease":
            msg = f"{wp} leases {blk}"
        elif kind == "steal":
            msg = (
                f"{wp} STEALS {blk} from expired p{e.get('from', '?')} "
                f"(gen {e.get('gen', '?')})"
            )
        elif kind == "speculate":
            msg = (
                f"{wp} SPECULATES on straggler p{e.get('from', '?')}'s "
                f"{blk}"
            )
        elif kind == "block_done":
            msg = (
                f"{wp} completed {blk} in "
                f"{float(e.get('wall_s', 0.0)):.1f}s"
                + (" (speculative win)" if e.get("spec") else "")
            )
        elif kind in ("spec_lost", "dup_discard"):
            msg = (
                f"{wp}'s duplicate of {blk} discarded "
                f"(lost first-complete-wins)"
            )
        elif kind == "join":
            msg = f"{wp} JOINS the fleet mid-replay"
        # Round 20 durable-journal trail:
        elif kind == "journal_adopt":
            msg = (
                f"{wp} ADOPTS {blk} from the durable journal "
                f"(completed by dead fleet's p{e.get('from', '?')})"
            )
        elif kind == "journal_resume":
            msg = (
                f"{wp} RESUMES from durable checkpoint at chunk "
                f"{e.get('cursor', '?')}"
            )
        # Round 21 black-box trail:
        elif kind == "ckpt_load":
            msg = (
                f"p{e.get('by', '?')} loads {wp}'s checkpoint at chunk "
                f"{e.get('cursor', '?')}"
            )
        elif kind == "ckpt_fallback":
            msg = (
                f"p{e.get('by', '?')} FALLS BACK from {wp}'s torn "
                f"checkpoint at chunk {e.get('cursor', '?')}"
            )
        elif kind == "fault_kill":
            msg = f"{wp} FAULT-KILLED (state {e.get('state', '?')})"
        elif kind in ("fault_inject", "fault_slow"):
            msg = (
                f"{wp} fault {e.get('class', '?')} injected"
                + (f" on {e.get('key')}" if e.get("key") else "")
            )
        else:
            msg = json.dumps(e, sort_keys=True)
        return f"dcn_launch[watch]: {msg}"

    def read(self) -> dict:
        beats = {}
        for pid in range(self.nproc):
            try:
                with open(os.path.join(self.hb_dir, f"p{pid}.json")) as f:
                    beats[pid] = json.load(f)
            except (OSError, json.JSONDecodeError):
                continue
        return beats

    def line(self, beats: dict) -> str:
        now = time.time()
        max_chunk = max(
            (int(b.get("chunk", -1)) for b in beats.values()), default=-1
        )
        segs = []
        for pid in range(self.nproc):
            b = beats.get(pid)
            if b is None:
                segs.append(f"p{pid} —")
                continue
            chunk = int(b.get("chunk", -1))
            total = b.get("total_chunks")
            age = max(0.0, now - float(b.get("t", now)))
            prev = self._prev.get(pid)
            rate = ""
            if prev is not None and b.get("t", 0) > prev[1]:
                cps = (chunk - prev[0]) / (float(b["t"]) - prev[1])
                rate = f" {cps:.1f}ch/s"
            self._prev[pid] = (chunk, float(b.get("t", now)))
            lag = max_chunk - chunk
            straggler = age > self.stall_s or (
                total and lag > max(2, self.lag_frac * int(total))
            )
            state = b.get("state", "?")
            if state == "recover" and "recovering_for" in b:
                # Round 15: a claimant re-executing a dead sibling's
                # block beats under its OWN pid with the dead pid named
                # (round 21: plus the fenced claim generation).
                state = f"recovering-p{b['recovering_for']}"
                if "recover_gen" in b:
                    state += f"@g{b['recover_gen']}"
            if "wq_block" in b and int(b.get("leased_blocks", 0)):
                # Round 18: the lease this process is executing ("spec"
                # state = speculative re-execution of a straggler's
                # block). Round 21: plus the lease generation it holds.
                state = f"{state}@b{b['wq_block']}"
                if "wq_gen" in b:
                    state += f".g{b['wq_gen']}"
            seg = (
                f"p{pid} {state} "
                f"chunk {chunk}"
                + (f"/{total}" if total is not None else "")
                + rate
            )
            if "queue_depth" in b and not int(b.get("leased_blocks", 0)):
                # Idle-but-queue-pending vs stalled-holding-a-lease: the
                # round-18 beacon extras make the distinction explicit.
                seg += f" qd={b['queue_depth']}"
            if "live_buffers" in b:
                seg += f" live={b['live_buffers']}"
            if "util_cpu" in b:
                # Fleet utilization gauge (round 13): the end-of-replay
                # gather beacon carries the mean scenario CPU utilization.
                seg += f" util={float(b['util_cpu']):.1%}"
            if "restart" in b:
                # Round 21: which supervised life this process is on
                # (KSIM_DCN_RESTART_COUNT, exported by the relauncher).
                seg += f" life={b['restart']}"
            if straggler:
                seg += " [STRAGGLER]"
            segs.append(seg)
        return "dcn_launch[watch]: " + " | ".join(segs)

    def wq_line(self, beats: dict) -> str:
        """Round 18: one line of per-block lease owners, derived from the
        ``wq_block``/``leased_blocks`` beacon extras ('' when no process
        holds a queue lease — e.g. a static-slicing fleet)."""
        owners = {}
        for pid, b in beats.items():
            if int(b.get("leased_blocks", 0)) and "wq_block" in b:
                suffix = "*" if b.get("state") == "spec" else ""
                owners.setdefault(int(b["wq_block"]), []).append(
                    f"p{pid}{suffix}"
                )
        if not owners:
            return ""
        segs = [
            f"b{bid}→{'+'.join(sorted(pids))}"
            for bid, pids in sorted(owners.items())
        ]
        return (
            "dcn_launch[watch]: wq leases " + " ".join(segs)
            + " (* = speculative)"
        )


def launch_once(
    cmd,
    args,
    nproc: int,
    tolerant: bool,
    hb_dir: str,
    watch,
    attempt: int = 0,
    resume: bool = False,
    durable: str = "",
) -> int:
    """One fleet attempt: launch ``nproc`` processes on a fresh
    coordination port, monitor them to completion, and return the
    attempt's exit code (0 = at least one process — all of them, when
    ``tolerant`` is off — completed the replay). Extracted from main()
    in round 20 so ``--supervise`` can run it in a bounded restart
    loop; ``attempt``/``resume``/``durable`` ride into every child's
    environment."""
    port = free_port()
    procs, tails = [], []
    for pid in range(nproc):
        join_delay = 0.0
        if args.join and pid >= args.nproc:
            # Joiner k defers its contribution k×delay seconds so a
            # multi-joiner launch trickles capacity in, not all at once.
            join_delay = args.join_delay * (pid - args.nproc + 1)
        env = child_env(
            pid, nproc, port, args.devices_per_proc, hb_dir,
            join_delay=join_delay, durable=durable, resume=resume,
            restart_count=attempt,
        )
        if pid == 0:
            p = subprocess.Popen(cmd, env=env)
            tails.append(None)
        else:
            p = subprocess.Popen(
                cmd, env=env, stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT, text=True,
            )
            buf: list = []
            tails.append(buf)

            def drain(proc=p, sink=buf):
                for line in proc.stdout:
                    sink.append(line)

            threading.Thread(target=drain, daemon=True).start()
        procs.append(p)

    deadline = time.monotonic() + args.timeout
    next_watch = time.monotonic() + args.watch_interval
    rc = 0
    ok_exits = 0
    first_bad = 0
    try:
        pending = set(range(nproc))
        while pending:
            if watch is not None and time.monotonic() >= next_watch:
                next_watch = time.monotonic() + args.watch_interval
                for e in watch.events():
                    print(watch.event_line(e), file=sys.stderr)
                beats = watch.read()
                if beats:
                    print(watch.line(beats), file=sys.stderr)
                    wql = watch.wq_line(beats)
                    if wql:
                        print(wql, file=sys.stderr)
                for fl in watch.flight_lines():
                    print(fl, file=sys.stderr)
            if time.monotonic() > deadline:
                print(
                    f"dcn_launch: timeout after {args.timeout}s",
                    file=sys.stderr,
                )
                rc = 124
                break
            for i in sorted(pending):
                r = procs[i].poll()
                if r is None:
                    continue
                pending.discard(i)
                if r == 0:
                    ok_exits += 1
                    continue
                if first_bad == 0:
                    first_bad = r
                if tolerant:
                    # Round 15: with recovery on a dead worker's block is
                    # claimed by a survivor — the replay can still finish.
                    # Succeed iff ANY process completes the gathered
                    # result (checked after the loop).
                    print(
                        f"dcn_launch: process {i} exited {r} — recovery "
                        "enabled, fleet continues (a survivor claims the "
                        "block)", file=sys.stderr,
                    )
                    if tails[i]:
                        sys.stderr.writelines(
                            f"[p{i}] {line}" for line in tails[i][-20:]
                        )
                    continue
                rc = r
                print(
                    f"dcn_launch: process {i} exited {r} — "
                    "killing the fleet", file=sys.stderr,
                )
                if tails[i]:
                    sys.stderr.writelines(
                        f"[p{i}] {line}" for line in tails[i][-50:]
                    )
            if rc:
                break
            time.sleep(0.1)
        if watch is not None:
            for e in watch.events():
                print(watch.event_line(e), file=sys.stderr)
        if not rc and tolerant and not pending and ok_exits == 0:
            # Every process died before completing the gather — nothing
            # holds the merged replay, so the launch failed after all.
            rc = first_bad or 1
            print(
                "dcn_launch: no process completed the replay — "
                f"exit {rc}", file=sys.stderr,
            )
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        for p in procs:
            p.wait()
    return rc


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    ap.add_argument("--nproc", type=int, default=2)
    ap.add_argument(
        "--devices-per-proc", type=int, default=4,
        help="virtual CPU devices per process (default 4: 2 procs "
             "reproduce the 8-device single-host mesh)",
    )
    ap.add_argument(
        "--timeout", type=float, default=900.0,
        help="kill the fleet after this many seconds",
    )
    ap.add_argument(
        "--watch", action="store_true",
        help="tail worker heartbeats and print fleet progress "
             "(chunks/sec per process, stragglers flagged) plus round-15 "
             "claim/recovery events to stderr",
    )
    ap.add_argument(
        "--elastic", type=int, default=0, metavar="SPARES",
        help="launch SPARES extra spare processes (no scenario block; "
             "claim-eligible capacity) and enable survivor recovery: a "
             "worker dying mid-replay no longer kills the fleet — the "
             "launch succeeds as long as any process completes "
             "(KSIM_DCN_SPARES / KSIM_DCN_RECOVER)",
    )
    ap.add_argument(
        "--join", type=int, default=0, metavar="JOINERS",
        help="round 18: launch JOINERS extra processes at the tail of "
             "the pid range and enable the work-stealing block queue "
             "(KSIM_DCN_WORKQUEUE=1 unless set): each joiner defers its "
             "queue contribution by --join-delay seconds (staggered), "
             "then leases pending blocks — true elastic capacity, not "
             "just dead-block claims",
    )
    ap.add_argument(
        "--join-delay", type=float, default=5.0, metavar="SECONDS",
        help="base contribution delay for --join processes (joiner k "
             "waits k×delay seconds; KSIM_DCN_JOIN_DELAY_S)",
    )
    ap.add_argument(
        "--watch-interval", type=float, default=2.0,
        help="seconds between --watch progress lines",
    )
    ap.add_argument(
        "--durable", default=os.environ.get("KSIM_DCN_DURABLE_DIR", ""),
        metavar="DIR",
        help="round 20: durability-journal directory "
             "(KSIM_DCN_DURABLE_DIR) — the fleet mirrors checkpoint "
             "blobs, work-queue results and the done/lease ledger there, "
             "so a whole-fleet crash is restartable with --resume or "
             "--supervise",
    )
    ap.add_argument(
        "--resume", action="store_true",
        help="round 20: seed the fleet from an existing --durable "
             "journal (KSIM_DCN_RESUME=1): completed blocks are adopted "
             "without re-execution, in-flight blocks resume from their "
             "newest complete durable cursor",
    )
    ap.add_argument(
        "--supervise", action="store_true",
        help="round 20: watch the fleet for whole-fleet death "
             "(coordinator included) and relaunch it with --resume on a "
             "fresh coordination port, under the --max-restarts / "
             "--restart-backoff budget; requires --durable",
    )
    ap.add_argument(
        "--max-restarts", type=int, default=3, metavar="N",
        help="restart budget for --supervise (default 3)",
    )
    ap.add_argument(
        "--restart-backoff", type=float, default=1.0, metavar="SECONDS",
        help="base delay before a supervised relaunch; doubles per "
             "attempt (default 1.0)",
    )
    ap.add_argument(
        "--flight", default=os.environ.get("KSIM_FLIGHT_WATCH", ""),
        metavar="PATH",
        help="round 16: with --watch, also tail this flight-recorder "
             "stream (process 0's path; .p<pid> siblings are tailed "
             "automatically) and print rolling pps / pager stalls "
             "per process — point it at the same path the "
             "children's flightRecorder: config writes. Missing streams "
             "are tolerated (the recorder is off by default)",
    )
    ap.add_argument("cmd", nargs=argparse.REMAINDER,
                    help="command to run in every process (after --)")
    args = ap.parse_args(argv)
    cmd = args.cmd
    if cmd and cmd[0] == "--":
        cmd = cmd[1:]
    if not cmd:
        ap.error("no command given (append: -- python -m ... )")
    if args.nproc < 1:
        ap.error("--nproc must be >= 1")
    if args.elastic < 0:
        ap.error("--elastic must be >= 0")
    if args.join < 0:
        ap.error("--join must be >= 0")
    if args.join and args.elastic:
        ap.error(
            "--join and --elastic are mutually exclusive: joiners ride "
            "the work queue (any process leases any pending block), "
            "which subsumes spare capacity"
        )
    if args.join_delay < 0:
        ap.error("--join-delay must be >= 0")
    if args.supervise and not args.durable:
        ap.error(
            "--supervise requires --durable DIR (or KSIM_DCN_DURABLE_DIR)"
            ": without a journal there is nothing for a restarted fleet "
            "to resume from"
        )
    if args.resume and not args.durable:
        ap.error("--resume requires --durable DIR (or KSIM_DCN_DURABLE_DIR)")
    if args.max_restarts < 0:
        ap.error("--max-restarts must be >= 0")
    if args.restart_backoff < 0:
        ap.error("--restart-backoff must be >= 0")
    nproc = args.nproc + args.elastic + args.join
    elastic = args.elastic > 0
    if elastic:
        # Spares own no scenario block (parallel.dcn.spare_count); the
        # recovery knob defaults on so survivors/spare claim dead blocks.
        os.environ["KSIM_DCN_SPARES"] = str(args.elastic)
        os.environ.setdefault("KSIM_DCN_RECOVER", "1")
    if args.join:
        # Round 18 joiners are spare-pid processes under the work queue:
        # they own no static block, connect at launch (the runtime
        # barriers on connects) and defer their queue contribution.
        os.environ["KSIM_DCN_SPARES"] = str(args.join)
        os.environ.setdefault("KSIM_DCN_WORKQUEUE", "1")
    tolerant = elastic or str(
        os.environ.get("KSIM_DCN_RECOVER", "0")
    ).strip().lower() in ("1", "true", "yes", "on")

    hb_dir = ""
    watch = None
    if args.watch:
        hb_dir = tempfile.mkdtemp(prefix="ksim_hb_")
        watch = FleetWatch(
            hb_dir, nproc,
            stall_s=float(os.environ.get("KSIM_DCN_STALL_S", "60")),
            flight_path=args.flight,
        )
    try:
        if not args.supervise:
            return launch_once(
                cmd, args, nproc, tolerant, hb_dir, watch,
                attempt=0, resume=args.resume, durable=args.durable,
            )
        # Round 20 supervision loop: each attempt gets a fresh
        # coordination port (the old coordinator may have died holding
        # the socket); every relaunch resumes from the journal with the
        # attempt number exported. Whole-fleet death is exactly "the
        # attempt returned nonzero": a tolerant fleet already absorbs
        # partial death in-attempt, so a failed attempt means nobody
        # completed the replay — coordinator death included.
        attempt = 0
        while True:
            rc = launch_once(
                cmd, args, nproc, tolerant, hb_dir, watch,
                attempt=attempt,
                resume=args.resume or attempt > 0,
                durable=args.durable,
            )
            if rc == 0:
                if attempt > 0:
                    print(
                        f"dcn_launch: fleet completed after {attempt} "
                        "supervised restart(s)", file=sys.stderr,
                    )
                return 0
            if attempt >= args.max_restarts:
                print(
                    f"dcn_launch: restart budget exhausted after "
                    f"{attempt} restart(s) — exit {rc}", file=sys.stderr,
                )
                return rc
            delay = args.restart_backoff * (2 ** attempt)
            attempt += 1
            print(
                f"dcn_launch: whole fleet died (exit {rc}) — "
                f"relaunching with --resume in {delay:.1f}s "
                f"(attempt {attempt}/{args.max_restarts})",
                file=sys.stderr,
            )
            time.sleep(delay)
    finally:
        if hb_dir:
            shutil.rmtree(hb_dir, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
