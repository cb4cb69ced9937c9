#!/usr/bin/env python
"""Fleet black box post-mortem (round 21): merge every artifact a DCN
run leaves behind into ONE causally-ordered timeline, export a
Perfetto/Chrome trace, and audit the fleet protocol invariants.

    python scripts/fleet_postmortem.py RUN_DIR [--out trace.json]
        [--flight PATH] [--journal PATH] [--supervisor-log PATH]
        [--jsonl PATH] [--quiet]

``RUN_DIR`` is the heartbeat mirror directory (``KSIM_DCN_HB_DIR``):
``events.jsonl`` (the dcn._mirror_event trail), ``p<pid>.json`` final
beacons, and — when the run was durable — a ``journal/`` tree
(``KSIM_DCN_DURABLE_DIR``). ``--flight`` names process 0's flight
stream (siblings at ``PATH.p<pid>``, the dcn suffix convention);
``--supervisor-log`` a captured ``dcn_launch --supervise`` transcript.

Every input is treated as potentially TORN (a SIGKILL drill writes
right up to the kill): a truncated final line, a missing per-process
file, or out-of-order timestamps degrade to a partial timeline plus a
warning — never a crash, never a false invariant violation.

The audit (exit 1 names the violated invariant and prints the block's
full event chain):

- ``one-done-winner``       exactly one done-CAS winner per block
                            episode, and the durable done ledger names
                            that winner
- ``lease-gen-monotonic``   lease/steal/claim generations never regress
- ``adopt-no-reexec``       a journal-adopted block is never re-executed
                            after the adoption
- ``resume-cursor-bounded`` a resumed cursor never exceeds the newest
                            published (and, when durable, the newest
                            complete durable) cursor
- ``steal-after-stale-renewal``  every steal observed a renewal older
                            than the stall threshold
- ``dup-has-winner``        every duplicate discard lost to a real
                            completion

``faultline_fuzz.py`` runs this tool over every drill's artifacts as
the final check after the byte-parity oracle (wired round 21).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import zlib
from typing import Dict, List, Optional

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

# Event kinds that open an execution attempt of a block (one "episode"
# runs from the first attempt to its done/adopt completion).
_ATTEMPT_KINDS = ("lease", "steal", "speculate")
_FAULT_KINDS = ("fault_inject", "fault_kill", "fault_slow")

# Flow-arrow phases for the Chrome trace: start / step / finish.
_EPS = 1e-3


def _int(v, default: int = 0) -> int:
    """Tolerant int coercion — torn inputs may hold any value."""
    try:
        return int(v)
    except (TypeError, ValueError):
        return default


def _emitting_pid(ev: dict) -> int:
    """The process that EMITTED the event (Perfetto track grouping):
    claim/recovered are emitted by the claimant, everything else by
    ``pid`` (for checkpoint events ``by`` — the loader — when present,
    since ``pid`` names the checkpoint OWNER there)."""
    kind = ev.get("event", ev.get("kind"))
    if kind in ("claim", "recovered"):
        return int(ev.get("claimant", -1))
    if kind in ("ckpt_load", "ckpt_fallback", "journal_resume"):
        return int(ev.get("by", ev.get("pid", -1)))
    try:
        return int(ev.get("pid", -1))
    except (TypeError, ValueError):
        return -1


def _read_jsonl_tolerant(path: str, warnings: List[str]):
    """Parse one line-delimited JSON file, tolerating a torn final line
    (SIGKILL mid-write) and arbitrary malformed lines. Returns a list
    of dict rows; a missing file returns [] with a warning."""
    rows = []
    try:
        with open(path, "rb") as f:
            blob = f.read()
    except OSError as e:
        warnings.append(f"{os.path.basename(path)}: unreadable ({e})")
        return rows
    lines = blob.split(b"\n")
    for i, line in enumerate(lines):
        line = line.strip()
        if not line:
            continue
        try:
            row = json.loads(line.decode("utf-8", "replace"))
        except ValueError:
            what = (
                "torn final line"
                if i >= len(lines) - 2
                else f"malformed line {i + 1}"
            )
            warnings.append(
                f"{os.path.basename(path)}: {what} skipped"
            )
            continue
        if isinstance(row, dict):
            rows.append(row)
    return rows


def load_events(run_dir: str, warnings: List[str]) -> List[dict]:
    """The primary source: ``events.jsonl`` (append-only, every process
    writes one line per fleet event, wall-stamped ``t``)."""
    path = os.path.join(run_dir, "events.jsonl")
    if not os.path.exists(path):
        warnings.append("events.jsonl: missing — timeline is partial")
        return []
    return _read_jsonl_tolerant(path, warnings)


def load_beacons(run_dir: str, warnings: List[str]) -> Dict[int, dict]:
    """Final heartbeat mirrors ``p<pid>.json`` (last state per process;
    faultline may have torn them — unparseable means absent)."""
    beacons: Dict[int, dict] = {}
    try:
        names = sorted(os.listdir(run_dir))
    except OSError:
        return beacons
    for name in names:
        if not (name.startswith("p") and name.endswith(".json")):
            continue
        try:
            pid = int(name[1:-5])
        except ValueError:
            continue
        try:
            with open(os.path.join(run_dir, name)) as f:
                beacons[pid] = json.load(f)
        except (OSError, ValueError):
            warnings.append(f"{name}: torn beacon skipped")
    return beacons


def load_flight_streams(
    flight: Optional[str], warnings: List[str]
) -> List[dict]:
    """Fleet rows from the per-process flight streams (``PATH`` +
    ``PATH.p<pid>`` siblings). Used to corroborate/extend the
    events.jsonl trail — fleet rows carry the same trace stamps. A
    missing sibling is a warning, not an error (the process may have
    died before its recorder opened)."""
    if not flight:
        return []
    rows = []
    base_dir = os.path.dirname(flight) or "."
    base_name = os.path.basename(flight)
    paths = [flight]
    try:
        for name in sorted(os.listdir(base_dir)):
            if name.startswith(base_name + ".p"):
                paths.append(os.path.join(base_dir, name))
    except OSError:
        pass
    missing = [p for p in paths if not os.path.exists(p)]
    for p in missing:
        warnings.append(
            f"flight stream {os.path.basename(p)}: missing — that "
            f"process's rows are absent from the timeline"
        )
    for p in paths:
        if p in missing:
            continue
        for row in _read_jsonl_tolerant(p, warnings):
            if row.get("kind") == "flight" and row.get("event") == "fleet":
                rows.append(row)
    return rows


def load_journal(journal: Optional[str], warnings: List[str]) -> dict:
    """Durable-journal facts for the audit: newest COMPLETE checkpoint
    cursor per (pid, block) — complete means ``manifest.json`` parses —
    and the work-queue done/lease ledgers."""
    out = {"ckpt": {}, "done": {}, "lease": {}}
    if not journal:
        return out
    if not os.path.isdir(journal):
        warnings.append(f"journal {journal}: missing — durable facts absent")
        return out
    ck = os.path.join(journal, "ckpt")
    if os.path.isdir(ck):
        for ep in sorted(os.listdir(ck)):
            for pid in sorted(
                os.listdir(os.path.join(ck, ep))
                if os.path.isdir(os.path.join(ck, ep)) else []
            ):
                pdir = os.path.join(ck, ep, pid)
                if not os.path.isdir(pdir):
                    continue
                for blk in sorted(os.listdir(pdir)):
                    bdir = os.path.join(pdir, blk)
                    if not os.path.isdir(bdir):
                        continue
                    for cur in sorted(os.listdir(bdir)):
                        man = os.path.join(bdir, cur, "manifest.json")
                        try:
                            with open(man) as f:
                                json.load(f)
                            cursor = int(cur)
                        except (OSError, ValueError):
                            continue  # in-flight / torn — not complete
                        key = (int(pid), blk)
                        if cursor > out["ckpt"].get(key, -(10**9)):
                            out["ckpt"][key] = cursor
    wq = os.path.join(journal, "wq")
    if os.path.isdir(wq):
        for seq in sorted(os.listdir(wq)):
            sdir = os.path.join(wq, seq)
            if not os.path.isdir(sdir):
                continue
            for name in sorted(os.listdir(sdir)):
                for sub in ("done", "lease"):
                    d = os.path.join(sdir, name, sub)
                    if not os.path.isdir(d):
                        continue
                    for bid in sorted(os.listdir(d)):
                        try:
                            with open(os.path.join(d, bid)) as f:
                                meta = json.load(f)
                            out[sub][int(bid)] = meta
                        except (OSError, ValueError):
                            warnings.append(
                                f"journal {sub}/{bid}: torn ledger "
                                f"record skipped"
                            )
    return out


def load_supervisor_log(
    path: Optional[str], warnings: List[str]
) -> dict:
    """Supervisor transcript facts: relaunch count (the
    ``KSIM_DCN_RESTART_COUNT`` lives the beacons also carry)."""
    info = {"relaunches": 0, "lines": 0}
    if not path:
        return info
    try:
        with open(path, errors="replace") as f:
            for line in f:
                info["lines"] += 1
                if "relaunching" in line:
                    info["relaunches"] += 1
    except OSError as e:
        warnings.append(f"supervisor log: unreadable ({e})")
    return info


def build_timeline(
    events: List[dict], flight_rows: List[dict], warnings: List[str]
) -> List[dict]:
    """One causally-ordered merged timeline. events.jsonl rows carry a
    wall stamp ``t``; flight fleet rows are deduplicated against them
    by span (both sides carry identical round-21 stamps) and slot in
    with the stream's ``ts`` when it is real, else by fill-forward
    order. Out-of-order stamps across processes demote to a warning +
    stable sort — never a crash."""
    timeline = []
    seen_spans = set()
    for i, ev in enumerate(events):
        e = dict(ev)
        e["_seq"] = i
        e["_t"] = float(ev.get("t", 0.0) or 0.0)
        timeline.append(e)
        if ev.get("span"):
            seen_spans.add((ev.get("span"), ev.get("event", ev.get("kind"))))
    base = len(timeline)
    for j, row in enumerate(flight_rows):
        kind = row.get("fleet_event")
        span = row.get("span")
        if span and (span, kind) in seen_spans:
            continue  # corroborates an events.jsonl row — already in
        e = {
            k: v for k, v in row.items()
            if k not in ("kind", "schema", "ts")
        }
        e["event"] = kind or "?"
        e.pop("fleet_event", None)
        e["_seq"] = base + j
        e["_t"] = float(row.get("ts", 0.0) or 0.0)
        e["_from_flight"] = 1
        timeline.append(e)
    # Fill-forward zero/absent stamps so file order is preserved for
    # deterministic-scrubbed streams.
    last = 0.0
    for e in timeline:
        if e["_t"] <= 0.0:
            e["_t"] = last
        last = e["_t"]
    # Out-of-order detection BEFORE the stable sort repairs it.
    prev = None
    disorder = 0
    for e in timeline:
        if prev is not None and e["_t"] < prev - _EPS:
            disorder += 1
        prev = e["_t"]
    if disorder:
        warnings.append(
            f"{disorder} event(s) carried out-of-order timestamps "
            f"across processes — timeline re-sorted (clock skew); "
            f"causal links follow trace ids, not wall order"
        )
    timeline.sort(key=lambda e: (e["_t"], e["_seq"]))
    return timeline


# ---------------------------------------------------------------------------
# Invariant audit


def _block_key(ev: dict):
    """Group key for block-lifecycle invariants: the trace id when
    stamped, else the raw block id (pre-round-21 event files)."""
    tr = ev.get("trace")
    if isinstance(tr, str) and tr.startswith("blk:"):
        return tr
    if ev.get("event") in (
        "lease", "steal", "speculate", "block_done", "spec_lost",
        "dup_discard", "journal_adopt",
    ) and ev.get("block") is not None and not isinstance(
        ev.get("block"), list
    ):
        return f"blk:{ev['block']}"
    return None


def audit(timeline: List[dict], journal: dict) -> List[dict]:
    """Run the six protocol invariants over the merged timeline.
    Returns violations: ``{"invariant", "trace", "detail", "chain"}``
    where ``chain`` is the full ordered event list for the offending
    block/cursor. Conservative by construction: an invariant whose
    evidence is absent (old event files, no journal) is SKIPPED, not
    violated — torn inputs degrade coverage, never correctness."""
    violations = []
    by_block: Dict[str, List[dict]] = {}
    for ev in timeline:
        key = _block_key(ev)
        if key is not None:
            by_block.setdefault(key, []).append(ev)

    def _chain(evs):
        return [
            {k: v for k, v in e.items() if not k.startswith("_")}
            for e in evs
        ]

    for trace_id, evs in sorted(by_block.items()):
        # Episode segmentation: within one wq_run a block's gen-0 lease
        # CAS can only be won once, so a SECOND gen-0 lease means a
        # fresh KV epoch — a later wq_run reusing block ids, or a
        # supervised restart re-executing an in-flight block. Each
        # episode is audited independently (a restart legitimately
        # re-opens gen 0 after the dead fleet's steals).
        episodes: List[List[dict]] = [[]]
        for e in evs:
            k = e.get("event")
            if (
                k == "lease"
                and _int(e.get("gen", 0) or 0) == 0
                and any(
                    x.get("event") in _ATTEMPT_KINDS
                    for x in episodes[-1]
                )
            ):
                episodes.append([])
            episodes[-1].append(e)
        for ep in episodes:
            dones = [e for e in ep if e.get("event") == "block_done"]
            adopts = [e for e in ep if e.get("event") == "journal_adopt"]
            attempts = [
                e for e in ep if e.get("event") in _ATTEMPT_KINDS
            ]
            dups = [
                e for e in ep
                if e.get("event") in ("dup_discard", "spec_lost")
            ]
            # 1. exactly one done-winner per block episode.
            if len(dones) > 1:
                violations.append({
                    "invariant": "one-done-winner",
                    "trace": trace_id,
                    "detail": (
                        f"{len(dones)} done-CAS winners: "
                        + ", ".join(
                            f"p{d.get('pid')}@g{d.get('gen')}"
                            for d in dones
                        )
                    ),
                    "chain": _chain(ep),
                })
            # 1b. the durable done ledger must name the winner.
            if len(dones) == 1 and trace_id.startswith("blk:"):
                tail = trace_id[4:]
                if tail.isdigit() and int(tail) in journal.get("done", {}):
                    led = journal["done"][int(tail)]
                    d = dones[0]
                    if (
                        _int(led.get("pid"), -1) != _int(d.get("pid"), -2)
                        or _int(led.get("gen", 0) or 0)
                        != _int(d.get("gen", 0) or 0)
                    ):
                        violations.append({
                            "invariant": "one-done-winner",
                            "trace": trace_id,
                            "detail": (
                                f"durable done ledger names "
                                f"p{led.get('pid')}@g{led.get('gen')} "
                                f"but the done-CAS winner was "
                                f"p{d.get('pid')}@g{d.get('gen')}"
                            ),
                            "chain": _chain(ep),
                        })
            # 2. lease/steal generations never regress.
            max_gen = -1
            for e in attempts:
                g = _int(e.get("gen", 0) or 0)
                if e.get("event") == "speculate":
                    continue  # speculation shares the holder's gen
                if g < max_gen:
                    violations.append({
                        "invariant": "lease-gen-monotonic",
                        "trace": trace_id,
                        "detail": (
                            f"{e.get('event')} at gen {g} after gen "
                            f"{max_gen} was already open"
                        ),
                        "chain": _chain(ep),
                    })
                    break
                max_gen = max(max_gen, g)
            # 3. adopted blocks never re-executed after the adoption.
            if adopts:
                t_adopt = min(a["_t"] for a in adopts)
                seq_adopt = min(a["_seq"] for a in adopts)
                re_exec = [
                    e for e in attempts
                    if (e["_t"], e["_seq"]) > (t_adopt, seq_adopt)
                ]
                if re_exec:
                    violations.append({
                        "invariant": "adopt-no-reexec",
                        "trace": trace_id,
                        "detail": (
                            f"{re_exec[0].get('event')} by "
                            f"p{re_exec[0].get('pid')} after the block "
                            f"was adopted from the durable journal"
                        ),
                        "chain": _chain(ep),
                    })
            # 5. every steal observed a stale renewal.
            for e in ep:
                if e.get("event") != "steal":
                    continue
                age = e.get("renew_age_s")
                thr = e.get("threshold_s")
                if age is None or thr is None:
                    continue  # pre-round-21 event file — no evidence
                try:
                    age, thr = float(age), float(thr)
                except (TypeError, ValueError):
                    continue  # torn row — not evidence
                if age + _EPS < thr:
                    violations.append({
                        "invariant": "steal-after-stale-renewal",
                        "trace": trace_id,
                        "detail": (
                            f"steal by p{e.get('pid')} with renewal "
                            f"age {age}s below the {thr}s stall "
                            f"threshold"
                        ),
                        "chain": _chain(ep),
                    })
            # 6. every duplicate discard lost to a real completion.
            # The winner's block_done event OR a durable done-ledger
            # entry counts — a winner killed between its CAS and the
            # mirror write leaves only the ledger as evidence.
            tail = trace_id.split(":", 1)[1] if ":" in trace_id else ""
            in_ledger = (
                tail.isdigit() and int(tail) in journal.get("done", {})
            )
            if dups and not dones and not adopts and not in_ledger:
                violations.append({
                    "invariant": "dup-has-winner",
                    "trace": trace_id,
                    "detail": (
                        f"{dups[0].get('event')} by "
                        f"p{dups[0].get('pid')} but no done-CAS winner "
                        f"exists for the block"
                    ),
                    "chain": _chain(ep),
                })

    # 2b. static recovery claims: generations never regress per trace.
    claims: Dict[str, int] = {}
    for ev in timeline:
        if ev.get("event") != "claim":
            continue
        tr = ev.get("trace") or f"blk:s{ev.get('for')}"
        g = _int(ev.get("gen", 0) or 0)
        if g < claims.get(tr, -1):
            violations.append({
                "invariant": "lease-gen-monotonic",
                "trace": tr,
                "detail": (
                    f"claim at gen {g} after gen {claims[tr]} was "
                    f"already open"
                ),
                "chain": [
                    {k: v for k, v in e.items() if not k.startswith("_")}
                    for e in timeline
                    if (e.get("trace") or f"blk:s{e.get('for')}") == tr
                ],
            })
        claims[tr] = max(claims.get(tr, -1), g)

    # 4. resumed cursor ≤ newest published / newest complete durable.
    published: Dict[int, int] = {}
    for ev in timeline:
        kind = ev.get("event", ev.get("kind"))
        if kind == "ckpt_publish":
            p = _int(ev.get("pid"), -1)
            published[p] = max(
                published.get(p, -(10**9)), _int(ev.get("cursor", 0))
            )
    for ev in timeline:
        if ev.get("event") not in ("ckpt_load", "journal_resume"):
            continue
        owner = _int(ev.get("pid"), -1)
        cursor = _int(ev.get("cursor", 0))
        caps = []
        blk = ev.get("block")
        if isinstance(blk, list) and len(blk) == 2:
            key = (owner, f"{blk[0]}-{blk[1]}")
            if key in journal.get("ckpt", {}):
                caps.append(journal["ckpt"][key])
        if owner in published:
            caps.append(published[owner])
        if not caps:
            continue  # no durable/published evidence — skip, not fail
        # Max of available evidence: the journal mirror is best-effort
        # and may lag the KV publish, so either source alone could
        # undercount and false-positive a legitimate resume.
        cap = max(caps)
        if cursor > cap:
            violations.append({
                "invariant": "resume-cursor-bounded",
                "trace": f"ckpt:{owner}:{cursor}",
                "detail": (
                    f"resumed cursor {cursor} exceeds the newest "
                    f"complete cursor {cap} for p{owner}"
                ),
                "chain": [
                    {k: v for k, v in e.items() if not k.startswith("_")}
                    for e in timeline
                    if _int(e.get("pid"), -2) == owner
                    and e.get("event", e.get("kind"))
                    in ("ckpt_publish", "ckpt_load", "journal_resume",
                        "ckpt_fallback")
                ],
            })
    return violations


# ---------------------------------------------------------------------------
# Perfetto / Chrome trace export


def _flow_groups(timeline: List[dict]) -> Dict[str, List[dict]]:
    """Events grouped by the trace id their flow arrow follows. An
    event with a ``link`` field joins BOTH groups — that is how a block
    arrow crosses a process death (dead pid's ckpt publish → survivor's
    load → recovery/steal)."""
    groups: Dict[str, List[dict]] = {}
    for ev in timeline:
        for key in (ev.get("trace"), ev.get("link")):
            if isinstance(key, str) and key:
                groups.setdefault(key, []).append(ev)
    return groups


def export_perfetto(
    timeline: List[dict], path: str, links_resolved: Optional[list] = None
) -> int:
    """Write a Chrome trace-event JSON: one track group per process,
    every fleet event a short slice (faultline injections as instant
    markers), and one flow arrow per trace id threading its hops in
    causal order — arrows cross track groups wherever a block changed
    hands. Returns the number of flow bindings emitted."""
    if not timeline:
        out = {"traceEvents": [], "displayTimeUnit": "ms"}
        with open(path, "w") as f:
            json.dump(out, f)
        return 0
    t0 = min(e["_t"] for e in timeline if e["_t"] > 0.0) if any(
        e["_t"] > 0.0 for e in timeline
    ) else 0.0
    events_out = []
    pids = sorted(
        {p for p in (_emitting_pid(e) for e in timeline) if p >= 0}
    )
    for p in pids:
        events_out.append({
            "name": "process_name", "ph": "M", "pid": p, "tid": 0,
            "args": {"name": f"ksim worker p{p}"},
        })
        events_out.append({
            "name": "thread_name", "ph": "M", "pid": p, "tid": 0,
            "args": {"name": "fleet events"},
        })

    def _us(e) -> int:
        return max(0, int(round((e["_t"] - t0) * 1e6)))

    for i, ev in enumerate(timeline):
        kind = str(ev.get("event", ev.get("kind", "?")))
        pid = _emitting_pid(ev)
        if pid < 0:
            pid = 0
        args = {
            k: v for k, v in ev.items() if not k.startswith("_")
        }
        name = ev.get("span") or kind
        if kind in _FAULT_KINDS:
            events_out.append({
                "name": name, "ph": "i", "s": "p",
                "pid": pid, "tid": 0, "ts": _us(ev),
                "cat": "faultline", "args": args,
            })
            continue
        cat = (
            str(ev.get("trace", "")).split(":", 1)[0]
            if ev.get("trace") else "fleet"
        )
        events_out.append({
            "name": name, "ph": "X", "dur": 500,
            "pid": pid, "tid": 0, "ts": _us(ev),
            "cat": cat or "fleet", "args": args,
        })
    flows = 0
    for trace_id, members in sorted(_flow_groups(timeline).items()):
        if len(members) < 2:
            continue
        fid = zlib.crc32(trace_id.encode()) & 0x7FFFFFFF
        ordered = sorted(members, key=lambda e: (e["_t"], e["_seq"]))
        for j, ev in enumerate(ordered):
            pid = _emitting_pid(ev)
            if pid < 0:
                pid = 0
            ph = "s" if j == 0 else ("f" if j == len(ordered) - 1 else "t")
            rec = {
                "name": trace_id, "ph": ph, "id": fid,
                "pid": pid, "tid": 0, "ts": _us(ev) + 1,
                "cat": "flow",
            }
            if ph == "f":
                rec["bp"] = "e"
            events_out.append(rec)
            flows += 1
    out = {"traceEvents": events_out, "displayTimeUnit": "ms"}
    with open(path, "w") as f:
        json.dump(out, f)
    return flows


def resolve_links(timeline: List[dict]) -> int:
    """Count parent/link references that resolve to an emitted span —
    the health gauge of the causal graph."""
    spans = {
        e.get("span") for e in timeline if isinstance(e.get("span"), str)
    }
    traces = {
        e.get("trace") for e in timeline
        if isinstance(e.get("trace"), str)
    }
    resolved = 0
    for e in timeline:
        par = e.get("parent")
        if isinstance(par, str) and (
            par in spans or any(
                isinstance(s, str) and s.startswith(par) for s in spans
            )
        ):
            resolved += 1
        link = e.get("link")
        if isinstance(link, str) and link in traces:
            resolved += 1
    return resolved


def run_postmortem(
    run_dir: str,
    flight: Optional[str] = None,
    journal: Optional[str] = None,
    supervisor_log: Optional[str] = None,
    out: Optional[str] = None,
    jsonl: Optional[str] = None,
    quiet: bool = False,
) -> dict:
    """Programmatic entry point (faultline_fuzz's cap and the tests).
    Returns the full report; ``rc`` is 0 (clean, possibly with
    warnings) or 1 (invariant violation)."""
    t_start = time.perf_counter()
    warnings: List[str] = []
    if journal is None:
        cand = os.path.join(run_dir, "journal")
        journal = cand if os.path.isdir(cand) else None
    events = load_events(run_dir, warnings)
    beacons = load_beacons(run_dir, warnings)
    flight_rows = load_flight_streams(flight, warnings)
    jfacts = load_journal(journal, warnings)
    sup = load_supervisor_log(supervisor_log, warnings)
    timeline = build_timeline(events, flight_rows, warnings)
    violations = audit(timeline, jfacts)
    links = resolve_links(timeline)
    flows = 0
    if out:
        flows = export_perfetto(timeline, out)
    wall = time.perf_counter() - t_start
    inv_names = (
        "one-done-winner", "lease-gen-monotonic", "adopt-no-reexec",
        "resume-cursor-bounded", "steal-after-stale-renewal",
        "dup-has-winner",
    )
    hit = {v["invariant"] for v in violations}
    report = {
        "rc": 1 if violations else 0,
        "run_dir": run_dir,
        "events_ingested": len(timeline),
        "flight_rows": len(flight_rows),
        "beacons": len(beacons),
        "links_resolved": links,
        "flow_bindings": flows,
        "relaunches": sup.get("relaunches", 0),
        "violations": violations,
        "warnings": warnings,
        "invariants": {
            n: ("violated" if n in hit else "ok") for n in inv_names
        },
        "audit_wall_s": round(wall, 6),
    }
    if jsonl:
        row = {
            "ts": time.time(),
            "schema": 7,  # rides the current JSONL rev (v7, round 22)
            "kind": "postmortem",
            "events_ingested": report["events_ingested"],
            "links_resolved": report["links_resolved"],
            "violations": len(violations),
            "warnings": len(warnings),
            "audit_wall_s": report["audit_wall_s"],
            "invariants": report["invariants"],
        }
        try:
            from kubernetes_simulator_tpu.utils.metrics import (
                deterministic_jsonl,
            )

            if deterministic_jsonl():
                row["ts"] = 0.0
                row["audit_wall_s"] = 0.0
        except Exception:
            pass
        with open(jsonl, "a") as f:
            f.write(json.dumps(row, sort_keys=True) + "\n")
    if not quiet:
        _print_report(report)
    return report


def _print_report(report: dict) -> None:
    print(
        f"fleet_postmortem: {report['events_ingested']} events "
        f"({report['flight_rows']} flight rows, "
        f"{report['beacons']} beacons), "
        f"{report['links_resolved']} causal links resolved, "
        f"{report['flow_bindings']} flow bindings, "
        f"audit {report['audit_wall_s'] * 1e3:.1f}ms"
    )
    for w in report["warnings"]:
        print(f"fleet_postmortem: warning: {w}")
    for name, verdict in report["invariants"].items():
        print(f"fleet_postmortem: invariant {name}: {verdict}")
    for v in report["violations"]:
        print(
            f"fleet_postmortem: VIOLATION {v['invariant']} "
            f"[{v['trace']}]: {v['detail']}"
        )
        print("fleet_postmortem: offending event chain:")
        for e in v["chain"]:
            print("  " + json.dumps(e, sort_keys=True))
    if not report["violations"]:
        print("fleet_postmortem: all invariants hold")


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__.split("\n\n")[0],
    )
    ap.add_argument("run_dir", help="heartbeat mirror dir (KSIM_DCN_HB_DIR)")
    ap.add_argument("--out", help="write a Perfetto/Chrome trace JSON here")
    ap.add_argument(
        "--flight",
        help="process 0's flight stream (siblings at PATH.p<pid>)",
    )
    ap.add_argument(
        "--journal",
        help="durable journal dir (default: RUN_DIR/journal when present)",
    )
    ap.add_argument("--supervisor-log", help="dcn_launch --supervise output")
    ap.add_argument(
        "--jsonl", help="append a schema-v7 'postmortem' summary row here"
    )
    ap.add_argument("--quiet", action="store_true")
    args = ap.parse_args(argv)
    if not os.path.isdir(args.run_dir):
        print(f"fleet_postmortem: {args.run_dir}: not a directory")
        return 2
    report = run_postmortem(
        args.run_dir,
        flight=args.flight,
        journal=args.journal,
        supervisor_log=args.supervisor_log,
        out=args.out,
        jsonl=args.jsonl,
        quiet=args.quiet,
    )
    return report["rc"]


if __name__ == "__main__":
    sys.exit(main())
