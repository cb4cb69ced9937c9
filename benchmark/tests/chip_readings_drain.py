#!/usr/bin/env python3
"""The readings the drained cell's limits and its configuration's record are
set from (PERF.md §2, §6), in one process: for each of ``--seeds`` seeds the
cell's trace is made, ONE engine built at the cell's own size and one whole
batch run; the comparison that decides ``correct`` reads its numbers five
times over that batch's answers (a sound run, and the four controls: the
reference in bfloat16, with the evicted left in place, with no node ever
back, with the evicted queued behind everything); and the plans' own numbers
are printed from the three answers: per plan its evictions, the evicted
re-bound in the boundary that evicted them or later, queued at the end,
dropped, stranded gang members, and the three targets of the configuration
(``scenarios.measured`` in its file is this script's first seed). One JSON
line a seed. On the chip:

    python3 benchmark/tests/chip_readings_drain.py --seeds 1

Not run by the benchmark's own runs. Off the TPU it needs ``--rehearse``.
"""

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH.parent), str(BENCH)]

import run  # noqa: E402

CELL = "borg10k-drain128"
CONTROLS = (("sound", None), ("bf16", "bf16"), ("no_evict", "no-evict"),
            ("no_return", "no-return"), ("evicted_last", "evicted-last"))


def plan_rows(engine, answers) -> list:
    """Per plan, from the answers alone: what its evictions came to. An
    eviction was re-bound if the task has a later row bound by a pass, or
    holds a node at the end by a pass at or after it."""
    bind, log = answers["bind_boundary"], answers["eviction_log"]
    gang = np.asarray(engine.engine.pods.group_id) >= 0
    out = []
    for s, plan in enumerate(engine.plans):
        rows = log[s][log[s][:, 1] >= 0]
        b, task, bound = rows[:, 0], rows[:, 1], rows[:, 3]
        order = np.lexsort((b, task))
        last = np.ones(len(rows), bool)
        last[order[:-1]] = task[order][1:] != task[order][:-1]
        nxt = np.full(len(rows), -9, np.int64)  # the pass that bound it again
        nxt[order[:-1]] = np.where(task[order][1:] == task[order][:-1],
                                   bound[order][1:], -9)
        nxt[last] = bind[s][task[last]]
        again = nxt >= 0
        out.append({
            "plan": s, "step": plan["step"], "order": plan["order"],
            "outFor": plan["outFor"], "first": plan["first"],
            "evictions": len(rows),
            "reboundSameBoundary": int((again & (nxt == b)).sum()),
            "reboundLater": int((again & (nxt > b)).sum()),
            "queuedAtTheEnd": int((last & (nxt == -2)).sum()),
            "dropped": int((last & (nxt == -3)).sum()),
            "strandedGangMembers": int((last & (nxt == -5)).sum()),
            "evictedTwice": int((~last).sum()),
            "arrivingQueuedAtTheEnd": int(((bind[s] == -2)).sum()
                                          - (last & (nxt == -2)).sum()),
        })
        assert not (gang[task] & again).any()
    return out


def targets(rows: list) -> dict:
    plans = rows[1:]
    ev = np.asarray([r["evictions"] for r in plans])
    back = np.asarray([r["reboundSameBoundary"] + r["reboundLater"] for r in plans])
    reject = [r for r in plans
              if r["dropped"] or r["queuedAtTheEnd"] > 0.01 * max(r["evictions"], 1)]
    median = sorted(plans, key=lambda r: r["evictions"])[len(plans) // 2]
    return {"plans": len(plans), "medianPlan": median,
            "evictionsMean": float(np.mean([r["evictions"] for r in rows])),
            "reboundShareOfTheEvicted": float(back.sum() / max(ev.sum(), 1)),
            "plansAPlannerWouldReject": len(reject),
            "rejected": [(r["plan"], r["step"], r["outFor"]) for r in reject]}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", type=int, default=1)
    ap.add_argument("--first-seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--no-controls", action="store_true")
    args = ap.parse_args()
    _, cell, config, traffic = run.load_cell(CELL)
    import jax

    if not args.rehearse and jax.devices()[0].platform != "tpu":
        run.say("no TPU: readings off the chip need --rehearse")
        return 1
    from kubernetes_simulator_tpu.utils import compile_cache

    compile_cache.enable()
    for seed in range(args.first_seed, args.first_seed + 7919 * args.seeds, 7919):
        trace, as_run, engine = run.prepare(config, traffic, seed, args.rehearse, {})
        warm = engine.answers(engine.batch())
        t = time.perf_counter()
        second = engine.batch()
        batch_s = time.perf_counter() - t
        rows = plan_rows(engine, warm)
        line = {"workload": cell["name"], "seed": seed, "batch_s": batch_s,
                "platform": jax.devices()[0].platform,
                "peak_bytes": int((jax.devices()[0].memory_stats() or {}).get(
                    "peak_bytes_in_use", 0)),
                "scenario0": rows[0], "targets": targets(rows),
                "retry": warm["retry"], "plans": rows}
        del second
        for who, control in CONTROLS[: 1 if args.no_controls else None]:
            checks = run.decide(trace, as_run, traffic, engine, warm, [warm],
                                [0], seed, control)
            line[who] = {n: v for n, v, *_ in checks}
            line[who + "_correct"] = all(ok for *_, ok in checks)
        # the whole line goes where ``chiprun`` brings files back from: the
        # end of a call's output holds 24,000 bytes, the plans take more
        out = BENCH.parent / "chiprun_out"
        out.mkdir(exist_ok=True)
        (out / f"readings_drain_{seed}.json").write_text(json.dumps(line))
        line.pop("plans")
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
