#!/usr/bin/env python3
"""The readings the budgeted drain's configuration records and its controls'
verdicts (PERF.md §2, §6), in one process: for each of ``--seeds`` seeds the
cell's trace is made, ONE engine built at the cell's own size and one whole
batch run; the comparison that decides ``correct`` reads its numbers six
times over that batch's answers (a sound run, and the five controls:
``bf16``, ``no-budget``, ``budget-never-restored``, ``failures-free``,
``static-out``); and the plans' own numbers are printed from the four
answers: per plan its evictions by kind, the voluntary ones made a boundary or
more after their node's cordon, the candidate turns refused, the nodes that
went out and the boundary the last did, and the three targets of the
configuration (``scenarios.measured`` in its file is this script's first
seed; ``--scenarios '{"steps": [...]}'`` lays other value sets over the file's
for a sweep). One JSON line a seed. On the chip:

    python3 benchmark/tests/chip_readings_budget_drain.py --seeds 1

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

CELL = "borg10k-budget128"
CONTROLS = (("sound", None), ("bf16", "bf16"), ("no_budget", "no-budget"),
            ("budget_never_restored", "budget-never-restored"),
            ("failures_free", "failures-free"), ("static_out", "static-out"))


def plan_rows(engine, answers, reference, boundaries: int) -> list:
    """Per plan, from the answers and the plan alone."""
    log, out_at = answers["eviction_log"], answers["node_out_at"]
    bind = answers["bind_boundary"]
    N = out_at.shape[1]
    rows = []
    for s, plan in enumerate(engine.plans):
        mine = log[s][log[s][:, 1] >= 0]
        kinds = mine[:, 4]
        nodes = reference.Nodes(plan, boundaries, N, out_at[s].astype(np.int64))
        vol = mine[kinds == reference.VOLUNTARY]
        late = int((vol[:, 0] > nodes.cordon[vol[:, 2]]).sum())
        cordoned = int((nodes.cordon >= 0).sum())
        went = out_at[s] >= 0
        rows.append({
            "plan": s, "step": plan["step"], "order": plan["order"],
            "grace": plan["grace"], "outFor": plan["outFor"],
            "first": plan["first"], "share": plan["share"],
            "failures": len(plan["failures"]),
            "evictions": len(mine), "voluntary": len(vol),
            "voluntaryAfterTheCordon": late,
            "forcedAtADeadline": int((kinds == reference.DEADLINE).sum()),
            "forcedByAFailure": int((kinds == reference.FAILURE).sum()),
            "nodesCordoned": cordoned, "nodesOut": int(went.sum()),
            "lastNodeOutAt": int(out_at[s].max()),
            "queuedAtTheEnd": int((bind[s] == -2).sum()),
            "dropped": int((bind[s] == -3).sum()),
        })
    return rows


def targets(rows: list, retry: dict, log) -> dict:
    plans = rows[1:]
    share = [r["voluntaryAfterTheCordon"] / max(r["voluntary"], 1) for r in plans]
    median = sorted(plans, key=lambda r: r["evictions"])[len(plans) // 2]
    free = sum(r["forcedAtADeadline"] == 0 for r in plans)
    return {
        "plans": len(plans), "medianPlan": median,
        "boundariesThatEvict": int(len(np.unique(log[:, :, 0][log[:, :, 1] >= 0]))),
        "evictionsMean": float(np.mean([r["evictions"] for r in rows])),
        "medianShareOfVoluntaryEvictionsAfterTheCordon": float(np.median(share)),
        "plansWithNoDeadlineForcedEviction": free,
        "plansWithSomeDeadlineForcedEviction": len(plans) - free,
        "candidateTurnsMean": float(
            np.mean([r["evictions"] for r in rows])
            + retry.get("evict_deferred", {}).get("mean", 0.0)),
    }


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", type=int, default=1)
    ap.add_argument("--first-seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--no-controls", action="store_true")
    ap.add_argument("--scenarios", default=None, help="JSON laid over the "
                    "configuration's scenarios block: a sweep of value sets")
    args = ap.parse_args()
    _, cell, config, traffic = run.load_cell(CELL)
    if args.scenarios:
        config = {**config, "scenarios": {**config["scenarios"],
                                          **json.loads(args.scenarios)}}
    import jax

    if not args.rehearse and jax.devices()[0].platform != "tpu":
        run.say("no TPU: readings off the chip need --rehearse")
        return 1
    from kubernetes_simulator_tpu.utils import compile_cache

    compile_cache.enable()
    for seed in range(args.first_seed, args.first_seed + 7919 * args.seeds, 7919):
        trace, as_run, engine = run.prepare(config, traffic, seed, args.rehearse, {})
        reference = run.load_part("references", as_run["reference"])
        warm = engine.answers(engine.batch())
        t = time.perf_counter()
        second = engine.batch()
        batch_s = time.perf_counter() - t
        boundaries = int(warm["retry"]["passes"])
        rows = plan_rows(engine, warm, reference, boundaries)
        sizes = getattr(engine.engine, "_evict_sizes", None)
        line = {"workload": cell["name"], "seed": seed, "batch_s": batch_s,
                "platform": jax.devices()[0].platform,
                "peak_bytes": int((jax.devices()[0].memory_stats() or {}).get(
                    "peak_bytes_in_use", 0)),
                "evict_sizes": sizes,
                "evict_scale": getattr(engine.engine, "_evict_scale", None),
                "scenarios": args.scenarios,
                "scenario0": rows[0], "targets": targets(rows, warm["retry"], warm["eviction_log"]),
                "retry": warm["retry"], "plans": rows}
        del second
        for who, control in CONTROLS[: 1 if args.no_controls else None]:
            t = time.perf_counter()
            checks = run.decide(trace, as_run, traffic, engine, warm, [warm],
                                [0], seed, control)
            line[who] = {n: v for n, v, *_ in checks}
            line[who + "_correct"] = all(ok for *_, ok in checks)
            line[who + "_failed"] = [n for n, *_, ok in checks if not ok]
            line[who + "_check_s"] = time.perf_counter() - t
        out = BENCH.parent / "chiprun_out"
        out.mkdir(exist_ok=True)
        tag = f"{seed}" + (f"_{abs(hash(args.scenarios)) % 10**6}"
                           if args.scenarios else "")
        (out / f"readings_budget_drain_{tag}.json").write_text(json.dumps(line))
        line.pop("plans")
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
