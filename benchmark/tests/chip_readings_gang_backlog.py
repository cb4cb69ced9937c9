#!/usr/bin/env python3
"""The readings the job-queue cell's limit of ``correct`` is set from (PERF.md
§2), in one process: for each of ``--seeds`` seeds the cell's trace is made, ONE
engine built at the cell's own size and one whole batch run, and the
comparison that decides ``correct`` reads its numbers five times over that
batch's answers: as they are (a sound run), with the reference in bfloat16 in
the program's place, with a reference that never re-tries a group, with one
that re-tries members singly, and with one that judges a wide job wave by wave
in the pass. One JSON line a seed (the batch's counters and the sha256 of both
answers beside the rows). On the chip:

    python3 benchmark/tests/chip_readings_gang_backlog.py --seeds 1

Not run by the benchmark's own runs. Off the TPU it needs ``--rehearse``.
The readings are in PERF.md §2 and in the configuration's ``assumed``.
"""

import argparse
import hashlib
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH.parent), str(BENCH)]

import run  # noqa: E402

CELL = "pai1800-gangqueue256"
CONTROLS = (("sound", None), ("bf16", "bf16"), ("never-retried", "never-retried"),
            ("members-singly", "members-singly"),
            ("wave-local-pass", "wave-local-pass"))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", type=int, default=2)
    ap.add_argument("--first-seed", type=int, default=2147483700)
    ap.add_argument("--rehearse", action="store_true")
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
        result = engine.batch()
        warm = engine.answers(result)
        line = {"workload": cell["name"], "seed": seed,
                "platform": jax.devices()[0].platform,
                "placed": int(sum(warm["placed"])),
                "gangs": result.fleet_telemetry.summary().get("gangs"),
                "retry": {k: v for k, v in warm["retry"].items() if k != "groups"},
                "groups": {k: {"sum": int(v.sum()), "max": int(v.max()),
                               "scenario0": int(v[0])}
                           for k, v in warm["groups"].items()},
                "sha256": {k: hashlib.sha256(warm[k].tobytes()).hexdigest()
                           for k in ("assignments", "bind_boundary")}}
        for who, control in CONTROLS:
            rows = run.decide(trace, as_run, traffic, engine, warm, [warm], [0],
                              seed, control)
            line[who] = {n: v for n, v, *_ in rows}
            line[who + "_correct"] = all(ok for *_, ok in rows)
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
