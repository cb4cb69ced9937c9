#!/usr/bin/env python3
"""The readings the limits of ``correct`` are set from (PERF.md §2), in one
process: for each of ``--seeds`` seeds the cell's trace is made, ONE engine
built at the cell's own size and one whole batch run, and the comparison
that decides ``correct`` reads its numbers twice over that batch's answers:
as they are (a sound run), and with the reference in bfloat16 in the
program's place (the control). One JSON line a seed. On the chip:

    python3 benchmark/tests/chip_readings.py --workload <cell> --seeds 12

Not run by the benchmark's own runs. Off the TPU it needs ``--rehearse``.
"""

import argparse
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH.parent), str(BENCH)]

import run  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=2147483700)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    _, cell, config, traffic = run.load_cell(args.workload)
    import jax

    if not args.rehearse and jax.devices()[0].platform != "tpu":
        run.say("no TPU: readings off the chip need --rehearse")
        return 1
    from kubernetes_simulator_tpu.utils import compile_cache

    compile_cache.enable()
    for seed in range(args.first_seed, args.first_seed + 7919 * args.seeds, 7919):
        trace, as_run, engine = run.prepare(config, traffic, seed, args.rehearse, {})
        warm = engine.answers(engine.batch())
        line = {"workload": cell["name"], "seed": seed,
                "platform": jax.devices()[0].platform}
        for who, control in (("sound", None), ("control", "bf16")):
            rows = run.decide(trace, as_run, traffic, engine, warm, [warm], [0],
                              seed, control)
            line[who] = {n: v for n, v, *_ in rows}
            line[who + "_correct"] = all(ok for *_, ok in rows)
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
