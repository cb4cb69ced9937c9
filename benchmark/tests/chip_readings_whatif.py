#!/usr/bin/env python3
"""The readings the what-if cell's limit of ``correct`` is set from (PERF.md
§2), in one process: for each of ``--seeds`` seeds the cell's trace is made,
ONE engine built at the cell's own size and one whole batch run, and the
comparison that decides ``correct`` reads its numbers three times over that
batch's answers: as they are (a sound run), with the reference in bfloat16
in the program's place, and with the base cluster's reference in every
scenario's place. One JSON line a seed. On the chip:

    python3 benchmark/tests/chip_readings_whatif.py --seeds 3

Not run by the benchmark's own runs. Off the TPU it needs ``--rehearse``.

Readings (my chip runs, PR 27, one TPU v5e; the share of choices that cannot
be the reference's pick, pooled over 128 scenarios, about 4,090 pairs a run):
sound 0.0 on every one of 20 seeds (18 runs of ``run.py``, two seeds of this
script), the worst single scenario 0.0; ``bf16`` 0.6575 (``run.py``), 0.6748
and 0.6609 (this script), the worst scenario 0.82-0.92; ``unperturbed``
0.2170 (``run.py``), 0.2073 and 0.2090 (this script), the worst scenario
1.0. The limit, 0.01, is 1/20 of the smallest control reading and above the
largest sound one.
"""

import argparse
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH.parent), str(BENCH)]

import run  # noqa: E402

CELL = "borg10k-whatif128"
CONTROLS = (("sound", None), ("bf16", "bf16"), ("unperturbed", "unperturbed"))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", type=int, default=3)
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
        warm = engine.answers(engine.batch())
        line = {"workload": cell["name"], "seed": seed,
                "platform": jax.devices()[0].platform}
        for who, control in CONTROLS:
            rows = run.decide(trace, as_run, traffic, engine, warm, [warm], [0],
                              seed, control)
            line[who] = {n: v for n, v, *_ in rows}
            line[who + "_correct"] = all(ok for *_, ok in rows)
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
