#!/usr/bin/env python3
"""The readings the default-plugin-set what-if cell's limit of ``correct`` is
set from (PERF.md §2), in one process: for each of ``--seeds`` seeds the
cell's trace is made, ONE engine built at the cell's own size and one whole
batch run, and the comparison that decides ``correct`` reads its numbers five
times over that batch's answers: as they are (a sound run), with the
reference in bfloat16 in the program's place, with the base cluster's
reference in every scenario's place, with InterPodAffinity left out of the
reference, and with the DoNotSchedule filter left out of it. One JSON line a
seed. On the chip:

    python3 benchmark/tests/chip_readings_k8s5k.py --seeds 2

Not run by the benchmark's own runs. Off the TPU it needs ``--rehearse``.

Readings (my chip runs, PR 31, one TPU v5e; the share of choices that cannot
be the reference's pick, pooled over 256 scenarios, about 4,350 pairs a run,
half of a scenario's 16 drawn from the pods under a term), three seeds from
2147483700: sound 0.0 on every seed, the worst single scenario 0.0, no choice
short by a point, the three rows over all 12.8M placements 0 (before the
program's normalize rows divided exactly on the chip,
``ops.tpu.floor_div_f32``: 0.0055-0.0085, every miss a ScheduleAnyway pod 2
points short); ``bf16`` 0.4826, 0.5036 and 0.4798, the worst scenario
0.76-0.82; ``unperturbed`` 0.2802, 0.2848 and 0.2813, the worst scenario 1.0;
``no-interpod`` 0.01126, 0.01425 and 0.01126 (``run.py --control`` on a
fourth seed 0.01540), the worst scenario 0.12-0.18, short by 1 point at most
(0.00666-0.00873 while every sample was drawn uniformly); ``no-spread``
0.1073, 0.1055 and 0.1631 (0.1154), the worst scenario 0.29-0.41. The rows
over all placements stay 0 under every control: they read the program's
answers. The limit, 0.0015, is 1/7.5 of the smallest control reading and 6
samples above the sound one (``assumed`` in the configuration's file says why
not 0.01).
"""

import argparse
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH.parent), str(BENCH)]

import run  # noqa: E402

CELL = "k8s5k-whatif256"
CONTROLS = (("sound", None), ("bf16", "bf16"), ("unperturbed", "unperturbed"),
            ("no-interpod", "no-interpod"), ("no-spread", "no-spread"))


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
