#!/usr/bin/env python3
"""The readings the multi-tenant mesh cell's limit of ``correct`` is set from
(PERF.md §2), in one process: for each of ``--seeds`` seeds the cell's trace
is made, ONE engine built at the cell's own size over the cell's chips and one
whole batch run, and the comparison that decides ``correct`` reads its numbers
five times over that batch's answers: as they are (a sound run), with the
reference in bfloat16 in the program's place, with the base cluster's
reference in every scenario's place, with the ``google.com/tpu`` row left out
of the reference's fit, and with the gang rollback left out of it. One JSON
line a seed. On the chips:

    python3 benchmark/tests/chip_readings_multitenant.py --seeds 2

Not run by the benchmark's own runs. Off the TPU it needs ``--rehearse`` (and
``XLA_FLAGS=--xla_force_host_platform_device_count=4``).

Readings (my chip runs, PR 33, one v5e host with four chips; the share of
choices that cannot be the reference's pick, pooled over 1,024 scenarios, about
5,100 pairs a run: in every scenario its last pod, one accelerator pod, one
gang member and two of all pods). With the accelerator pods at their arrival
slots (the generator as it stands), three seeds from 2147483700: sound 0.0 on
every seed, the worst single scenario 0.0, no choice short by a point, the
rows over all 10.24M placements 0, 0.0027-0.0043 of the sample left out
behind a rebuilt gang on a score edge; ``bf16`` 0.2530, 0.2706 and 0.3766,
the worst scenario 0.8-1.0; ``unperturbed`` 0.1970, 0.1946 and 0.2003, the
worst scenario 1.0; ``no-extended`` 0.3709, 0.3680 and 0.3642 (it also leaves
out 0.012-0.015 of the sample: it rebuilds more gangs); ``no-gang`` 0.03152,
0.02721 and 0.02820 (``run.py --control no-gang`` on a fourth seed 0.02649),
the worst scenario 0.4. While the accelerator pods were dealt too (five
readings over three seeds, two calls): sound 0.0; 0.188-0.235, 0.172-0.197,
0.374-0.568 and 0.0276-0.0330. The rows over all placements stay 0 under every
control: they read the program's answers. The limit, 0.004, is 1/6.6 of the
smallest control reading and 20 samples above the sound one. The sha256 of
every pod's node in all 1,024 scenarios is printed beside the readings;
``--unmeshed`` on one chip has to print the same.
"""

import argparse
import hashlib
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH.parent), str(BENCH)]

import run  # noqa: E402

CELL = "multitenant-mesh4"
CONTROLS = (("sound", None), ("bf16", "bf16"), ("unperturbed", "unperturbed"),
            ("no-extended", "no-extended"), ("no-gang", "no-gang"))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", type=int, default=2)
    ap.add_argument("--first-seed", type=int, default=2147483700)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--unmeshed", action="store_true",
                    help="the same scenarios on ONE device, no mesh, the "
                         "sound reading only: its sha256 has to be the "
                         "meshed run's")
    args = ap.parse_args()
    _, cell, config, traffic = run.load_cell(CELL)
    controls = CONTROLS
    if args.unmeshed:
        traffic, controls = {**traffic, "engine": "whatif_arrivals"}, CONTROLS[:1]
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
                "platform": jax.devices()[0].platform,
                "devices": 1 if args.unmeshed else traffic["chips"],
                "placed": int(sum(warm["placed"])),
                "assignments_sha256": hashlib.sha256(
                    warm["assignments"].tobytes()).hexdigest()}
        for who, control in controls:
            rows = run.decide(trace, as_run, traffic, engine, warm, [warm], [0],
                              seed, control)
            line[who] = {n: v for n, v, *_ in rows}
            line[who + "_correct"] = all(ok for *_, ok in rows)
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
