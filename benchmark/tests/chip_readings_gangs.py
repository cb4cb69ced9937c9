#!/usr/bin/env python3
"""The readings the gang-scheduled training cell's limit of ``correct`` is set
from (PERF.md §2), in one process: for each of ``--seeds`` seeds the cell's
trace is made, ONE engine built at the cell's own size and one whole batch
run, and the comparison that decides ``correct`` reads its numbers five times
over that batch's answers: as they are (a sound run), with the reference in
bfloat16 in the program's place, with the base cluster's reference in every
scenario's place, with the rollback left out of the reference (a member
stands or falls alone), and with a wide group judged wave by wave. One JSON
line a seed. On the chip:

    python3 benchmark/tests/chip_readings_gangs.py --seeds 2

Not run by the benchmark's own runs. Off the TPU it needs ``--rehearse``.

Readings (my chip runs, PR 37, one TPU v5e; the share of choices that cannot be
the reference's pick, pooled over 256 scenarios, about 5,120 pairs a run: in
every scenario its last pod, two of all pods, one from each stratum: wide
groups in their first, a middle and their last wave, a member of a rolled-back
wide group, a GPU pod, the pod right after a rolled-back wide group; and four
members of rolled-back wide groups from waves that held no failure, about
1,030 pairs), seeds 2147500203 and 2147508122 from the committed files: sound
0.0 on both (and on every run of ``run.py``), the worst single scenario 0.0, no
choice short by a point, the rows over all 8.39M placements 0,
``pod_groups_partly_bound`` among them; 46,201 wide groups rolled back a batch
and 44,700 binds undone (the program's own counters; the reference counts the
same 46,201), 1,096 and 1,117 rebuilt picks on a score edge (for the record:
nothing is left out); ``bf16`` 0.2350 and 0.2420, the worst scenario 0.48 and
0.55; ``unperturbed`` 0.1922 and 0.1912, the worst scenario 1.0; ``no-gang``
0.2096 and 0.2078 (0.33 and 0.31); ``wave-local-gang`` 0.2031 and 0.2010 (0.33
and 0.29). The rows over all placements stay 0 under every control: they read
the program's answers. The limit, 0.004, is 1/48 of the smallest control
reading and 20 samples above the sound one. Without the stratum of whole
waves ``wave-local-gang`` reads 0.0037 and ``no-gang`` 0.0115 (the reference on
its own schedule at the cell's size, CPU). At the rehearsal size
(``--rehearse``, my CPU run): 0.0 against 0.154, 0.439, 0.055 and 0.011.
"""

import argparse
import hashlib
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH.parent), str(BENCH)]

import run  # noqa: E402

CELL = "pai1800-whatif256"
CONTROLS = (("sound", None), ("bf16", "bf16"), ("unperturbed", "unperturbed"),
            ("no-gang", "no-gang"), ("wave-local-gang", "wave-local-gang"))


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
                "assignments_sha256": hashlib.sha256(
                    warm["assignments"].tobytes()).hexdigest()}
        for who, control in CONTROLS:
            rows = run.decide(trace, as_run, traffic, engine, warm, [warm], [0],
                              seed, control)
            line[who] = {n: v for n, v, *_ in rows}
            line[who + "_correct"] = all(ok for *_, ok in rows)
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
