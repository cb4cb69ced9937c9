#!/usr/bin/env python3
"""Hand reading of the what-if path no benchmark cell measures yet
(PERF.md §7, ``borg10k-whatif128``): 128 perturbed copies of the
benchmark's Borg cluster (``uniform_scenarios`` 0.02 / 0.3 / 0.1) replay
131,072 tasks through the vmapped v3 chunk program, completions on.

    chiprun -- python3 scripts/whatif128_reading.py --seed 7 --batches 3

One warm-up ``run()`` (compiles), then ``--batches`` timed ones; prints one
JSON line: seconds per batch, placements per second of the best and the
median batch, and scenario 0's placed count. Not a benchmark: no reference
judges the answers (the timed path returns counts), and ``run()`` still
compiles four small reductions in every batch (ROADMAP S2). Refuses off
the TPU unless ``--rehearse`` (tiny sizes, for tests of the script).
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import statistics
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--batches", type=int, default=3)
    ap.add_argument("--scenarios", type=int, default=128)
    ap.add_argument("--tasks", type=int, default=131072)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse:
        print("whatif128_reading: no TPU — refusing (--rehearse runs a tiny "
              "size on the CPU, for no number)", file=sys.stderr)
        return 1
    from kubernetes_simulator_tpu.framework.framework import FrameworkConfig
    from kubernetes_simulator_tpu.sim.whatif import WhatIfEngine, uniform_scenarios
    from kubernetes_simulator_tpu.utils import compile_cache

    compile_cache.enable()
    config = json.loads(
        (ROOT / "benchmark/configs/borg2019-10k-gangs.json").read_text())
    spec = importlib.util.spec_from_file_location(
        "borg_generator", ROOT / "benchmark/generators/borg.py")
    generator = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(generator)
    nodes, tasks, chunk, scenarios = (
        (64, 2048, 16, 4) if args.rehearse
        else (config["cluster"]["nodes"], args.tasks,
              config["engine"]["chunkWaves"], args.scenarios))
    t0 = time.perf_counter()
    ec, ep = generator.to_program(
        generator.generate(config, nodes, tasks, args.seed), config)
    engine = WhatIfEngine(
        ec, ep, uniform_scenarios(ec, scenarios, args.seed, 0.02, 0.3, 0.1),
        FrameworkConfig(), wave_width=config["engine"]["waveWidth"],
        chunk_waves=chunk, completions=True,
    )
    warm = engine.run()
    setup_s = time.perf_counter() - t0
    seconds, placed = [], []
    for _ in range(args.batches):
        t = time.perf_counter()
        res = engine.run()
        seconds.append(time.perf_counter() - t)
        placed.append(int(res.total_placed))
    assert all(p == int(warm.total_placed) for p in placed), placed
    print(json.dumps({
        "kind": "whatif128-reading", "device": dev.device_kind,
        "scenarios": scenarios, "nodes": nodes, "tasks": tasks,
        "engine": res.engine, "completions_on": bool(res.completions_on),
        "setup_s": round(setup_s, 2),
        "batch_s": [round(s, 4) for s in seconds],
        # a rate is a device number: none from a CPU rehearsal
        "placements_per_s_best": (
            None if args.rehearse else round(placed[0] / min(seconds), 1)),
        "placements_per_s_median": (
            None if args.rehearse
            else round(placed[0] / statistics.median(seconds), 1)),
        "total_placed": placed[0], "placed_scenario0": int(res.placed[0]),
        "placed_sum_check": int(res.placed.astype("int64").sum()),
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
