#!/usr/bin/env python3
"""One traced run of a cell with an eviction program, as the benchmark makes
it (``benchmark/run.py --trace 1``, unchanged, called in this process), and
beside its two lines what the benchmark does not print (PR 50):

* the eviction program's device time split by stage path
  (``ksim.evict/Search``, ``ksim.evict/Budget``, the rest under
  ``ksim.evict``). Since PR 52 the ledger holds this split a boundary, in
  both eviction cells: ``evict_search_`` / ``evict_sort_`` /
  ``evict_rewind_`` / ``evict_join_`` / ``evict_write_ms_per_boundary``,
  ``budget_admit_ms_per_boundary`` and ``evict_unscoped_share``
  (``benchmark/layer_metrics/_program_stages.py``; its stderr lines name
  every path). The script keeps its own join: it also runs trees (``--root``)
  whose benchmark has no such reader;
* the program's fifteen largest ops with their stage;
* the sha256 of every answer the last batch handed back, ``summary()["retry"]``
  and the sizes the program was compiled for: two trees on one seed have to
  agree in all of them.

``--root`` names the tree to run (another checkout unpacked inside this one):
the script itself needs nothing of the tree it lies in. One JSON line, kept
in ``chiprun_out/evict_trace_<tag>.json`` too. On the chip:

    python3 scripts/chip_evict_trace.py --workload borg10k-budget128 \\
        --seed 2147500003 [--root _smoke_tree/parent] [--tag parent]
"""

import argparse
import bisect
import hashlib
import json
import os
import re
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
PROGRAM = re.compile(r"^jit_whatif_evict\(")
ANSWERS = ("assignments", "bind_boundary", "eviction_log", "node_out_at",
           "evictions", "placed")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--root", default=str(HERE))
    ap.add_argument("--tag", default="change")
    ap.add_argument("--rehearse", action="store_true",
                    help="the shrunken cell on the CPU, untraced: the answers only")
    args = ap.parse_args()
    root = Path(args.root).resolve()
    os.chdir(root)
    sys.path[:0] = [str(root), str(root / "benchmark")]
    import numpy as np
    import run as bench
    import trace_reduce

    from kubernetes_simulator_tpu.utils import profiling

    kept = {}
    reduce_dir, tables, prepare = (
        trace_reduce.reduce_dir, profiling.stage_tables, bench.prepare)

    def reduce_once(*a, **kw):
        kept["trace"] = reduce_dir(*a, **kw)
        return kept["trace"]

    def tables_once():
        if "tables" not in kept:
            kept["tables"] = tables()
        return kept["tables"]

    def prepare_and_keep(*a, **kw):
        out = prepare(*a, **kw)
        adapter = kept["adapter"] = out[2]
        batch = adapter.batch

        def batch_and_keep():
            kept["result"] = batch()
            return kept["result"]

        adapter.batch = batch_and_keep
        return out

    trace_reduce.reduce_dir = reduce_once
    profiling.stage_tables = tables_once
    bench.prepare = prepare_and_keep
    rc = bench.main(["--workload", args.workload, "--seed", str(args.seed),
                     "--seconds", "10"] + (
        ["--rehearse", "--trace", "0"] if args.rehearse else ["--trace", "1"]))
    if rc:
        return rc
    trace, table = kept.get("trace"), {}
    runs = []
    if trace is not None:
        table = tables_once().get("jit_whatif_evict", {})
        w0, w1 = trace.window
        runs = sorted((s, s + d) for n, s, d in trace.devices[0]["modules"]
                      if PROGRAM.match(n) and s >= w0 and s + d <= w1)
    starts = [r[0] for r in runs]
    by_stage, by_op = {}, {}
    for name, s, d in (trace.devices[0]["ops"] if runs else ()):
        i = bisect.bisect_right(starts, s) - 1
        if i < 0 or s >= runs[i][1] or re.match(r"^%?while[.\d]* ", name):
            continue
        stage = table.get(re.match(r"^%?([\w.\-]+)", name).group(1), "")
        by_stage[stage] = by_stage.get(stage, 0) + d
        key = (trace_reduce.op_label(name), stage)
        by_op[key] = by_op.get(key, 0) + d
    n = max(len(runs), 1)
    result, eng = kept["result"], kept["adapter"].engine
    sha = {}
    for k in ANSWERS:
        v = getattr(result, k, None)
        if v is not None:
            sha[k] = hashlib.sha256(
                np.ascontiguousarray(np.asarray(v)).tobytes()).hexdigest()
    line = {
        "tag": args.tag, "workload": args.workload, "seed": args.seed,
        "evict_runs_in_window": len(runs),
        "evict_ms_per_run": sum(e - s for s, e in runs) / 1e6 / n,
        "evict_ms_per_run_by_stage": {
            k or "(none)": v / 1e6 / n for k, v in sorted(by_stage.items())},
        "evict_top_ops_ms_per_run": [
            [op, stage, v / 1e6 / n] for (op, stage), v in
            sorted(by_op.items(), key=lambda kv: -kv[1])[:15]],
        "sha256": sha,
        "retry": result.fleet_telemetry.summary()["retry"],
        "evict_sizes": eng._evict_sizes, "evict_scale": eng._evict_scale,
    }
    text = json.dumps(line, default=float)
    print(text, flush=True)
    out = HERE / "chiprun_out"
    out.mkdir(exist_ok=True)
    (out / f"evict_trace_{args.workload}_{args.tag}.json").write_text(text + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
