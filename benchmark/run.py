#!/usr/bin/env python3
"""The benchmark's one harness.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, one import of JAX. The cell is found by name in
``BENCHMARK.json``; its configuration is ``configs/<config>.json`` and its
traffic mix ``traffic/<traffic>.json``. The configuration names its
generator (``generators/<name>.py``) and its plain reference
(``references/<name>.py``), the traffic mix its engine
(``engines/<name>.py``), and each per-layer metric is read by
``layer_metrics/<metric>.py``: adding any of them is adding files
(README.md). The trace is made from ``--seed``.

Set-up (counted in ``setup_s``): imports, generate + encode, build ONE
resident engine, one warm-up batch (which compiles or loads every program
the window uses). Window: whole batches, each one call of the engine that
ends when its results are on the host, until ``--seconds`` have passed,
never cutting the batch in flight, at least two. ``placements_per_s`` is
every placement of the window over all its seconds. With ``--trace 1`` the
window is a few batches under the JAX profiler instead, and the metrics
are the per-layer ones.

After the window, outside both clocks, ``correct`` is decided (PERF.md §2)
on what the timed batches answered, at the timed size: every timed batch
equals the warm-up batch, every task is accounted for, nothing compiled
inside the window, and for a sample of tasks drawn from the seed the node
the program chose is the one the plain reference picks on the state the
program's own earlier answers give.

stdout: one ``{"kind": "batches", ...}`` line with every batch's seconds
and every number compared, then LAST the result object the driver reads.
Off the TPU the harness refuses (exit 1, no result) unless ``--rehearse``
is given, which runs the sizes under ``rehearse`` in the traffic file on
the CPU and names the CPU as its device: for tests, never for a number.
``--control bf16`` puts the reference in bfloat16 in the program's place
(references/); such a run must come out ``correct: false``.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse
import contextlib
import importlib.util
import json
import os
import re
import shutil
import sys
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
TRACE_DIR = ROOT / ".bench_trace"


def say(msg: str) -> None:
    print(f"benchmark: {msg}", file=sys.stderr, flush=True)


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str):
    """(benchmark, cell, config, traffic) for a ``workloads`` entry."""
    bench = load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"benchmark: no workload {name!r} in BENCHMARK.json "
                         f"(have {sorted(cells)})")
    cell = cells[name]
    files = {c["name"]: c["file"] for c in bench["configs"]}
    config = load_json(ROOT / files[cell["config"]])
    traffic = load_json(BENCH / "traffic" / f"{cell['traffic']}.json")
    return bench, cell, config, traffic


class CompileMeter:
    """Sums JAX's own compile-time events (``jax.monitoring``); a copy of
    ``chip_smoke.py``'s, so the yardstick does not move with the program."""

    _KEYS = {
        "/jax/core/compile/backend_compile_duration": "compile_s",
        "/jax/core/compile/jaxpr_trace_duration": "trace_s",
        "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    }
    _COUNTS = {
        "/jax/compilation_cache/cache_hits": "cache_hits",
        "/jax/compilation_cache/cache_misses": "cache_misses",
    }

    def __init__(self):
        import jax

        self.totals = {v: 0.0 for v in self._KEYS.values()}
        self.totals.update({v: 0 for v in self._COUNTS.values()})
        self.totals["compiles"] = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_dur)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_dur(self, name, dur, **kw):
        key = self._KEYS.get(name)
        if key:
            self.totals[key] += dur
            if key == "compile_s":
                self.totals["compiles"] += 1

    def _on_event(self, name, **kw):
        key = self._COUNTS.get(name)
        if key:
            self.totals[key] += 1


# -- the pieces a cell names ---------------------------------------------------


def load_part(kind: str, name: str):
    """The module ``<kind>/<name>.py`` under benchmark/: a configuration's
    generator or reference, a traffic mix's engine, a per-layer metric."""
    path = BENCH / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"{kind}_{re.sub(r'[^0-9A-Za-z_]', '_', name)}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def sizes(config: dict, traffic: dict, rehearse: bool) -> dict:
    """Nodes, tasks and chunk: the configuration's, the traffic's
    override, ``--rehearse``'s cuts."""
    if rehearse:
        return dict(traffic["rehearse"])
    return {"nodes": config["cluster"]["nodes"],
            "tasks": traffic.get("tasks") or config["workload"]["tasks"],
            "chunkWaves": config["engine"]["chunkWaves"]}


def prepare(config: dict, traffic: dict, seed: int, rehearse: bool, spans: dict):
    """(trace, config as run, resident engine): the trace from the seed by
    the configuration's generator, handed to the program, and ONE engine
    built on it. Seconds go into ``spans``."""
    size = sizes(config, traffic, rehearse)
    config = {**config, "engine": {**config["engine"],
                                   "chunkWaves": size["chunkWaves"]}}
    generator = load_part("generators", config["generator"])
    t = time.perf_counter()
    trace = generator.generate(config, size["nodes"], size["tasks"], seed)
    ec, ep = generator.to_program(trace, config)
    spans["encode_s"] = time.perf_counter() - t
    t = time.perf_counter()
    engine = load_part("engines", traffic["engine"]).Engine(
        ec, ep, config, traffic, size["chunkWaves"])
    spans["engine_build_s"] = time.perf_counter() - t
    return trace, config, engine


def same_answers(a: dict, b: dict) -> bool:
    return (a["placed"] == b["placed"]
            and a["unschedulable"] == b["unschedulable"]
            and np.array_equal(a["assignments"], b["assignments"]))


def decide(trace, config, traffic, engine, warm, answers, compiles, seed,
           control=None) -> list:
    """[(what, value, limit, ok)]: every number compared, beside its limit
    (None = printed for the record). The timed batches against the warm-up
    batch, the books of every scenario, compiles inside the window, and the
    plain reference over a sample of the answers (PERF.md §2)."""
    rows = [
        ("full.unaccounted_tasks_max",
         max(abs(p + u - engine.offered)
             for p, u in zip(warm["placed"], warm["unschedulable"])), 0),
        ("full.batches_differing_from_warmup",
         sum(not same_answers(a, warm) for a in answers), 0),
        ("window.compiles", sum(compiles), 0),
    ]
    reference = load_part("references", config["reference"])
    rows += reference.check(trace, config, warm, seed,
                            traffic["check_samples"], control)
    return [(n, v, lim, lim is None or v <= lim) for n, v, lim in rows]


# -- per-layer metrics -------------------------------------------------------


def read_layer_metrics(bench: dict, cell: dict, ctx: dict) -> dict:
    """Every ``per_layer`` metric of BENCHMARK.json that lists this cell (or
    lists none), read by its own file; what reads nothing is left out."""
    out = {}
    for m in bench["per_layer"]:
        if cell["name"] not in m.get("workloads", [cell["name"]]):
            continue
        value = load_part("layer_metrics", m["name"]).read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


# -- main --------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--control", default=None)
    args = ap.parse_args(argv)

    bench, cell, config, traffic = load_cell(args.workload)
    if not (ROOT / "kubernetes_simulator_tpu").is_dir():
        say("the system under test (kubernetes_simulator_tpu/) is not in "
            "this checkout — nothing to measure")
        return 1
    sys.path.insert(0, str(ROOT))
    sys.path.insert(0, str(BENCH))
    chips = int(cell["chips"])
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    devs = jax.devices()
    if not args.rehearse and (devs[0].platform != "tpu" or len(devs) < chips):
        say(f"need {chips} TPU chip(s), found {len(devs)} x "
            f"{devs[0].platform!r} (JAX_PLATFORMS="
            f"{os.environ.get('JAX_PLATFORMS')!r}) — refusing; --rehearse "
            "runs a shrunken cell on the CPU for tests")
        return 1
    from kubernetes_simulator_tpu import native
    from kubernetes_simulator_tpu.utils import compile_cache

    cache_dir = compile_cache.enable()
    if cache_dir is None and not args.rehearse:
        say("the persistent compile cache is off (KSIM_COMPILE_CACHE=0?)")
        return 1
    meter = CompileMeter()
    spans = {"import_s": time.perf_counter() - T0}
    trace, config, engine = prepare(config, traffic, args.seed, args.rehearse, spans)
    if not native.available() and not args.rehearse:
        say("native packers did not build (g++ output is in the log above)")
        return 1
    t = time.perf_counter()
    warm = engine.answers(engine.batch())
    spans["warmup_s"] = time.perf_counter() - t
    setup_compile = dict(meter.totals)
    setup_s = time.perf_counter() - T0
    say(f"set-up {setup_s:.2f}s {json.dumps({k: round(v, 3) for k, v in spans.items()})} "
        f"compile {json.dumps({k: round(v, 3) for k, v in setup_compile.items()})}")

    # The window: whole batches, never cut, at least two. Nothing but the
    # batch call and two clock reads happens inside it; the answers are
    # looked at after it.
    # Beside each batch's seconds goes the process's own cpu time: a slow
    # batch far over its cpu time was kept off the cores by the host.
    results, seconds, compiles, cpu_s = [], [], [], []
    n_trace = int(traffic.get("trace_batches", 2))
    tracing = contextlib.nullcontext()
    if args.trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        os.environ["KSIM_PROFILE_DIR"] = str(TRACE_DIR)  # arms chunk:<i> spans
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # no per-call Python events: less host drag
        tracing = jax.profiler.trace(str(TRACE_DIR), profiler_options=opts)
    with tracing:
        w0 = time.perf_counter()
        while True:
            span = (jax.profiler.TraceAnnotation(f"bench:batch:{len(results)}")
                    if args.trace else contextlib.nullcontext())
            c0, p0 = meter.totals["compiles"], time.process_time()
            t = time.perf_counter()
            with span:
                results.append(engine.batch())
            now = time.perf_counter()
            seconds.append(now - t)
            compiles.append(meter.totals["compiles"] - c0)
            cpu_s.append(time.process_time() - p0)
            if args.trace:
                if len(results) >= n_trace or seconds[-1] > 10.0:
                    break
            elif len(results) >= 2 and now - w0 >= args.seconds:
                break
        window_s = now - w0
    os.environ.pop("KSIM_PROFILE_DIR", None)
    peak = 0
    for d in devs[:max(chips, 1)]:
        peak = max(peak, int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)))

    # correct: outside both clocks.
    t = time.perf_counter()
    answers = [engine.answers(r) for r in results]
    checks = decide(trace, config, traffic, engine, warm, answers, compiles,
                    args.seed, args.control)
    spans["check_s"] = time.perf_counter() - t
    for name, value, limit, ok in checks:
        say(f"check {name}: {value} (limit {limit}) {'ok' if ok else 'FAIL'}")
    failed = sum(not same_answers(a, warm) for a in answers)
    placed = sum(sum(a["placed"]) for a in answers)

    device = {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs), "memory_peak_bytes": peak,
    }
    result = {"correct": all(ok for *_, ok in checks),
              "attempted": len(results), "failed": failed}
    if args.trace:
        import trace_reduce

        reduced = trace_reduce.reduce_dir(TRACE_DIR, n_devices=max(chips, 1))
        n = trace["nodes"]
        ctx = {
            "spans": spans, "compile": setup_compile, "trace": reduced,
            "device_kind": devs[0].device_kind,
            "shape": {
                "scenarios_per_chip": engine.scenarios_per_chip,
                "nodes": len(n["cpu"]), "resources": len(config["resources"]),
                "wave_width": config["engine"]["waveWidth"],
                "chunk_waves": engine.chunk_waves,
                "planes": config["scheduler"]["planes"],
            },
        }
        metrics = read_layer_metrics(bench, cell, ctx)
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        result["breakdown"] = reduced.breakdown()
    else:
        # All the work over all the time of the window (PERF.md §2).
        values = {"placements_per_s": placed / window_s, "setup_s": setup_s}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in bench["end_to_end"]
                   if cell["name"] in m.get("workloads", [cell["name"]])}
    print(json.dumps({
        "kind": "batches", "workload": cell["name"], "seed": args.seed,
        "seconds": seconds, "cpu_s": cpu_s, "placed_per_batch": [sum(a["placed"]) for a in answers],
        "window_s": window_s, "setup_s": setup_s, "spans": spans,
        "setup_compile": setup_compile, "compiles_per_batch": compiles,
        "checks": [[n, v, lim, ok] for n, v, lim, ok in checks],
        "compile_cache_dir": cache_dir, "rehearse": args.rehearse,
    }))
    result.update({"metrics": metrics, "device": device})
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
