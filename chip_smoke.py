#!/usr/bin/env python3
"""On-chip smoke: the quickest proof that the system still starts on the TPU.

    python chip_smoke.py

One process, one import of JAX, no child that needs the chip. Drives the
main paths once through the entry points a user calls —
``kubernetes_simulator_tpu.cli.main`` (the function behind ``python -m
kubernetes_simulator_tpu``) and the public ``api`` — at the sizes
BASELINE.json names, and checks what comes out by the repo's own means
(scenario 0 == the single replay; device placements == the host greedy
reference, element-wise).

Phases, in order; the first failure ends the run with a non-zero exit and
no result line (nothing here catches a phase's exception):

  device          platform must be "tpu" (else exit 1 before any heavy
                  work), native packers built, compile cache on
  whatif-config3  what-if examples/config3_whatif_256.yaml
                  (256 scenarios x 5,000 nodes x 50,000 pods, one chip)
  run-config2     run examples/config2_full_plugins_5k.yaml --strategy jax
  parity          chip vs host reference: config-2 head, a Borg-shaped
                  case with completions, the same as a what-if batch
                  through the device release program, and a release-heavy
                  what-if batch whose every release block adds many
                  releases to one node (prints ``release_rounds``), and
                  the Borg case on 16 stride-zoned nodes, single replay
                  against what-if scenario 0; each case prints its
                  ``select_form``, ``zone_packed`` required of the zoned one,
                  and every what-if batch ``inwave_corrections``
                  ``resolved_terms``; the Borg case again with a pending
                  queue re-tried on the device (``retry_buffer``), nodes and
                  bind boundaries against the host reference, the pass
                  required to read its class rows as a select
                  (``class_row_reads``) and the arrival waves as a slice;
                  and the default plugin set on 160 nodes as an
                  arrivals-only what-if batch (``two_pass``, host-scale
                  count rows), scenario 0 against the single replay; and
                  jobs of 16 workers at waveWidth 8 (pod groups wider than
                  the wave, some rolled back) against the same trace at
                  waveWidth 16, ``summary()["gangs"]`` and ``rollback_form``
                  required, no pod group partly bound; and
                  the normalize rows' divisions against the integer division
  serve           serve examples/config20_service.yaml < 4 defrag queries
  mesh            only with >1 device: what-if
                  examples/config5_multitenant_mesh.yaml over all devices
                  vs the same file on one device; the meshed run's
                  ``summary()["mesh"]`` counters are required, with no
                  collective in its chunk program, and of one more batch
                  of that engine, armed and traced
                  (``utils.profiling.device_trace``): one root span,
                  ``mesh_fetch`` with ``bytes`` = ``fetch_bytes``, every
                  span closed inside the root

Stdout is two JSON lines, written only after every phase passed. The
first is the report (versions, compile-cache directory, per phase: cold
wall, compile time, the engine's own wall, placements, any ``reduced``).
The LAST is the result the driver reads, with exactly these keys and
nothing else:
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}``.
Progress goes to stderr. CPU debugging of this script itself is not a
mode: without a TPU it refuses.
"""

from __future__ import annotations

import contextlib
import io
import json
import os
import sys
import tempfile
import time
from pathlib import Path

EXAMPLES = Path(__file__).resolve().parent / "examples"
CONFIG2 = str(EXAMPLES / "config2_full_plugins_5k.yaml")
CONFIG3 = str(EXAMPLES / "config3_whatif_256.yaml")
CONFIG5 = str(EXAMPLES / "config5_multitenant_mesh.yaml")
CONFIG20 = str(EXAMPLES / "config20_service.yaml")

# Parity shapes (small on purpose: the host reference is numpy, per pod).
PARITY_HEAD_PODS = 2000
# Borg case: few nodes and long tasks, so the cluster runs full (some tasks
# stay unschedulable) and fit decisions sit on what the releases freed.
BORG_NODES, BORG_TASKS, BORG_MEAN_DURATION, BORG_CHUNK_WAVES = 12, 2048, 15000.0, 16
# The same case with a pending queue of this many tasks (a multiple of the wave).
RETRY_BUFFER = 64
# Release-heavy what-if case: short tasks and wide chunks on the same 12 nodes,
# so a boundary releases hundreds of tasks and every 128-row block of the
# release program adds many releases to one node, in rank order.
HEAVY_SEED, HEAVY_MEAN_DURATION, HEAVY_CHUNK_WAVES, HEAVY_MIN_ROUNDS = 0, 2000.0, 64, 8
# Zoned Borg case: two nodes in each of the generator's 8 zones, the stride
# layout of the 10,000-node cluster, which 12 nodes are not: its chunk
# programs take the zone-packed select (ops.tpu3.select_form).
ZONED_NODES = 16
# Default-plugin-set what-if case: over 128 nodes, so that hostname is a
# host-scale topology (ops.tpu3.DMAX_COARSE) as in the 5,000-node cluster.
PLUGINS_NODES, PLUGINS_PODS = 160, 1024


def say(msg: str) -> None:
    print(f"chip_smoke: {msg}", file=sys.stderr, flush=True)


class CompileMeter:
    """Sums JAX's own compile-time events (``jax.monitoring``) so each
    phase can report how much of its cold wall was XLA."""

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
        jax.monitoring.register_event_duration_secs_listener(self._on_dur)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_dur(self, name, dur, **kw):
        key = self._KEYS.get(name)
        if key:
            self.totals[key] += dur

    def _on_event(self, name, **kw):
        key = self._COUNTS.get(name)
        if key:
            self.totals[key] += 1

    def since(self, before: dict) -> dict:
        return {
            k: round(v - before[k], 3) if isinstance(v, float) else v - before[k]
            for k, v in self.totals.items()
        }


def cli(argv) -> list:
    """``python -m kubernetes_simulator_tpu <argv>`` in this process; returns
    the JSONL rows it printed to stdout."""
    from kubernetes_simulator_tpu.cli import main

    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = main(list(argv))
    if rc != 0:
        raise RuntimeError(f"cli {argv} returned {rc}")
    return [json.loads(ln) for ln in buf.getvalue().splitlines() if ln.strip()]


def require(cond, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


def program_spans(trace_dir) -> list:
    """[(name, start_ns, end_ns, thread, stats)] of the host events in the
    profiler trace under ``trace_dir`` that carry a name the program
    exports (``sim.telemetry``), roots among them, in start order: read by
    the benchmark's reader of the same spans."""
    sys.path[:0] = [str(Path(__file__).resolve().parent / "benchmark")]
    import trace_reduce
    from layer_metrics import _program_spans

    kept, _ = _program_spans.span_names()
    events = _program_spans.events_from_xplane(
        trace_reduce.find_xplane(trace_dir), kept)
    return [(name, start, start + duration, thread, stats)
            for name, start, duration, thread, stats in events]


def split_whatif(rows):
    agg = [r for r in rows if r["kind"] == "whatif-aggregate"]
    scen = [r for r in rows if r["kind"] == "whatif-scenario"]
    require(len(agg) == 1, f"expected one whatif-aggregate row, got {len(agg)}")
    return agg[0], scen


# -- phases -----------------------------------------------------------------


def config_size(path: str):
    """(scenarios, nodes, pods) a synthetic-workload config asks for."""
    from kubernetes_simulator_tpu.utils.config import SimConfig

    cfg = SimConfig.load(path)
    return cfg.whatif.scenarios, cfg.cluster.nodes, cfg.workload.pods


def phase_whatif_config3() -> dict:
    S, N, P = config_size(CONFIG3)
    agg, scen = split_whatif(cli(["what-if", CONFIG3]))
    require(agg["engine"] == "v3", f"engine {agg['engine']!r}, want v3")
    require(agg["scenarios"] == S == len(scen),
            f"{agg['scenarios']} scenarios / {len(scen)} rows, want {S}")
    require(all(r["placed"] + r["unschedulable"] == P for r in scen),
            f"a scenario does not account for all {P} pods")
    require(sum(r["placed"] for r in scen) == agg["total_placed"] > 0,
            "scenario rows do not add up to total_placed")
    return {
        "size": f"{S} scenarios x {N} nodes x {P} pods",
        "engine": agg["engine"],
        "wall_clock_s": agg["wall_clock_s"],
        "placements": agg["total_placed"],
        "placements_per_sec": agg["placements_per_sec"],
        "placed_scenario0": scen[0]["placed"],
        "completions_on": agg["completions_on"],
    }


def phase_run_config2(placed_scenario0: int) -> dict:
    _, N, P = config_size(CONFIG2)
    (row,) = cli(["run", CONFIG2, "--strategy", "jax"])
    require(row["kind"] == "replay-jax", f"row kind {row['kind']!r}")
    require(row["placed"] + row["unschedulable"] == P,
            f"replay does not account for all {P} pods")
    require(row["placed"] == placed_scenario0,
            f"single replay placed {row['placed']}, what-if scenario 0 "
            f"placed {placed_scenario0} (same cluster and workload seeds)")
    return {
        "size": f"{N} nodes x {P} pods",
        "engine": row["engine"],
        "wall_clock_s": row["wall_clock_s"],
        "placements": row["placed"],
        "placements_per_sec": row["placements_per_sec"],
        "unschedulable": row["unschedulable"],
    }


def phase_parity() -> dict:
    import dataclasses
    import re

    import numpy as np

    from kubernetes_simulator_tpu.framework.framework import FrameworkConfig
    from kubernetes_simulator_tpu.models.encode import encode
    from kubernetes_simulator_tpu.sim.borg import BorgSpec, make_borg_encoded
    from kubernetes_simulator_tpu.sim.greedy import greedy_replay
    from kubernetes_simulator_tpu.sim.jax_runtime import JaxReplayEngine
    from kubernetes_simulator_tpu.sim.synthetic import make_cluster
    from kubernetes_simulator_tpu.sim.whatif import (
        Perturbation,
        Scenario,
        ScenarioSet,
        WhatIfEngine,
    )
    from kubernetes_simulator_tpu.utils import profiling
    from kubernetes_simulator_tpu.utils.config import SimConfig, build_case

    def same(dev, ref, what, ref_name="the host reference"):
        bad = np.nonzero(np.asarray(dev) != np.asarray(ref))[0]
        require(bad.size == 0,
                f"{what}: {bad.size} placements differ from {ref_name}, "
                f"first at pod {bad[:5].tolist()}")

    def select_form(result, what, want=None):
        """The chunk program's select form (ops.tpu3.select_form), printed;
        the zoned Borg case has to run the one-reduce form."""
        tel = getattr(result, "fleet_telemetry", None) or result.telemetry
        form = tel.summary().get("select_form")
        say(f"{what}: select_form {form}")
        require(want is None or form == want,
                f"{what}: select_form {form!r}, not {want!r}")
        return form

    def resolved_terms(result, what):
        """A what-if batch's in-wave usage corrections
        (ops.tpu3.inwave_corrections): collisions resolved among the
        scenarios' scalars, a compare and R selects a node a term."""
        form = result.fleet_telemetry.summary().get("inwave_corrections")
        say(f"{what}: inwave_corrections {form}")
        require(form == "resolved_terms",
                f"{what}: inwave_corrections {form!r}, not 'resolved_terms'")
        return form

    out = {}
    # (a) the head of the config-2 case, full default plugin set.
    cfg = SimConfig.load(CONFIG2)
    cluster, pods = build_case(cfg)
    ec, ep = encode(cluster, pods[:PARITY_HEAD_PODS])
    host = greedy_replay(ec, ep, cfg.framework, wave_width=8)
    dev = JaxReplayEngine(ec, ep, cfg.framework, wave_width=8).replay()
    same(dev.assignments, host.assignments, "config2 head")
    require(dev.placed == host.placed > 0, "config2 head: placed differs")
    out["config2_head"] = {"pods": PARITY_HEAD_PODS, "placed": dev.placed,
                           "select_form": select_form(dev, "config2 head")}

    # (b) Borg-shaped, gangs on, finite durations: 0.1-core requests (not
    # bf16-exact) are bound AND released mid-replay.
    ec, ep, meta = make_borg_encoded(
        BorgSpec(nodes=BORG_NODES, tasks=BORG_TASKS, seed=0,
                 mean_duration=BORG_MEAN_DURATION)
    )
    fw = FrameworkConfig()
    kw = dict(wave_width=8, chunk_waves=BORG_CHUNK_WAVES,
              granularity_guard=False)
    host = greedy_replay(
        ec, ep, fw, wave_width=8, completions_chunk_waves=BORG_CHUNK_WAVES
    )
    no_release = greedy_replay(ec, ep, fw, wave_width=8)
    require(meta["num_gangs"] > 0, "borg case has no gangs")
    require(0 < host.unschedulable < BORG_TASKS // 2,
            "borg case: the cluster never runs full")
    require((host.assignments != no_release.assignments).any(),
            "borg case: releases change no placement — vacuous")
    dev = JaxReplayEngine(ec, ep, fw, **kw).replay()
    same(dev.assignments, host.assignments, "borg single replay")
    require(dev.placed == host.placed, "borg single replay: placed differs")
    out["borg_single"] = {
        "nodes": BORG_NODES, "tasks": BORG_TASKS, "placed": dev.placed,
        "unschedulable": dev.unschedulable,
        "select_form": select_form(dev, "borg single replay"),
    }

    # (c) the same case as a 4-scenario what-if, completions on, every
    # scenario against the host reference on the equally perturbed
    # cluster. The batch takes the DEVICE release program
    # (WhatIfEngine._release_core/_release_fn) and hands every task's node
    # back from the device's placement buffer when the last chunk is done.
    cpu = ec.vocab._r["cpu"]
    half, few = np.arange(BORG_NODES // 2), np.array([2, 5, 7])
    scen = [
        Scenario(),
        Scenario([Perturbation("scale_capacity", nodes=half,
                               resource="cpu", factor=0.75)]),
        Scenario([Perturbation("scale_capacity", nodes=few,
                               resource="cpu", factor=1.5)]),
        Scenario([Perturbation("node_down", nodes=np.array([3]))]),
    ]
    refs = [host]
    for sc in scen[1:]:
        (pt,) = sc.perturbations
        alloc = ec.allocatable.copy()
        if pt.op == "node_down":
            alloc[pt.nodes, :] = 0.0
        else:
            alloc[pt.nodes, cpu] = alloc[pt.nodes, cpu] * pt.factor
        refs.append(greedy_replay(
            dataclasses.replace(ec, allocatable=alloc), ep, fw,
            wave_width=8, completions_chunk_waves=BORG_CHUNK_WAVES,
        ))
    require(len({r.placed for r in refs}) == 4,
            "what-if: the perturbations do not change the outcome")
    on_dev = WhatIfEngine(ec, ep, scen, fw, completions=True,
                          collect_assignments=True, **kw)
    require(on_dev._completions_dev,
            "what-if did not take the device release path")
    r_dev = on_dev.run()
    require(r_dev.completions_on, "what-if ran arrivals-only")
    for s, ref in enumerate(refs):
        same(r_dev.assignments[s], ref.assignments,
             f"what-if scenario {s} (device release path)")
        require(int(r_dev.placed[s]) == ref.placed,
                f"what-if scenario {s}: device release path placed "
                f"{int(r_dev.placed[s])}, host reference {ref.placed}")
    out["borg_whatif"] = {
        "scenarios": len(scen),
        "placed": [int(x) for x in r_dev.placed],
        "select_form": select_form(r_dev, "borg what-if"),
        "inwave_corrections": resolved_terms(r_dev, "borg what-if"),
    }

    # (c2) the same case with a pending queue: ``retry_buffer`` re-tries the
    # tasks that failed at every chunk boundary, in priority order, on the
    # device: two programs a boundary, the pass (``jit_per_scenario_retry``)
    # and the arrival scan with the queue's upkeep
    # (``jit_per_scenario_arrivals``), each with ONE loop that carries the
    # state, so that the compiler keeps the step's node planes on chip in
    # both (printed below from the compiled texts: at this size for the
    # record, the full cell's are held by tests/test_retry_memory_spaces.py
    # and PERF.md §7). Every scenario's nodes AND bind
    # boundaries against the host reference with the same buffer on the
    # equally perturbed cluster, one scenario with a taint that nobody
    # tolerates. The pass walks each scenario's own queue, so it has to
    # read a slot's toleration class row as a select among the plane's rows
    # (ops.tpu3.class_row_reads "select"); the arrival waves, whose slots
    # every scenario shares, by the dynamic index ("slice").
    q_scen = scen + [Scenario([Perturbation(
        "add_taint", nodes=np.array([1, 6]), key="whatif", value="cordon")])]
    q_refs = [
        greedy_replay(e, ep, fw, wave_width=8, retry_buffer=RETRY_BUFFER,
                      completions_chunk_waves=BORG_CHUNK_WAVES)
        for e in ScenarioSet(ec, q_scen, keep_host_stacks=True).host_clusters(ec)
    ]
    require(all((r.bind_boundary >= 0).any() for r in q_refs),
            "retry what-if: some scenario's passes bind nothing")
    queued = WhatIfEngine(ec, ep, q_scen, fw, completions=True,
                          retry_buffer=RETRY_BUFFER,
                          collect_assignments=True, **kw)
    require(queued.release_path == "device",
            "retry what-if did not take the device release path")
    programs = {}
    for attr in ("_retry_fn", "_chunk_fn"):
        def first(*args, _attr=attr, _real=getattr(queued, attr)):
            if _attr not in programs:  # before the call: it donates its buffers
                programs[_attr] = _real.lower(*args)
            return _real(*args)

        setattr(queued, attr, first)
    r_q = queued.run()
    require(sorted(programs) == ["_chunk_fn", "_retry_fn"],
            f"retry what-if: the boundary's programs were {sorted(programs)}")
    S_q, (N_q, R_q) = len(q_scen), ec.allocatable.shape
    planes = {"used": f"f32[{S_q},{R_q},{N_q}]",
              "allocatable": f"f32[{S_q},{N_q},{R_q}]",
              "class_mask": f"bf16[{S_q},2,{N_q}]"}
    # plane by plane: at this size the compiler may fold one into another
    # form, and a loop is reported for the planes it does carry
    texts = {re.search(r"module @(\w+)", low.as_text()).group(1):
             low.compile().as_text() for low in programs.values()}
    spaces = {
        module: {name: {loop: got[shape] for loop, got in
                        profiling.loop_memory_spaces(text, [shape]).items()}
                 for name, shape in planes.items()}
        for module, text in texts.items()
    }
    say(f"retry what-if: memory space of {planes} in each loop that carries "
        f"it (1 = on chip, 0 = HBM): {spaces}")
    for s, ref in enumerate(q_refs):
        same(r_q.assignments[s], ref.assignments,
             f"retry what-if scenario {s}")
        same(r_q.bind_boundary[s], ref.bind_boundary,
             f"retry what-if scenario {s}: bind boundaries")
    reads = r_q.fleet_telemetry.summary()["class_row_reads"]
    say(f"retry what-if: class_row_reads {reads}")
    require(reads["retry"] == "select" and reads["arrival"] == "slice"
            and reads["tol_classes"] == 2,
            "retry what-if: the pass does not select its class rows, or the "
            f"arrival waves do not slice theirs ({reads})")
    no_queue = r_dev.fleet_telemetry.summary()["class_row_reads"]
    require(no_queue["arrival"] == "slice" and "retry" not in no_queue,
            f"borg what-if: class_row_reads {no_queue} in a batch with no queue")
    # Every pass ends with the fullest scenario's last queued wave (a loop
    # whose trip count is read from the queue): ``pass_waves`` against the
    # depths the references' answers give (a task stands in pass b's queue
    # if it failed in a chunk before b, was not dropped and no earlier pass
    # bound it).
    w_idx = np.asarray(queued.waves.idx)
    since = np.full(ep.num_pods, 1 << 30)
    since[w_idx[w_idx >= 0]] = np.nonzero(w_idx >= 0)[0] // BORG_CHUNK_WAVES + 1
    retry = r_q.fleet_telemetry.summary()["retry"]
    deepest = np.max([[((since <= b) & ((r.bind_boundary >= b)
                                        | (r.bind_boundary == -2))).sum()
                       for b in range(retry["passes"])] for r in q_refs], axis=0)
    walked, whole = int((-(-deepest // 8)).sum()), retry["passes"] * RETRY_BUFFER // 8
    say(f"retry what-if: pass_waves {retry['pass_waves']} of {whole} compiled")
    require(retry["pass_waves"] == {"mean": float(walked), "max": walked}
            and 0 < walked < whole,
            f"retry what-if: the passes walked {retry['pass_waves']} wave "
            f"steps, the references' queues give {walked} (of {whole})")
    out["retry_whatif"] = {
        "scenarios": len(q_scen), "buffer": RETRY_BUFFER,
        "placed": [int(x) for x in r_q.placed],
        "retry_placed": [int((r_q.bind_boundary[s] >= 0).sum())
                         for s in range(len(q_scen))],
        "class_row_reads": reads,
        "pass_waves": walked, "pass_waves_compiled": whole,
        "loop_memory_spaces": spaces,
        "inwave_corrections": resolved_terms(r_q, "retry what-if"),
    }

    # (d) the release-heavy case. The CPU backend's dot is exact and
    # sequential, so only here can an MXU that is not show: the device
    # release program sums a node's releases through single-term products
    # (ops.release_planes), and each scenario has to equal the host
    # reference (np.add.at in list order) placement for placement.
    ec, ep, _ = make_borg_encoded(
        BorgSpec(nodes=BORG_NODES, tasks=BORG_TASKS, seed=HEAVY_SEED,
                 mean_duration=HEAVY_MEAN_DURATION)
    )
    alloc = ec.allocatable.copy()
    alloc[half, cpu] = alloc[half, cpu] * 0.75
    refs = [
        greedy_replay(e, ep, fw, wave_width=8,
                      completions_chunk_waves=HEAVY_CHUNK_WAVES)
        for e in (ec, dataclasses.replace(ec, allocatable=alloc))
    ]
    require(0 < refs[0].unschedulable < BORG_TASKS // 2,
            "release-heavy case: the cluster never runs full")
    heavy = WhatIfEngine(
        ec, ep, scen[:2], fw, completions=True, collect_assignments=True,
        wave_width=8, chunk_waves=HEAVY_CHUNK_WAVES, granularity_guard=False)
    require(heavy._completions_dev,
            "release-heavy what-if did not take the device release path")
    r_heavy = heavy.run()
    rounds = r_heavy.fleet_telemetry.summary()["release_rounds"]
    require(rounds >= HEAVY_MIN_ROUNDS,
            f"release-heavy case: release_rounds {rounds}, no deep collision")
    for s, ref in enumerate(refs):
        same(r_heavy.assignments[s], ref.assignments,
             f"release-heavy what-if scenario {s} (device release path)")
        require(int(r_heavy.placed[s]) == ref.placed,
                f"release-heavy what-if scenario {s}: placed differs")
    out["release_heavy_whatif"] = {
        "placed": [int(x) for x in r_heavy.placed],
        "unschedulable": refs[0].unschedulable,
        "release_rounds": rounds,
        "select_form": select_form(r_heavy, "release-heavy what-if"),
        "inwave_corrections": resolved_terms(r_heavy, "release-heavy what-if"),
    }
    say(f"release-heavy what-if: release_rounds {rounds}")

    # (e) the Borg case on a stride-zoned cluster: both chunk programs make
    # ONE node-wide reduce a slot (the best packed node per zone), the
    # single replay through a lane fold and the scenario-mapped batch
    # through a sublane view (ops.tpu.zone_packed_max), two compiled forms
    # that have to agree task for task: scenario 0 is the single replay.
    # Held to each other and not to the host reference, which at this size
    # parts from the device at a float32 floor edge on most seeds, on the
    # chip and off it, with the two-pass form too (PERF.md §7).
    ec, ep, _ = make_borg_encoded(
        BorgSpec(nodes=ZONED_NODES, tasks=BORG_TASKS, seed=0,
                 mean_duration=BORG_MEAN_DURATION)
    )
    dev = JaxReplayEngine(ec, ep, fw, **kw).replay()
    r_zoned = WhatIfEngine(ec, ep, scen, fw, completions=True,
                           collect_assignments=True, **kw).run()
    same(r_zoned.assignments[0], dev.assignments,
         "zoned what-if scenario 0", "the single replay's")
    require(0 < dev.unschedulable < BORG_TASKS // 2,
            "zoned borg case: the cluster never runs full")
    for s in range(len(scen)):
        require(int((r_zoned.assignments[s] >= 0).sum()) == int(r_zoned.placed[s]),
                f"zoned what-if scenario {s}: placed differs from the "
                "placements handed back")
        require(s == 0 or (r_zoned.assignments[s] != dev.assignments).any(),
                f"zoned what-if scenario {s}: the perturbation changes nothing")
    out["borg_zoned"] = {
        "nodes": ZONED_NODES, "placed": [int(x) for x in r_zoned.placed],
        "select_form": [
            select_form(dev, "zoned single replay", "zone_packed"),
            select_form(r_zoned, "zoned what-if", "zone_packed"),
        ],
        "inwave_corrections": resolved_terms(r_zoned, "zoned what-if"),
    }
    # (f) the default plugin set as a what-if batch, arrivals only, on 160
    # nodes: hostname is then a host-scale topology, as at 5,000, so the
    # anti-affinity rows are [H, N] count planes and the chunk program is
    # the two-pass form. Scenario 0 has to be the single replay pod for pod,
    # the placements come back through the hand-back of the chunks' choices.
    ec, ep = encode(make_cluster(PLUGINS_NODES, seed=0, taint_fraction=0.1),
                    pods[:PLUGINS_PODS])  # config 2's pods, as in (a)
    dev = JaxReplayEngine(ec, ep, cfg.framework, wave_width=8,
                          chunk_waves=BORG_CHUNK_WAVES).replay()
    r_plug = WhatIfEngine(ec, ep, scen, cfg.framework, wave_width=8,
                          chunk_waves=BORG_CHUNK_WAVES,
                          collect_assignments=True).run()
    same(r_plug.assignments[0], dev.assignments,
         "default-plugins what-if scenario 0", "the single replay's")
    planes = r_plug.fleet_telemetry.summary()["count_planes"]
    require(planes["host_rows"] > 0,
            f"default-plugins what-if: no host-scale count rows ({planes})")
    require(planes["host_read_positions"] > 0,
            "default-plugins what-if: no position of the term axis reads a "
            f"host row by its index ({planes})")
    require(0 < planes["expand_positions"] < planes["term_rows"],
            "default-plugins what-if: a slot's domain-row expansion does not "
            f"run over some positions of the term axis and not all ({planes})")
    require(planes["host_commit"] == {"rows": planes["host_rows"],
                                      "elementwise": 0, "dot": 0},
            "default-plugins what-if: its host rows are not all committed "
            f"row by row, with no dot ({planes})")
    for s in range(len(scen)):
        require(int((r_plug.assignments[s] >= 0).sum()) == int(r_plug.placed[s]),
                f"default-plugins what-if scenario {s}: placed differs from "
                "the placements handed back")
    require((r_plug.assignments[1:] != dev.assignments).any(),
            "default-plugins what-if: the perturbations change nothing")
    out["plugins_whatif"] = {
        "nodes": PLUGINS_NODES, "pods": PLUGINS_PODS,
        "placed": [int(x) for x in r_plug.placed], "count_planes": planes,
        "select_form": select_form(r_plug, "default-plugins what-if",
                                   "two_pass"),
        "inwave_corrections": resolved_terms(r_plug, "default-plugins what-if"),
    }
    # (h) pod groups WIDER than the wave: jobs of 16 workers on 16 nodes (6
    # with 8 nvidia.com/gpu) at waveWidth 8, two waves a group, the
    # transaction carried by the scan (ops.tpu3.GangTxn) and some groups
    # rolled back where they close; held to the same trace at waveWidth 16
    # (one wave a group, the wave-local mask): two device programs against
    # each other, in a what-if batch and in the single replay. No pod group
    # may come back partly bound.
    from kubernetes_simulator_tpu.models.encode import PAD
    from kubernetes_simulator_tpu.sim.synthetic import make_workload
    from kubernetes_simulator_tpu.sim.whatif import uniform_scenarios

    gpu = "nvidia.com/gpu"
    jobs, _ = make_workload(
        400, seed=0, gang_sizes={1: 0.5, 4: 0.2, 16: 0.3},
        job_extended_resource={
            "resource": gpu, "counts": {1: 0.5, 2: 0.3, 8: 0.2}, "wideFrom": 8,
            "smallJobFraction": 0.05, "wideJobFraction": 0.6})
    ec, ep = encode(
        make_cluster(16, seed=0, extended_resources={gpu: (8, 0.45)}), jobs)
    scen_w = uniform_scenarios(ec, 4, seed=0)
    wide = {}
    for width in (8, 16):
        wide[width] = WhatIfEngine(
            ec, ep, scen_w, FrameworkConfig(), wave_width=width, chunk_waves=7,
            collect_assignments=True).run()
    r_wide = wide[8]
    single = JaxReplayEngine(ec, ep, FrameworkConfig(), wave_width=8,
                             chunk_waves=7).replay()
    same(r_wide.assignments.reshape(-1), wide[16].assignments.reshape(-1),
         "wide-group what-if at waveWidth 8", "the same trace's at waveWidth 16")
    same(r_wide.assignments[0], single.assignments,
         "wide-group what-if scenario 0", "the single replay's")
    gangs = r_wide.fleet_telemetry.summary().get("gangs")
    say(f"wide-group what-if: gangs {gangs}")
    require(gangs is not None and gangs["rollback_form"] == "txn_plane",
            f"wide-group what-if: no gangs counters or rollback_form ({gangs})")
    require(gangs["wide_groups"] >= 20 and gangs["max_waves_spanned"] == 2,
            f"wide-group what-if: not groups of 16 over two waves ({gangs})")
    require(gangs["wide_rolled_back"] > 0 and gangs["pods_rolled_back"] > 0,
            f"wide-group what-if: no group rolled back after a bind ({gangs})")
    mine = single.telemetry.summary().get("gangs")
    require(mine is not None and mine["pods_rolled_back"] > 0,
            f"wide-group replay: no gangs counters ({mine})")
    members = np.bincount(ep.group_id[ep.group_id != PAD])
    for s in range(len(scen_w)):
        a = r_wide.assignments[s]
        bound = np.bincount(ep.group_id[(ep.group_id != PAD) & (a >= 0)],
                            minlength=len(members))
        partly = int(((bound > 0) & (bound < members)).sum())
        require(partly == 0,
                f"wide-group what-if scenario {s}: {partly} pod groups partly bound")
        require(int((a >= 0).sum()) == int(r_wide.placed[s]),
                f"wide-group what-if scenario {s}: placed differs from the "
                "placements handed back")
    out["wide_gangs_whatif"] = {
        "nodes": 16, "pods": 400, "placed": [int(x) for x in r_wide.placed],
        "gangs": gangs,
        "inwave_corrections": resolved_terms(r_wide, "wide-group what-if"),
    }
    # (g) the normalize rows divide exactly. The chip's float32 division is
    # not correctly rounded (floor(6100 / 61) reads 99), which the CPU
    # backend cannot show: every node-affinity weight against itself, and
    # spread triples (max, min, score) against the integer division.
    import jax
    import jax.numpy as jnp

    from kubernetes_simulator_tpu.ops import tpu as T

    w = np.arange(1, 100, dtype=np.float32)
    got = np.asarray(jax.vmap(
        lambda x: T._normalize_row(jnp.stack([x, 0 * x]), None, x, None,
                                   minmax=False, reverse=False)
    )(jnp.asarray(w)))
    require((got == [100.0, 0.0]).all(),
            f"max-normalize: weights {w[got[:, 0] != 100].tolist()} of "
            "themselves are not 100")
    hi, lo, sc = np.meshgrid(np.arange(1, 400), np.arange(0, 400, 7),
                             np.arange(0, 400, 3), indexing="ij")
    ok = (lo <= sc) & (sc <= hi)
    hi, lo, sc = hi[ok], lo[ok], sc[ok]
    got = np.asarray(jax.vmap(
        lambda h, l, x: T.spread_norm_from_extrema(
            x, jnp.asarray(False), h, l, jnp.asarray(True), True)
    )(*(jnp.asarray(a, jnp.float32) for a in (hi, lo, sc))))
    bad = np.nonzero(got != (100 * (hi + lo - sc)) // hi)[0]
    require(bad.size == 0,
            f"spread normalize: {bad.size} of {ok.sum()} triples part from "
            f"the integer division, first (max, min, score) "
            f"{(hi[bad[:1]], lo[bad[:1]], sc[bad[:1]])}")
    out["normalize_exact"] = {"weights": len(w), "spread_triples": int(ok.sum())}
    return out


SERVE_QUERIES = [
    # q1-q3 fill config20's batch of 3 (the cold build); q4 repeats q1 in a
    # second batch, which the resident executable must answer WARM and
    # identically.
    {"op": "defrag", "tenant": "team-a", "id": "q1",
     "nodes": [3, 7], "drainAt": 5.0, "recoverAt": 12.0},
    {"op": "defrag", "tenant": "team-b", "id": "q2",
     "nodes": [11, 12], "drainAt": 6.0, "recoverAt": 14.0},
    {"op": "defrag", "tenant": "team-a", "id": "q3",
     "nodes": [20, 21, 22, 23], "drainAt": 8.0},
    {"op": "defrag", "tenant": "team-c", "id": "q4",
     "nodes": [3, 7], "drainAt": 5.0, "recoverAt": 12.0},
]
_SERVE_ANSWER_KEYS = (
    "placed", "unschedulable", "placed_delta", "evictions",
    "evict_rescheduled", "evict_stranded", "evict_latency_mean",
    "stranded_cpu", "frag_index_cpu", "packing_efficiency",
)


def phase_serve() -> dict:
    """``serve examples/config20_service.yaml < queries.ndjson``. config20
    batches 3 queries (service.maxBatch), so q1-q3 are one cold batch and
    q4 a second, warm one. The service's cold/warm counters reach only the
    log, so they are read off the rows: every batch with ``warm: false``
    was a cold build (a ValueError from set_scenarios quietly rebuilds —
    sim/service.py — which would show here as a second cold batch)."""
    prev_cwd, prev_stdin = os.getcwd(), sys.stdin
    with tempfile.TemporaryDirectory(prefix="ksim_smoke_") as tmp:
        queries = Path(tmp) / "queries.ndjson"
        queries.write_text("".join(json.dumps(q) + "\n" for q in SERVE_QUERIES))
        # config20 writes ./service_results.jsonl: run it from the temp dir.
        os.chdir(tmp)
        try:
            with open(queries) as sys.stdin:
                cli(["serve", CONFIG20])
        finally:
            sys.stdin = prev_stdin
            os.chdir(prev_cwd)
        rows = [
            json.loads(ln)
            for ln in (Path(tmp) / "service_results.jsonl").read_text().splitlines()
        ]
    errors = [r for r in rows if r["kind"] == "query-error"]
    results = {r["query"]: r for r in rows if r["kind"] == "query-result"}
    require(not errors, f"query-error rows: {errors}")
    require(sorted(results) == ["q1", "q2", "q3", "q4"],
            f"answered {sorted(results)}, want q1..q4")
    cold = {r["batch"] for r in results.values() if not r["warm"]}
    warm = {r["batch"] for r in results.values() if r["warm"]}
    require(len(cold) == 1, f"cold_builds == {len(cold)}, want 1")
    require(len(warm) >= 1, "warm_hits == 0: the resident engine was not reused")
    require(results["q4"]["warm"], "the repeated query was not warm")
    for k in _SERVE_ANSWER_KEYS:
        require(results["q1"][k] == results["q4"][k],
                f"q4 repeats q1 but {k}: {results['q1'][k]} != {results['q4'][k]}")
    require(results["q1"]["evictions"] > 0, "the drain evicted nothing")
    return {
        "size": "64 nodes x 2048 pods (serving path, not a size claim)",
        "engine": results["q1"]["engine"],
        "queries": len(results),
        "cold_builds": len(cold),
        "warm_hits": len(warm),
        "cold_latency_s": results["q1"]["latency_s"],
        "warm_latency_s": results["q4"]["latency_s"],
        "placements": sum(r["placed"] for r in results.values()),
    }


def device_peaks() -> list:
    import jax

    return [d.memory_stats()["peak_bytes_in_use"] for d in jax.devices()]


def phase_mesh() -> dict:
    """config 5 sharded over every visible device vs the same file on one
    device, rows compared under KSIM_DETERMINISTIC_JSONL=1."""
    import yaml

    def strip(rows):
        # The one-device run reads a copy of the file with mesh off: its
        # provenance stamps differ by construction, its results must not.
        drop = ("config", "config_hash", "mesh")
        return [{k: v for k, v in r.items() if k not in drop} for r in rows]

    before = device_peaks()
    prev = os.environ.get("KSIM_DETERMINISTIC_JSONL")
    os.environ["KSIM_DETERMINISTIC_JSONL"] = "1"
    # The CLI's rows carry no telemetry: the meshed run's own summary is
    # taken as the engine hands it back, at no further run.
    from unittest import mock

    from kubernetes_simulator_tpu.sim.whatif import WhatIfEngine

    from kubernetes_simulator_tpu.utils import profiling

    summaries, engine_run, engine_init = [], WhatIfEngine.run, WhatIfEngine.__init__
    armed, trace_dir = [], tempfile.mkdtemp(prefix="ksim_smoke_trace_")

    def run_and_keep(self):
        res = engine_run(self)
        summaries.append(res.fleet_telemetry.summary())
        if self.mesh is not None and not armed:
            # One more batch of the meshed engine, nothing left to compile:
            # armed and traced as ``--profile-dir`` does it.
            with profiling.device_trace(trace_dir):
                again = engine_run(self)
            armed.append(again.fleet_telemetry.summary())
        return res

    def init_handing_back(self, *args, **kw):
        # The CLI asks for counts only; the meshed run here also hands the
        # placements back, so that the batch has a fetch to trace.
        engine_init(self, *args, **{**kw, "collect_assignments": True})

    try:
        with mock.patch.object(WhatIfEngine, "run", run_and_keep), \
                mock.patch.object(WhatIfEngine, "__init__", init_handing_back):
            rows_mesh = cli(["what-if", CONFIG5])
        after = device_peaks()
        with tempfile.TemporaryDirectory(prefix="ksim_smoke_") as tmp:
            with open(CONFIG5) as f:
                doc = yaml.safe_load(f)
            doc["whatIf"]["mesh"] = False
            one = Path(tmp) / "config5_one_device.yaml"
            one.write_text(yaml.safe_dump(doc))
            rows_one = cli(["what-if", str(one)])
    finally:
        if prev is None:
            del os.environ["KSIM_DETERMINISTIC_JSONL"]
        else:
            os.environ["KSIM_DETERMINISTIC_JSONL"] = prev
    S, N, P = config_size(CONFIG5)
    agg, scen = split_whatif(rows_mesh)
    require(agg["mesh"] is True and agg["scenarios"] == S == len(scen),
            f"mesh run: mesh={agg['mesh']} scenarios={agg['scenarios']}")
    # Devices past the first ran nothing in the earlier phases, so a peak
    # that grew means the mesh run put data (its shard) there.
    held = [a > b for a, b in zip(after, before)]
    require(all(held[1:]) and after[0] > 0,
            f"devices that held a shard: {held} (peak bytes {after})")
    require(strip(rows_mesh) == strip(rows_one),
            "mesh rows differ from the one-device run of the same file")
    # What the meshed run says of itself (``summary()["mesh"]``): every
    # device holds its share, the tables went to the devices, and neither
    # the chunk program nor any hand-back holds a collective.
    counters = summaries[0].get("mesh") or {}
    say(f"mesh: {counters}")
    require(counters.get("devices") == len(held)
            and counters.get("scenarios_per_device") == S // len(held),
            f"mesh counters: {counters} for {S} scenarios on {len(held)} devices")
    require(counters.get("put_bytes", 0) > 0 and "fetch_bytes" in counters,
            f"mesh counters: no bytes put on the devices: {counters}")
    held_by = counters.get("collectives", {})
    require(held_by.get("chunk") == 0 and not held_by.get("handback"),
            f"mesh programs hold collectives: {held_by}")
    # The armed batch: one root, the fetch's span carrying the bytes the
    # counter holds, and every span of the program closed inside the root
    # (one left open is never written; a root left open takes all with it).
    require(len(armed) == 1, f"armed meshed batches: {len(armed)}")
    spans = program_spans(trace_dir)
    roots = [e for e in spans if e[0].startswith("whatif_run:")]
    require(len(roots) == 1, f"root spans of one armed batch: {roots}")
    root = roots[0]
    loose = [e[0] for e in spans if e is not root and not (
        e[3] == root[3] and root[1] <= e[1] and e[2] <= root[2])]
    require(not loose, f"spans outside the root {root[0]}: {loose}")
    names = [e[0] for e in spans]
    for phase in ("stage", "dispatch", "device_wait", "gather", "handback"):
        require(phase in names, f"no {phase!r} span in the armed batch: {names}")
    fetch = [e for e in spans if e[0] == "mesh_fetch"]
    handback = [e for e in spans if e[0] == "handback"]
    want = armed[0]["mesh"]["fetch_bytes"]
    require(len(fetch) == 1 and fetch[0][4] == {"bytes": want} and want > 0
            and handback[0][1] <= fetch[0][1] and fetch[0][2] <= handback[0][2],
            f"mesh_fetch spans {fetch} for fetch_bytes {want}")
    say(f"mesh: armed batch {root[0]} {(root[2] - root[1]) / 1e6:.3f} ms, "
        f"{len(spans)} spans, mesh_fetch {want} bytes in "
        f"{(fetch[0][2] - fetch[0][1]) / 1e6:.3f} ms")
    return {
        "size": f"{S} scenarios x {N} nodes x {P} pods",
        "engine": agg["engine"],
        "n_devices": len(held),
        "peak_bytes_per_device": after,
        "placements": agg["total_placed"],
        "rows_equal_one_device": True,
        "mesh": counters,
    }


def result_line(report: dict) -> str:
    """The last stdout line: exactly ``ok`` and ``device`` {platform, kind,
    count} as JAX reports them — the driver refuses any other key."""
    dev = report["device"]
    return json.dumps({
        "ok": report["ok"],
        "device": {
            "platform": dev["platform"],
            "kind": dev["kind"],
            "count": dev["count"],
        },
    })


def main() -> int:
    t_start = time.perf_counter()
    import jax

    dev0 = jax.devices()[0]
    if dev0.platform != "tpu":
        say(f"no TPU: jax.devices()[0].platform == {dev0.platform!r}, "
            f"JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r} — refusing "
            "to run (this smoke has no CPU mode)")
        return 1
    meter = CompileMeter()

    import importlib.metadata as md

    import jaxlib

    from kubernetes_simulator_tpu import native
    from kubernetes_simulator_tpu.utils import compile_cache

    cache_dir = compile_cache.enable()
    require(cache_dir is not None,
            "compile cache is off (KSIM_COMPILE_CACHE=0?) — every run "
            "would pay a cold compile")
    require(native.available(),
            "native packers did not build (g++ output is in the log "
            "above) — the Python fallbacks cost minutes at 1M pods")
    report = {
        "ok": True,
        "device": {
            "platform": dev0.platform,
            "kind": dev0.device_kind,
            "count": len(jax.devices()),
        },
        "versions": {
            "jax": jax.__version__,
            "jaxlib": jaxlib.__version__,
            "libtpu": md.version("libtpu"),
        },
        "compile_cache_dir": cache_dir,
        "native": True,
        "phases": {},
    }

    def run(name, fn, *args):
        say(f"phase {name} ...")
        t0, c0 = time.perf_counter(), dict(meter.totals)
        out = fn(*args)
        out["cold_wall_s"] = round(time.perf_counter() - t0, 3)
        out.update(meter.since(c0))
        out.setdefault("reduced", None)
        say(f"phase {name} ok: {json.dumps(out)}")
        report["phases"][name] = out
        return out

    w3 = run("whatif-config3", phase_whatif_config3)
    run("run-config2", phase_run_config2, w3["placed_scenario0"])
    run("parity", phase_parity)
    run("serve", phase_serve)
    if len(jax.devices()) > 1:
        run("mesh", phase_mesh)
    report["total_wall_s"] = round(time.perf_counter() - t_start, 3)
    report["claim"] = None
    print(json.dumps({"kind": "chip-smoke-report", **report}))
    print(result_line(report), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
