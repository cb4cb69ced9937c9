"""CLI (SURVEY.md §2 "CLI / API"): run replays and what-if sweeps from a
YAML config.

    python -m kubernetes_simulator_tpu run config.yaml [--strategy jax]
    python -m kubernetes_simulator_tpu what-if config.yaml
    python -m kubernetes_simulator_tpu tune config.yaml
    python -m kubernetes_simulator_tpu serve config.yaml < queries.ndjson
    python -m kubernetes_simulator_tpu validate config.yaml
"""

from __future__ import annotations

import argparse
import json
import os
import sys

from .framework.registry import get_strategy
from .parallel import dcn
from .utils import compile_cache
from .utils.config import SimConfig, build_encoded_case
from .utils.metrics import (
    JsonlWriter,
    config_hash,
    log,
    replay_row,
    whatif_rows,
)
from .utils.profiling import device_trace


def _writer_context(cfg, config_path: str) -> dict:
    """Row-stamping context (schema v2): the seed / engine / config hash
    that produced every row in the file, so results stay attributable
    after the config moves on."""
    import yaml

    with open(config_path) as f:
        d = yaml.safe_load(f) or {}
    seed = (
        cfg.borg.seed if cfg.borg is not None
        else (cfg.workload.seed if cfg.workload is not None else 0)
    )
    return {
        "seed": int(seed),
        "engine": cfg.strategy,
        "config_hash": config_hash(d),
    }


def _chaos_timeline(cfg, ec, ep, seed):
    """Materialize one seeded chaos campaign from the ``chaos:`` section
    (horizon defaults to the workload makespan — later events could never
    fire anyway)."""
    from .sim.synthetic import make_chaos_timeline

    ch = cfg.chaos
    last_arrival = float(ep.arrival.max())
    horizon = ch.horizon if ch.horizon is not None else last_arrival
    events = make_chaos_timeline(
        ec.num_nodes,
        seed=seed,
        horizon=horizon,
        mtbf=ch.mtbf,
        mttr=ch.mttr,
        node_fraction=ch.node_fraction,
        max_events=ch.max_events,
    )
    # Envelope guard: device engines replay no chunks past the final
    # wave, so events beyond the last arrival can only fire on the CPU
    # engine — a configured horizon out there is almost always a
    # mis-set horizon, not a longer campaign.
    late = sum(1 for ev in events if ev.time > last_arrival)
    if late:
        log.warning(
            "chaos: %d event(s) beyond the trace's last arrival "
            "(t=%.1f; chaos.horizon=%.1f) — device engines stop at the "
            "final wave and will never apply them",
            late, last_arrival, horizon,
        )
    return events


def cmd_run(args) -> int:
    cfg = SimConfig.load(args.config)
    if args.strategy:
        cfg.strategy = args.strategy
    timeline_out = (
        getattr(args, "timeline_out", None) or cfg.telemetry.timeline_out
    )
    gran = cfg.telemetry.granularity
    if timeline_out and gran != "off":
        gran = "timeline"  # a timeline sink needs timeline events
    ec, ep = build_encoded_case(cfg)
    log.info("encoded %d nodes / %d pods", ec.num_nodes, ep.num_pods)
    factory = get_strategy(cfg.strategy)
    kw = {"telemetry": gran}
    if cfg.strategy == "jax":
        kw.update({"wave_width": cfg.wave_width, "chunk_waves": cfg.chunk_waves,
                   "preemption": cfg.device_preemption,
                   "retry_buffer": cfg.whatif.retry_buffer,
                   "paged": cfg.paged_waves})
        if cfg.flight_recorder is not None:
            from .sim.flight import FlightRecorderConfig

            kw["flight_recorder"] = FlightRecorderConfig(
                path=cfg.flight_recorder.path,
                every=cfg.flight_recorder.every,
            )
    engine = factory(ec, ep, cfg.framework, **kw)
    events = None
    if cfg.chaos is not None and cfg.chaos.enabled:
        events = _chaos_timeline(cfg, ec, ep, cfg.chaos.seed)
        log.info("chaos: injecting %d node events", len(events))
    # The writer owns the output file for the whole command: a failing
    # replay still closes (and flushes) whatever was written.
    out_path = dcn.output_path_for_process(cfg.output)
    with JsonlWriter(out_path, context=_writer_context(cfg, args.config)) as out:
        with device_trace(args.profile_dir):
            res = engine.replay(node_events=events) if events else engine.replay()
        out.write(replay_row(f"replay-{cfg.strategy}", res, {"config": args.config}))
    if timeline_out and res.telemetry is not None:
        from .sim.telemetry import write_chrome_trace

        n_ev = write_chrome_trace(
            timeline_out, res, arrival=ep.arrival, duration=ep.duration,
            requests=ep.requests, rindex=ec.vocab._r,
        )
        log.info("timeline: wrote %d trace events to %s", n_ev, timeline_out)
    log.info(
        "placed %d/%d pods in %.3fs (%.0f placements/sec)",
        res.placed,
        res.placed + res.unschedulable,
        res.wall_clock_s,
        res.placements_per_sec,
    )
    return 0


def _drain_scenarios(cfg, ec, ep, scen) -> int:
    """``drain:`` (utils.config.DrainSpec): give every scenario s > 0 its
    walk of ``node_cordon`` events, merged into what ``chaos:`` gave it, and
    its ``DisruptionBudget``. Returns the cordons written."""
    import numpy as np

    from .sim.jax_runtime import wave_start_times
    from .sim.runtime import DisruptionBudget, NodeEvent
    from .sim.waves import pack_waves

    dr = cfg.drain
    if ep.app_id is None:
        raise ValueError(
            "drain: needs a workload that names each task's application "
            "(workload.borg); this one has none"
        )
    app = np.asarray(ep.app_id, np.int32)
    most = np.maximum(
        1, np.floor(dr.max_unavailable_share * np.bincount(app))
    ).astype(np.int32)
    starts = wave_start_times(ep, pack_waves(ep, cfg.wave_width).idx)[
        0 :: cfg.chunk_waves
    ]
    N, n_ev = ec.num_nodes, 0
    for s in range(1, len(scen)):
        place = int(np.random.default_rng(dr.seed + s).integers(0, N))
        walk = np.roll(np.arange(N), -place)
        cordons = [
            NodeEvent(float(starts[b]), "node_cordon", int(n))
            for b in range(dr.first, len(starts))
            for n in walk[(b - dr.first) * dr.step : (b - dr.first + 1) * dr.step]
        ]
        n_ev += len(cordons)
        scen[s].events = sorted(
            list(scen[s].events) + cordons, key=lambda e: e.time
        )
        scen[s].budget = DisruptionBudget(
            app, most, grace=dr.grace, out_for=dr.out_for
        )
    return n_ev


def cmd_whatif(args) -> int:
    from .parallel.mesh import make_mesh
    from .sim.whatif import WhatIfEngine, uniform_scenarios

    cfg = SimConfig.load(args.config)
    if cfg.whatif.scenarios <= 0:
        log.error("config has no whatIf.scenarios")
        return 2
    ec, ep = build_encoded_case(cfg)
    scen = uniform_scenarios(
        ec,
        cfg.whatif.scenarios,
        seed=cfg.whatif.seed,
        p_node_down=cfg.whatif.node_down_p,
        p_capacity=cfg.whatif.capacity_p,
        p_taint=cfg.whatif.taint_p,
    )
    if cfg.chaos is not None and cfg.chaos.enabled:
        # Failure-sweep campaign: scenario 0 stays the clean reference;
        # every other scenario gets its own seeded timeline so the batch
        # answers "which failure timeline hurts most" in one SPMD run.
        n_ev = 0
        for s in range(1, len(scen)):
            scen[s].events = _chaos_timeline(
                cfg, ec, ep, cfg.chaos.seed + s
            )
            n_ev += len(scen[s].events)
        log.info(
            "chaos: %d timed events across %d scenario timelines",
            n_ev, len(scen) - 1,
        )
    if cfg.drain is not None and cfg.drain.enabled:
        log.info(
            "drain: %d node_cordon events across %d scenario timelines, "
            "under disruption budgets",
            _drain_scenarios(cfg, ec, ep, scen), len(scen) - 1,
        )
    mesh = make_mesh() if cfg.whatif.mesh else None
    eng = WhatIfEngine(
        ec,
        ep,
        scen,
        cfg.framework,
        wave_width=cfg.wave_width,
        chunk_waves=cfg.chunk_waves,
        mesh=mesh,
        preemption=cfg.device_preemption,
        completions=cfg.whatif.completions,
        retry_buffer=cfg.whatif.retry_buffer,
        retry_groups=cfg.whatif.retry_groups,
        collect_assignments=cfg.whatif.placements,
        telemetry=cfg.telemetry.granularity,
    )
    # DCN: every process assembles the identical gathered result; each
    # writes its own sink (process 0 keeps the configured path, which is
    # the file the parity bar compares against a single-process run).
    out_path = dcn.output_path_for_process(cfg.output)
    with JsonlWriter(out_path, context=_writer_context(cfg, args.config)) as out:
        with device_trace(args.profile_dir):
            res = eng.run()
        for row in whatif_rows(res, {"config": args.config, "mesh": bool(mesh)}):
            out.write(row)
    log.info(
        "what-if: %d scenarios, %d placements in %.3fs (%.0f placements/sec aggregate)"
        + (f" across {res.process_count} processes" if res.process_count > 1 else ""),
        len(scen),
        res.total_placed,
        res.wall_clock_s,
        res.placements_per_sec,
    )
    return 0


def cmd_tune(args) -> int:
    from .parallel.mesh import make_mesh
    from .sim.tuner import PolicyTuner

    cfg = SimConfig.load(args.config)
    if cfg.tune is None:
        log.error("config has no tune: section")
        return 2
    errors = validate_config(cfg)
    if errors:
        for e in errors:
            log.error("config: %s", e)
        return 2
    tu = cfg.tune
    ec, ep = build_encoded_case(cfg)
    log.info("encoded %d nodes / %d pods", ec.num_nodes, ep.num_pods)
    mesh = make_mesh() if tu.mesh else None
    tuner = PolicyTuner(
        ec, ep, cfg.framework,
        algo=tu.algo, population=tu.population, rounds=tu.rounds,
        seed=tu.seed, elite_frac=tu.elite_frac, objective=tu.objective,
        constraints=tu.constraints, evaluator=tu.evaluator,
        train_scenarios=tu.train_scenarios,
        heldout_scenarios=tu.heldout_scenarios,
        scenario_seed=tu.scenario_seed,
        p_node_down=tu.node_down_p, p_capacity=tu.capacity_p,
        p_taint=tu.taint_p,
        weight_bounds=(
            tuple(tu.weight_bounds) if tu.weight_bounds else None
        ),
        tune_strategy=tu.tune_strategy,
        wave_width=8 if cfg.wave_width == "auto" else cfg.wave_width,
        chunk_waves=cfg.chunk_waves,
        completions=cfg.whatif.completions,
        mesh=mesh,
        cpu_oracle=tu.cpu_oracle, cpu_envelope=tu.cpu_envelope,
    )
    out_path = dcn.output_path_for_process(tu.output or cfg.output)
    with JsonlWriter(out_path, context=_writer_context(cfg, args.config)) as out:
        with device_trace(args.profile_dir):
            res = tuner.run(writer=out)
    log.info(
        "tune: %s over %d rounds x %d candidates (%d evaluations, "
        "%d compile%s) in %.3fs",
        tu.algo, res.rounds, res.population, res.evaluations,
        res.compile_count or 0, "" if res.compile_count == 1 else "s",
        res.wall_clock_s,
    )
    log.info(
        "tune: held-out objective %.6f vs default %.6f (%s); best policy %s",
        res.heldout_objective, res.default_heldout_objective,
        "improved" if res.improved() else "no improvement",
        res.best_policy,
    )
    if res.cpu_envelope is not None:
        log.info(
            "tune: CPU-oracle objective %.6f (envelope %.3g)",
            res.cpu_objective, res.cpu_envelope,
        )
    return 0


def cmd_serve(args) -> int:
    """Resident query service (round 22): read NDJSON what-if queries
    from ``service.input`` (a file or named pipe) or stdin, answer them
    through a pooled-engine :class:`~.sim.service.QueryService`, and
    stream schema-v7 ``query-result`` rows to the configured output."""
    from .sim.service import QueryService, serve_lines

    cfg = SimConfig.load(args.config)
    if args.strategy:
        cfg.strategy = args.strategy
    if cfg.service is None:
        log.error("config has no service: section")
        return 2
    errors = validate_config(cfg)
    if errors:
        for e in errors:
            log.error("config: %s", e)
        return 2
    sv = cfg.service
    ec, ep = build_encoded_case(cfg)
    log.info("encoded %d nodes / %d pods", ec.num_nodes, ep.num_pods)
    flight = None
    if cfg.flight_recorder is not None:
        from .sim.flight import FlightRecorder, FlightRecorderConfig

        flight = FlightRecorder(
            FlightRecorderConfig(
                path=cfg.flight_recorder.path,
                every=cfg.flight_recorder.every,
            ),
            meta={"mode": "serve"},
        )
    with JsonlWriter(
        cfg.output, context=_writer_context(cfg, args.config)
    ) as out:
        service = QueryService(
            ec, ep, cfg.framework,
            max_batch=sv.max_batch,
            batch_deadline_s=sv.batch_deadline_s,
            max_engines=sv.max_engines,
            granularity=sv.granularity,
            retry_buffer=sv.retry_buffer,
            writer=out,
            flight=flight,
            wave_width=8 if cfg.wave_width == "auto" else cfg.wave_width,
            chunk_waves=cfg.chunk_waves,
        )
        try:
            with device_trace(args.profile_dir):
                if sv.input is not None:
                    # A named pipe blocks here until a producer connects —
                    # that is the serving contract, not a hang.
                    with open(sv.input) as f:
                        stats = serve_lines(service, f, out)
                else:
                    stats = serve_lines(service, sys.stdin, out)
        finally:
            if flight is not None:
                flight.close()
    log.info(
        "serve: %d queries in %d batches (%d cold build%s, %d warm, "
        "%d error%s)",
        stats["queries"], stats["batches"],
        stats["cold_builds"], "" if stats["cold_builds"] == 1 else "s",
        stats["warm_hits"],
        stats["errors"], "" if stats["errors"] == 1 else "s",
    )
    return 0


def _recovery_errors(cfg) -> list:
    """Actionable refusals for the ``dcn.recovery`` section (round 15).
    Shared by validate_config and the pre-dispatch env export in main():
    enabling survivor recovery outside a DCN fleet, or with the liveness
    heartbeats its failure detector rides on disabled, must fail with a
    message naming the fix — not silently no-op."""
    rec = getattr(cfg, "dcn_recovery", None)
    if rec is None:
        return []
    errors = []
    if rec.checkpoint_every < 0:
        errors.append(
            "dcn.recovery.checkpointEvery: must be >= 0 (0 disables "
            "checkpoint publication; a claimed block then re-executes "
            "from chunk 0)"
        )
    if rec.max_claims < 1:
        errors.append(
            "dcn.recovery.maxClaims: must be >= 1 (each dead block "
            "needs at least one claim generation)"
        )
    if not rec.enable:
        return errors
    if int(os.environ.get("KSIM_DCN_NPROC", "1") or 1) <= 1:
        errors.append(
            "dcn.recovery.enable: survivor recovery needs a multi-process "
            "DCN fleet — launch through scripts/dcn_launch.py (--elastic N "
            "adds spare claimants); KSIM_DCN_NPROC is unset/1, so there is "
            "no sibling to claim a dead block"
        )
    if dcn.heartbeat_every() == 0:
        errors.append(
            "dcn.recovery.enable: recovery needs liveness heartbeats — "
            "remove KSIM_DCN_HEARTBEAT_EVERY=0 (stale beacons are the "
            "failure detector that opens claims)"
        )
    return errors


def _workqueue_errors(cfg) -> list:
    """Actionable refusals for the ``dcn.workQueue`` section (round 18).
    Shared by validate_config and the pre-dispatch env export in main():
    the queue outside a DCN fleet, speculation without the checkpoints
    it resumes from, or a nonsensical block size must fail with a
    message naming the fix — not silently no-op."""
    wq = getattr(cfg, "dcn_workqueue", None)
    if wq is None:
        return []
    errors = []
    if wq.block_size < 0:
        errors.append(
            "dcn.workQueue.blockSize: must be >= 0 scenarios per block "
            "(0 = auto: one block per worker, reproducing the static "
            "partition when nobody steals)"
        )
    if wq.straggler_s < 0:
        errors.append(
            "dcn.workQueue.stragglerS: must be >= 0 seconds (0 = auto: "
            "half the KSIM_DCN_STALL_S lease-expiry window)"
        )
    if not wq.enable:
        if wq.speculate or wq.block_size or wq.straggler_s:
            log.warning(
                "dcn.workQueue: speculate/blockSize/stragglerS set but "
                "enable is false — the work queue stays off"
            )
        return errors
    if int(os.environ.get("KSIM_DCN_NPROC", "1") or 1) <= 1:
        errors.append(
            "dcn.workQueue.enable: the work-stealing queue needs a "
            "multi-process DCN fleet — launch through "
            "scripts/dcn_launch.py; KSIM_DCN_NPROC is unset/1, so there "
            "is nobody to lease blocks from the queue"
        )
    if dcn.heartbeat_every() == 0:
        errors.append(
            "dcn.workQueue.enable: the queue needs liveness heartbeats — "
            "remove KSIM_DCN_HEARTBEAT_EVERY=0 (lease renewals ride the "
            "heartbeat cadence; without them every lease looks expired)"
        )
    if wq.speculate:
        rec = getattr(cfg, "dcn_recovery", None)
        if rec is None or rec.checkpoint_every < 1:
            errors.append(
                "dcn.workQueue.speculate: speculative re-execution "
                "resumes from the straggler's newest published "
                "checkpoint — set dcn.recovery.checkpointEvery >= 1 "
                "(without checkpoints a backup re-executes the whole "
                "block and rarely beats the straggler)"
            )
    return errors


def _faultline_errors(cfg) -> list:
    """Actionable refusals for the ``faultline:`` section (round 17).
    Shared by validate_config and the pre-dispatch env export in main().
    Negative rates/seeds and malformed kill schedules are refused;
    injection with recovery disabled is LEGAL but warned — every injected
    kill or retry give-up then fails the fleet attributed instead of
    recovering, which is occasionally what a drill wants."""
    fl = getattr(cfg, "faultline", None)
    if fl is None:
        return []
    errors = []
    if fl.seed < 0:
        errors.append(
            "faultline.seed: must be >= 0 (the seed derives every "
            "per-class injection stream)"
        )
    for attr, yaml_key in (
        ("kv_error_rate", "kvErrorRate"),
        ("kv_delay_rate", "kvDelayRate"),
        ("torn_write_rate", "tornWriteRate"),
        ("stale_read_rate", "staleReadRate"),
    ):
        rate = getattr(fl, attr)
        if not (0.0 <= rate <= 1.0):
            errors.append(
                f"faultline.{yaml_key}: must be in [0, 1], got {rate!r} "
                "(a per-operation injection probability)"
            )
    if fl.kv_delay_s < 0:
        errors.append("faultline.kvDelayS: must be >= 0 seconds")
    if fl.kill:
        from .parallel import faultline as _faultline

        try:
            _faultline.parse_kill_schedule(str(fl.kill))
        except ValueError as e:
            errors.append(f"faultline.kill: {e}")
    if getattr(fl, "slow", None):
        from .parallel import faultline as _faultline

        try:
            _faultline.parse_slow_schedule(str(fl.slow))
        except ValueError as e:
            errors.append(f"faultline.slow: {e}")
    if not fl.enabled:
        return errors
    rec = getattr(cfg, "dcn_recovery", None)
    if rec is None or not rec.enable:
        log.warning(
            "faultline: injection enabled with dcn.recovery disabled — "
            "injected kills and retry give-ups will fail the fleet with "
            "an attributed error instead of recovering (set "
            "dcn.recovery.enable to drill the recovery path)"
        )
    return errors


def _overlap_errors(cfg) -> list:
    """Actionable refusals for the ``overlap:`` section (round 19).
    Shared by validate_config and the pre-dispatch env export in main().
    A gate explicitly enabled on a config that lacks the machinery it
    overlaps is refused — silently accepting it would report perfect
    hidden wall for work that never existed."""
    ov = getattr(cfg, "overlap", None)
    if ov is None:
        return []
    errors = []
    if ov.pager_thread and not getattr(cfg, "paged_waves", False):
        errors.append(
            "overlap.pagerThread: true requires pagedWaves: true — "
            "without paged pod waves there is no pager (and no page "
            "fetch) to move off the chunk-loop thread"
        )
    if ov.background_publisher:
        rec = getattr(cfg, "dcn_recovery", None)
        wq = getattr(cfg, "dcn_workqueue", None)
        has_ckpt = (
            rec is not None and rec.enable and rec.checkpoint_every >= 1
        ) or (wq is not None and wq.enable)
        if not has_ckpt:
            errors.append(
                "overlap.backgroundPublisher: true requires a checkpoint "
                "cadence — enable dcn.recovery with checkpointEvery >= 1 "
                "(or dcn.workQueue) so there are publications to move "
                "off the loop thread"
            )
    return errors


def _durable_errors(cfg) -> list:
    """Actionable refusals for the ``dcn.durable`` section (round 20).
    Shared by validate_config and the pre-dispatch env export in main().
    A durability journal outside a DCN fleet, or on a config with no
    checkpoint cadence at all, is refused — the journal would sit empty
    while claiming crash-restart coverage; an unwritable journal
    directory is refused up front rather than discovered at the first
    mirrored publication."""
    du = getattr(cfg, "dcn_durable", None)
    if du is None:
        return []
    errors = []
    if not du.dir:
        if du.resume:
            errors.append(
                "dcn.durable.resume: true requires dcn.durable.dir — "
                "there is no journal to seed the fleet from"
            )
        return errors
    if int(os.environ.get("KSIM_DCN_NPROC", "1") or 1) <= 1:
        errors.append(
            "dcn.durable.dir: the durability journal mirrors a DCN "
            "fleet's checkpoint/queue publications — launch through "
            "scripts/dcn_launch.py (ideally --supervise); "
            "KSIM_DCN_NPROC is unset/1, so there is no fleet state to "
            "make durable"
        )
    rec = getattr(cfg, "dcn_recovery", None)
    wq = getattr(cfg, "dcn_workqueue", None)
    has_ckpt = (
        rec is not None and rec.enable and rec.checkpoint_every >= 1
    ) or (wq is not None and wq.enable)
    if not has_ckpt:
        errors.append(
            "dcn.durable.dir: the journal rides checkpoint/queue "
            "publication — enable dcn.recovery with checkpointEvery >= 1 "
            "(or dcn.workQueue) so there is something durable to mirror"
        )
    try:
        os.makedirs(du.dir, exist_ok=True)
        probe = os.path.join(du.dir, f".ksim_probe.{os.getpid()}")
        with open(probe, "w") as f:
            f.write("ok")
        os.remove(probe)
    except OSError as e:
        errors.append(
            f"dcn.durable.dir: {du.dir!r} is not writable ({e}) — the "
            "journal must outlive the fleet, so it is created eagerly"
        )
    return errors


def _service_errors(cfg) -> list:
    """Actionable refusals for the ``service:`` section (round 22). The
    resident query service swaps scenario values against ONE compiled
    executable per pool engine, so every envelope the defrag family
    rides on — the kube boundary mirror, single-process planes — must
    hold before the first query is admitted, not fail mid-batch."""
    sv = getattr(cfg, "service", None)
    if sv is None:
        return []
    errors = []
    if cfg.strategy != "jax":
        errors.append(
            "service: requires strategy: jax (the resident engine pool "
            "is the compiled what-if plane)"
        )
    if cfg.device_preemption != "kube":
        errors.append(
            "service: defrag queries drain nodes through chaos eviction, "
            "which needs devicePreemption: kube (the boundary host "
            "mirror applies per-scenario timelines)"
        )
    if not cfg.whatif.retry_buffer:
        errors.append(
            "service: requires whatIf.retryBuffer > 0 — without the "
            "boundary retry pass a drained node's pods are never "
            "rescheduled, so every defrag answer degenerates"
        )
    if cfg.whatif.mesh:
        errors.append(
            "service: whatIf.mesh is not supported (resident engines "
            "are single-process; set_scenarios refuses meshed engines)"
        )
    if sv.max_batch < 1:
        errors.append("service.maxBatch: must be >= 1")
    if sv.batch_deadline_s <= 0:
        errors.append(
            "service.batchDeadlineS: must be > 0 (the admission queue "
            "needs a flush deadline; use maxBatch: 1 for per-query "
            "dispatch)"
        )
    if sv.max_engines < 1:
        errors.append("service.maxEngines: must be >= 1")
    if sv.retry_buffer < 1:
        errors.append("service.retryBuffer: must be >= 1")
    from .sim.telemetry import _LEVELS as _tel_levels

    if sv.granularity not in _tel_levels:
        errors.append(
            f"service.granularity: must be one of "
            f"{', '.join(_tel_levels)}, got {sv.granularity!r}"
        )
    if sv.input is not None and not os.path.exists(sv.input):
        errors.append(f"service.input: file not found: {sv.input}")
    return errors


def _wide_gang_errors(cfg, key: str, widest: int, ww: int, **on) -> list:
    """What the device engines refuse of a gang wider than the wave
    (``sim.waves.refuse_wide_gangs``, the one list), refused at ``validate``;
    the CPU event engine takes a gang of any size."""
    from .sim.waves import refuse_wide_gangs

    if cfg.strategy == "cpu":
        return []
    pre = cfg.device_preemption
    try:
        refuse_wide_gangs(
            ww, widest, retry_groups=cfg.whatif.retry_groups,
            retry_buffer=bool(cfg.whatif.retry_buffer),
            kube_preemption=pre == "kube", tier_preemption=pre in (True, "tier"),
            **on,
        )
    except ValueError as e:
        return [f"{key}: {e}"]
    return []


def validate_config(cfg) -> list:
    """Structural checks → list of actionable error strings (empty = ok)."""
    from .framework.registry import available_strategies
    from .plugins.builtin import PLUGIN_FACTORIES

    errors = []
    # Built-ins register lazily — make them visible before consulting the
    # registry (the L6 contract: validate agrees with get_strategy).
    from .sim import runtime as _rt  # noqa: F401
    try:
        from .sim import jax_runtime as _jrt  # noqa: F401
    except Exception:
        pass
    known_strategies = available_strategies()
    if cfg.strategy not in known_strategies:
        errors.append(
            f"strategy: unknown '{cfg.strategy}' "
            f"(registered: {', '.join(known_strategies)})"
        )
    ww = 8 if cfg.wave_width == "auto" else cfg.wave_width
    for e in cfg.framework.plugins or []:
        if not isinstance(e, dict) or "name" not in e:
            errors.append(
                f"profile.plugins: entry must be a mapping with name:, got {e!r}"
            )
            continue
        name = e.get("name")
        if name not in PLUGIN_FACTORIES:
            errors.append(
                f"profile.plugins: unknown plugin '{name}' "
                f"(known: {', '.join(sorted(PLUGIN_FACTORIES))})"
            )
    known = set(PLUGIN_FACTORIES)
    for name, w in (cfg.framework.weights or {}).items():
        if name not in known:
            errors.append(f"profile.weights: unknown plugin '{name}'")
        elif not isinstance(w, (int, float)) or w < 0:
            errors.append(f"profile.weights.{name}: must be a number >= 0")
    if cfg.borg is not None:
        if cfg.borg.nodes <= 0:
            errors.append("workload.borg.nodes: must be > 0")
        if cfg.borg.tasks <= 0:
            errors.append("workload.borg.tasks: must be > 0")
        # A Borg trace has durations, and releases know nothing of the open
        # transaction of a gang wider than the wave.
        errors += _wide_gang_errors(
            cfg, "workload.borg.maxGang", cfg.borg.max_gang, ww, completions=True
        )
        for p_attr, key in (
            ("trace_path", "tracePath"),
            ("instance_events", "instanceEvents"),
            ("collection_events", "collectionEvents"),
        ):
            p = getattr(cfg.borg, p_attr, None)
            if p and not os.path.exists(p):
                errors.append(f"workload.borg.{key}: file not found: {p}")
        if cfg.borg.cpu_scale <= 0 or cfg.borg.mem_scale <= 0:
            errors.append("workload.borg.cpuScale/memScale: must be > 0")
        if cfg.borg.tasks_per_day < 0:
            errors.append("workload.borg.tasksPerDay: must be >= 0")
        fill, band = cfg.borg.resident_fill, cfg.borg.resident_band
        if fill < 0 or fill + band > 1.0 or band < 0 or (fill and fill <= band):
            errors.append(
                "workload.borg.residentFill / residentBand: the fill is a share "
                "of each node's cpu, drawn in fill +- band inside (0, 1]"
            )
        if fill and (cfg.borg.trace_path or cfg.borg.instance_events):
            errors.append(
                "workload.borg.residentFill: a trace file brings its own "
                "resident set (a bound_node column); the generator's is for "
                "the sampled workload"
            )
    else:
        if cfg.cluster.nodes <= 0:
            errors.append("cluster.nodes: must be > 0")
        wl = cfg.workload
        if wl is not None:
            if wl.pods <= 0:
                errors.append("workload.pods: must be > 0")
            sizes = wl.gang_sizes or {}
            bad = [
                k for k, v in sizes.items()
                if not (isinstance(k, int) and k >= 1
                        and isinstance(v, (int, float)) and v >= 0)
            ]
            if bad or (wl.gang_sizes is not None and not sum(sizes.values())):
                errors.append(
                    "workload.gangSizes: a mapping {workers: share} of whole "
                    f"worker counts >= 1 to shares >= 0, not all 0 (bad: {bad})"
                )
            if wl.job_extended_resource is not None:
                jx = wl.job_extended_resource
                missing = [
                    k for k in ("resource", "counts", "wideFrom",
                                "smallJobFraction", "wideJobFraction")
                    if not isinstance(jx, dict) or k not in jx
                ]
                if missing:
                    errors.append(
                        f"workload.jobExtendedResource: missing {missing}"
                    )
                if not wl.gang_sizes:
                    errors.append(
                        "workload.jobExtendedResource is read with "
                        "workload.gangSizes only"
                    )
            widest = max(
                [k for k, v in sizes.items() if isinstance(k, int) and v]
                or [wl.gang_size if wl.gang_fraction else 1]
            )
            errors += _wide_gang_errors(
                cfg, "a gang of workload.gangSizes / gangSize", widest, ww,
                completions=(wl.duration_mean is not None
                             or bool(wl.job_durations)),
                count_planes=bool(wl.affinity or wl.spread),
            )
    if cfg.whatif.scenarios < 0:
        errors.append("whatIf.scenarios: must be >= 0")
    if cfg.whatif.retry_buffer < 0:
        errors.append("whatIf.retryBuffer: must be >= 0")
    if cfg.whatif.retry_groups and not (
        cfg.whatif.retry_buffer and cfg.whatif.scenarios
        and cfg.strategy == "jax" and not cfg.device_preemption
    ):
        errors.append(
            "whatIf.retryGroups: a queue of whole jobs runs in a what-if "
            "batch on the device retry path: needs strategy: jax, "
            "whatIf.scenarios > 0, whatIf.retryBuffer > 0 and no "
            "devicePreemption"
        )
    if cfg.device_preemption not in (True, False, "tier", "kube"):
        errors.append(
            f"devicePreemption: must be true/false/'tier'/'kube', got "
            f"{cfg.device_preemption!r}"
        )
    tier_on = cfg.device_preemption in (True, "tier")
    if cfg.whatif.retry_buffer and tier_on:
        errors.append(
            "whatIf.retryBuffer is not supported with tier devicePreemption"
        )
    if cfg.device_preemption == "kube" and not cfg.whatif.retry_buffer:
        errors.append(
            "devicePreemption: kube requires whatIf.retryBuffer > 0 "
            "(failed pods reach the PostFilter through the boundary "
            "retry pass)"
        )
    if cfg.device_preemption == "kube" and cfg.whatif.mesh:
        errors.append(
            "devicePreemption: kube requires a no-mesh what-if batch "
            "(the eager per-chunk folds would serialize the scenario "
            "axis); tier preemption runs under a mesh"
        )
    if cfg.whatif.placements and cfg.whatif.scenarios <= 0:
        errors.append("whatIf.placements: needs whatIf.scenarios > 0")
    if cfg.whatif.retry_buffer and cfg.whatif.completions is False:
        errors.append(
            "whatIf.retryBuffer requires the device-release path; remove "
            "whatIf.completions: false (the retry pass runs at completion "
            "boundaries)"
        )
    if cfg.paged_waves:
        if cfg.strategy != "jax":
            errors.append("pagedWaves: requires strategy: jax")
        if cfg.whatif.retry_buffer or cfg.device_preemption == "kube":
            errors.append(
                "pagedWaves is not supported with whatIf.retryBuffer / "
                "devicePreemption: kube yet (the boundary mirror "
                "pre-stages the whole wave index tensor)"
            )
    ch = cfg.chaos
    if ch is not None and ch.enabled:
        if ch.mtbf <= 0:
            errors.append("chaos.mtbf: must be > 0")
        if ch.mttr < 0:
            errors.append("chaos.mttr: must be >= 0")
        if not 0.0 < ch.node_fraction <= 1.0:
            errors.append("chaos.nodeFraction: must be in (0, 1]")
        if ch.horizon is not None and ch.horizon <= 0:
            errors.append("chaos.horizon: must be > 0 (or omitted)")
        if ch.max_events is not None and ch.max_events < 0:
            errors.append("chaos.maxEvents: must be >= 0")
        if cfg.strategy == "jax" and not cfg.whatif.retry_buffer:
            errors.append(
                "chaos with strategy: jax requires whatIf.retryBuffer > 0 "
                "— without the boundary retry pass node_down only blocks "
                "future placements (no NoExecute eviction of bound pods)"
            )
        if (
            cfg.whatif.scenarios > 0
            and cfg.device_preemption != "kube"
            and (cfg.whatif.mesh or cfg.whatif.completions is False)
        ):
            # With whatIf.retryBuffer (checked above) the timelines run on
            # the device retry path: the eviction program, no host mirror.
            errors.append(
                "chaos what-if sweeps without devicePreemption: kube run "
                "on the device retry path, which takes no mesh and needs "
                "completions (whatIf.mesh: false, whatIf.completions not "
                "false); with devicePreemption: kube the timelines apply "
                "through the per-scenario host mirrors"
            )
    dr = cfg.drain
    if dr is not None and dr.enabled:
        if dr.step <= 0 or dr.first < 0:
            errors.append("drain.step: must be > 0 and drain.first >= 0")
        if dr.grace < 0 or dr.out_for < 1:
            errors.append("drain.grace: must be >= 0 and drain.outFor >= 1")
        if not 0.0 < dr.max_unavailable_share <= 1.0:
            errors.append("drain.maxUnavailableShare: must be in (0, 1]")
        if cfg.whatif.scenarios <= 0:
            errors.append("drain: is a what-if section (whatIf.scenarios > 0)")
        if (
            not cfg.whatif.retry_buffer
            or cfg.device_preemption == "kube"
            or cfg.whatif.mesh
            or cfg.whatif.completions is False
        ):
            errors.append(
                "drain: node_cordon events and disruption budgets run on the "
                "device retry path: whatIf.retryBuffer > 0, no "
                "devicePreemption: kube, whatIf.mesh: false, "
                "whatIf.completions not false"
            )
        if cfg.borg is None:
            errors.append(
                "drain: needs a workload that names each task's application "
                "(workload.borg)"
            )
    tu = cfg.tune
    if tu is not None:
        from .sim.tuner import (
            _ALWAYS_METRICS, _RESULT_METRICS, normalize_constraints,
        )

        if tu.algo not in ("cem", "random"):
            errors.append(
                f"tune.algo: must be 'cem' or 'random', got {tu.algo!r}"
            )
        if tu.population < 2:
            errors.append("tune.population: must be >= 2")
        if tu.rounds < 1:
            errors.append("tune.rounds: must be >= 1")
        if not 0.0 < tu.elite_frac <= 1.0:
            errors.append("tune.eliteFrac: must be in (0, 1]")
        if tu.train_scenarios < 1 or tu.heldout_scenarios < 1:
            errors.append(
                "tune.scenarios: train and heldout must both be >= 1 "
                "(the acceptance check runs on the held-out split)"
            )
        if tu.evaluator not in ("auto", "device", "cpu"):
            errors.append(
                f"tune.evaluator: must be 'auto', 'device' or 'cpu', "
                f"got {tu.evaluator!r}"
            )
        try:
            cons = normalize_constraints(tu.constraints)
        except ValueError as e:
            errors.append(f"tune.constraints: {e}")
            cons = []
        terms = list(tu.objective or {}) + [c["metric"] for c in cons]
        for term in terms:
            if term not in _RESULT_METRICS:
                errors.append(
                    f"tune.objective: unknown term '{term}' "
                    f"(known: {', '.join(sorted(_RESULT_METRICS))})"
                )
            elif term not in _ALWAYS_METRICS and tu.evaluator == "device":
                # auto/cpu route such terms to the CPU event engine
                # (round 13); only an EXPLICIT device evaluator is stuck
                # with the batched-sweep metric set.
                errors.append(
                    f"tune.objective: term '{term}' rides the kube host "
                    "mirrors, which the batched policy sweep does not "
                    "support — drop 'evaluator: device' or use terms "
                    f"from {', '.join(sorted(_ALWAYS_METRICS))}"
                )
        wb = tu.weight_bounds
        if wb is not None and (len(wb) != 2 or wb[0] >= wb[1]):
            errors.append(
                "tune.weightBounds: must be [lo, hi] with lo < hi"
            )
        if tu.cpu_envelope < 0:
            errors.append("tune.cpuEnvelope: must be >= 0")
    from .sim.telemetry import _LEVELS as _TEL_LEVELS

    if cfg.telemetry.granularity not in _TEL_LEVELS:
        errors.append(
            f"telemetry.granularity: must be one of "
            f"{', '.join(_TEL_LEVELS)}, got {cfg.telemetry.granularity!r}"
        )
    if cfg.telemetry.timeline_out:
        d = os.path.dirname(cfg.telemetry.timeline_out) or "."
        if not os.path.isdir(d):
            errors.append(
                f"telemetry.timelineOut: directory not found: {d}"
            )
    if cfg.chunk_waves <= 0:
        errors.append("chunkWaves: must be > 0")
    if cfg.wave_width != "auto" and cfg.wave_width <= 0:
        errors.append("waveWidth: must be > 0 (or 'auto')")
    if cfg.device_preemption and cfg.strategy == "cpu":
        errors.append(
            "devicePreemption requires strategy: jax (the cpu engine runs "
            "kube PostFilter preemption instead)"
        )
    if cfg.flight_recorder is not None:
        fr = cfg.flight_recorder
        if cfg.strategy != "jax":
            errors.append(
                "flightRecorder requires strategy: jax (the cpu engine "
                "has no chunk loop to record)"
            )
        d = os.path.dirname(fr.path) or "."
        if not os.path.isdir(d):
            errors.append(
                f"flightRecorder.path: directory not found: {d}"
            )
        elif not os.access(d, os.W_OK):
            errors.append(
                f"flightRecorder.path: directory not writable: {d}"
            )
        if fr.every <= 0:
            errors.append("flightRecorder.every: must be > 0")
    errors.extend(_recovery_errors(cfg))
    errors.extend(_workqueue_errors(cfg))
    errors.extend(_faultline_errors(cfg))
    errors.extend(_overlap_errors(cfg))
    errors.extend(_durable_errors(cfg))
    errors.extend(_service_errors(cfg))
    return errors


def cmd_validate(args) -> int:
    try:
        cfg = SimConfig.load(args.config)
    except ValueError as e:
        # Parse-time schema errors (e.g. non-bool whatIf.completions)
        # still come out as the JSON error report, not a traceback.
        print(json.dumps({"errors": [str(e)]}, indent=2))
        return 1
    errors = validate_config(cfg)
    nodes = cfg.borg.nodes if cfg.borg else cfg.cluster.nodes
    tasks = (
        cfg.borg.tasks if cfg.borg
        else (cfg.workload.pods if cfg.workload else 1000)
    )
    print(json.dumps({"strategy": cfg.strategy, "nodes": nodes, "tasks": tasks,
                      "workload": "borg" if cfg.borg else "synthetic",
                      "devicePreemption": cfg.device_preemption,
                      "whatif_scenarios": cfg.whatif.scenarios,
                      "errors": errors}, indent=2))
    return 1 if errors else 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="kubernetes_simulator_tpu")
    sub = ap.add_subparsers(dest="cmd", required=True)
    for name, fn in (("run", cmd_run), ("what-if", cmd_whatif),
                     ("tune", cmd_tune), ("serve", cmd_serve),
                     ("validate", cmd_validate)):
        p = sub.add_parser(name)
        p.add_argument("config")
        p.add_argument("--strategy", choices=["cpu", "jax"])
        p.add_argument("--profile-dir", default=None,
                       help="jax.profiler trace output dir; arms the "
                       "program's host spans (KSIM_PROFILE_DIR) for the run")
        if name == "run":
            p.add_argument(
                "--timeline-out", default=None,
                help="write the simulated cluster timeline as a Chrome "
                     "trace JSON (Perfetto-loadable); implies telemetry "
                     "granularity 'timeline'",
            )
        p.set_defaults(fn=fn)
    args = ap.parse_args(argv)
    # Config-driven recovery knobs (round 15, dcn.recovery:) must land in
    # the env BEFORE jax.distributed bring-up — the coordination-service
    # failure-detector widening reads KSIM_DCN_RECOVER at initialize.
    # setdefault: an operator's explicit env always wins over the YAML.
    if args.cmd != "validate":
        try:
            cfg_pre = SimConfig.load(args.config)
        except Exception:
            cfg_pre = None  # the command fn reports config errors itself
        rec = cfg_pre.dcn_recovery if cfg_pre is not None else None
        if rec is not None and rec.enable:
            errors = _recovery_errors(cfg_pre)
            if errors:
                for e in errors:
                    log.error("config: %s", e)
                return 2
            os.environ.setdefault("KSIM_DCN_RECOVER", "1")
            if rec.checkpoint_every:
                os.environ.setdefault(
                    "KSIM_DCN_CKPT_EVERY", str(rec.checkpoint_every)
                )
            os.environ.setdefault(
                "KSIM_DCN_MAX_CLAIMS", str(rec.max_claims)
            )
        # Work-queue knobs (round 18, dcn.workQueue:) must also land
        # before bring-up — mesh.init_distributed widens the runtime
        # failure detector when the queue is on (a straggler must not be
        # declared dead while a backup races it).
        wq = (
            getattr(cfg_pre, "dcn_workqueue", None)
            if cfg_pre is not None
            else None
        )
        if wq is not None and wq.enable:
            errors = _workqueue_errors(cfg_pre)
            if errors:
                for e in errors:
                    log.error("config: %s", e)
                return 2
            os.environ.setdefault("KSIM_DCN_WORKQUEUE", "1")
            if wq.block_size:
                os.environ.setdefault(
                    "KSIM_DCN_WQ_BLOCK", str(wq.block_size)
                )
            if wq.speculate:
                os.environ.setdefault("KSIM_DCN_SPECULATE", "1")
            if wq.straggler_s:
                os.environ.setdefault(
                    "KSIM_DCN_STRAGGLER_S", str(wq.straggler_s)
                )
        # Faultline injection knobs (round 17, faultline:) ride the same
        # pre-dispatch export — the KV-client wrapper reads KSIM_FAULTLINE_*
        # lazily, but a consistent fleet wants them pinned before any
        # worker touches the coordination plane.
        fl = cfg_pre.faultline if cfg_pre is not None else None
        if fl is not None and fl.enabled:
            errors = _faultline_errors(cfg_pre)
            if errors:
                for e in errors:
                    log.error("config: %s", e)
                return 2
            os.environ.setdefault("KSIM_FAULTLINE", "1")
            os.environ.setdefault("KSIM_FAULTLINE_SEED", str(fl.seed))
            for val, env in (
                (fl.kv_error_rate, "KSIM_FAULTLINE_KV_ERROR_RATE"),
                (fl.kv_delay_rate, "KSIM_FAULTLINE_KV_DELAY_RATE"),
                (fl.kv_delay_s, "KSIM_FAULTLINE_KV_DELAY_S"),
                (fl.torn_write_rate, "KSIM_FAULTLINE_TORN_RATE"),
                (fl.stale_read_rate, "KSIM_FAULTLINE_STALE_RATE"),
            ):
                if val:
                    os.environ.setdefault(env, str(val))
            if fl.kill:
                os.environ.setdefault("KSIM_FAULTLINE_KILL", str(fl.kill))
            if getattr(fl, "slow", None):
                os.environ.setdefault("KSIM_FAULTLINE_SLOW", str(fl.slow))
        # Overlap gates (round 19, overlap:) ride the same pre-dispatch
        # export. Engines default every gate ON, so only explicit values
        # are exported — a None field stays the engine default, and an
        # operator's explicit env still wins (setdefault).
        ov = getattr(cfg_pre, "overlap", None) if cfg_pre is not None else None
        if ov is not None:
            errors = _overlap_errors(cfg_pre)
            if errors:
                for e in errors:
                    log.error("config: %s", e)
                return 2
            for val, env in (
                (ov.pager_thread, "KSIM_PAGER_THREAD"),
                (ov.background_publisher, "KSIM_DCN_CKPT_ASYNC"),
            ):
                if val is not None:
                    os.environ.setdefault(env, "1" if val else "0")
        # Durable-ground knobs (round 20, dcn.durable:) ride the same
        # pre-dispatch export — resume seeding happens during the first
        # replay's bring-up, so the journal path must be pinned before
        # any engine touches the coordination plane.
        du = (
            getattr(cfg_pre, "dcn_durable", None)
            if cfg_pre is not None
            else None
        )
        if du is not None and (du.dir or du.resume):
            errors = _durable_errors(cfg_pre)
            if errors:
                for e in errors:
                    log.error("config: %s", e)
                return 2
            os.environ.setdefault("KSIM_DCN_DURABLE_DIR", str(du.dir))
            if du.resume:
                os.environ.setdefault("KSIM_DCN_RESUME", "1")
    # Persistent compile cache for every command that compiles, single
    # process or fleet — BEFORE jax.distributed.initialize (documented
    # ordering). None means off (CPU backend / KSIM_COMPILE_CACHE=0).
    if args.cmd != "validate":
        log.info("compile cache: %s", compile_cache.enable())
    # Multi-host DCN bring-up (round 11): a no-op without the
    # KSIM_DCN_* env set by scripts/dcn_launch.py.
    if dcn.maybe_init_from_env():
        nproc, pid = dcn.process_info()
        log.info("DCN: process %d/%d up", pid, nproc)
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
