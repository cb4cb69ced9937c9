"""YAML configuration (SURVEY.md §5 config/flag system).

One schema with the upstream ``KubeSchedulerConfiguration`` vocabulary
(profiles → plugins → args, per-plugin Score weights) plus simulator
sections (cluster, workload, what-if, strategy). ``strategy`` selects the
backend through the L6 registry — ``cpu`` is the default path, ``jax`` the
TPU backend ([BASELINE] requirement).

Example::

    strategy: jax
    cluster:
      synthetic: {nodes: 5000, seed: 0, taintFraction: 0.1}
    workload:
      synthetic: {pods: 50000, seed: 0, affinity: true, spread: true,
                  tolerations: true, gangFraction: 0.02, gangSize: 4}
    profile:
      plugins:
        - name: NodeResourcesFit
          args: {strategy: LeastAllocated, resources: {cpu: 1, memory: 1}}
        - name: TaintToleration
        - name: NodeAffinity
        - name: InterPodAffinity
        - name: PodTopologySpread
      weights: {NodeResourcesFit: 1, TaintToleration: 3}
    whatIf:
      scenarios: 256
      seed: 0
      mesh: true
    output: results.jsonl
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import yaml

from ..framework.framework import FrameworkConfig


@dataclass
class SyntheticClusterSpec:
    nodes: int = 100
    seed: int = 0
    taint_fraction: float = 0.0
    zones: int = 8
    extended_resources: Optional[Dict[str, Any]] = None


@dataclass
class SyntheticWorkloadSpec:
    pods: int = 1000
    seed: int = 0
    affinity: bool = False
    spread: bool = False
    tolerations: bool = False
    gang_fraction: float = 0.0
    gang_size: int = 4
    arrival_rate: float = 100.0
    duration_mean: Optional[float] = None
    num_apps: int = 20
    # [resource, count, fraction]: that fraction of the pods asks for 1, 2
    # or ``count`` of the extended resource (``make_workload``).
    extended_resource: Optional[list] = None
    # A job-size mix ``{workers: share}``: the trace is then made job by job
    # (``make_job_workload``), a job of two or more workers a pod group of
    # its size, and ``gangFraction`` / ``gangSize`` are not read.
    gang_sizes: Optional[Dict[int, float]] = None
    # ``{resource, counts: {count: share}, wideFrom, smallJobFraction,
    # wideJobFraction}``: which jobs ask for an extended resource, all their
    # workers the same count (with ``gangSizes`` only).
    job_extended_resource: Optional[Dict[str, Any]] = None
    # ``{median, mean[, diurnal]}`` seconds: a job is ONE arrival (its
    # members share arrival time, priority and a log-normal duration, one
    # draw a job): what ``whatIf.retryGroups`` wants (with ``gangSizes``).
    job_durations: Optional[Dict[str, float]] = None


@dataclass
class BorgWorkloadSpec:
    nodes: int = 10_000
    tasks: int = 1_000_000
    seed: int = 0
    gang_fraction: float = 0.08
    max_gang: int = 8
    num_apps: int = 48  # template/app vocabulary (clip bound for app_id)
    trace_path: Optional[str] = None  # external task-event CSV (sim.borg)
    # Real Borg-2019 schema ingest (sim.borg_etl): instance_events CSV
    # (required for the ETL path) + optional collection_events fallback.
    instance_events: Optional[str] = None
    collection_events: Optional[str] = None
    cpu_scale: float = 8.0
    mem_scale: float = 16.0 * 2**30
    # A cell that is full (sim.borg.BorgSpec): ``tasksPerDay`` cuts the tasks
    # as a window out of a day of that many (0: the day thinned to ``tasks``);
    # ``residentFill`` binds a resident set before the window starts, up to
    # that share (+- ``residentBand``) of each node's cpu (0: none).
    tasks_per_day: float = 0.0
    resident_fill: float = 0.0
    resident_band: float = 0.05


@dataclass
class WhatIfSpec:
    scenarios: int = 0
    seed: int = 0
    mesh: bool = False
    node_down_p: float = 0.02
    capacity_p: float = 0.3
    taint_p: float = 0.1
    # None = default-on completions (warn when unhonorable); True/False
    # are the explicit forms (sim.whatif.WhatIfEngine docstring).
    completions: object = None
    # Device-path unschedulable retry buffer width (0 = off).
    retry_buffer: int = 0
    # The queue holds whole JOBS (pod groups): a rolled-back group joins
    # whole and is tried again whole at every boundary; a group wider than
    # the wave then runs with completions and the buffer
    # (``WhatIfEngine(retry_groups=True)``; semantics, like ``retryBuffer``).
    retry_groups: bool = False
    # Hand every task's node back (WhatIfEngine collect_assignments); with
    # ``retryBuffer`` on the device path also the boundary that bound it.
    # The scenario rows then count the re-tried binds and the tasks still
    # queued or dropped.
    placements: bool = False


@dataclass
class ChaosSpec:
    """Seeded chaos campaign (``chaos:`` YAML section): MTBF/MTTR-style
    failure injection. ``cmd_run`` turns this into a single
    ``node_events`` timeline; ``cmd_whatif`` gives each scenario s > 0 its
    own ``seed + s`` timeline (scenario 0 stays the clean reference)."""

    enabled: bool = False
    seed: int = 0
    mtbf: float = 200.0
    mttr: float = 20.0
    node_fraction: float = 0.2
    horizon: Optional[float] = None  # None → workload makespan
    max_events: Optional[int] = None


@dataclass
class DrainSpec:
    """A maintenance drain under disruption budgets (``drain:`` YAML section,
    what-if only): scenario 0 stays the clean cell; every other scenario
    cordons ``step`` nodes a chunk boundary from boundary ``first`` on, in
    node index order from a place drawn from ``seed + s``
    (``node_cordon`` events), under a ``sim.runtime.DisruptionBudget``: an
    application (the workload's ``app_id``: Borg workloads) may have
    ``max(1, floor(maxUnavailableShare x its tasks))`` tasks down at once,
    a node still holding tasks ``grace`` boundaries after its cordon is
    forced out, a drained node is out for ``outFor`` boundaries. With a
    ``chaos:`` section beside it the failures spend the same budgets."""

    enabled: bool = False
    seed: int = 0
    step: int = 25
    first: int = 1
    grace: int = 2
    out_for: int = 1
    max_unavailable_share: float = 0.005


@dataclass
class TuneSpec:
    """Policy-tuner section (``tune:`` YAML, round 9 — sim.tuner). Drives
    ``cmd_tune`` / ``Simulator.tune()``: a seeded search over the Score
    policy surface of the ``profile:`` scheduler against scenarios derived
    from the config's cluster/workload. ``objective`` maps metric name →
    weight (maximized; costs use negative weights). ``scenarios`` holds
    the train/held-out split sizes plus the perturbation sampler knobs;
    ``weight_bounds`` overrides the default search range for every weight
    column; ``output`` is the trajectory JSONL sink (falls back to the
    top-level ``output``)."""

    algo: str = "cem"
    population: int = 16
    rounds: int = 6
    seed: int = 0
    elite_frac: float = 0.25
    objective: Optional[Dict[str, float]] = None
    # Round 13: penalty constraints (list of {metric, max|min, penalty})
    # and the evaluator knob — "auto" (device sweep when the terms allow,
    # else the CPU event engine), "device", or "cpu".
    constraints: Optional[List[Dict[str, float]]] = None
    evaluator: str = "auto"
    train_scenarios: int = 4
    heldout_scenarios: int = 2
    scenario_seed: int = 0
    node_down_p: float = 0.02
    capacity_p: float = 0.3
    taint_p: float = 0.1
    weight_bounds: Optional[List[float]] = None
    tune_strategy: bool = True
    mesh: bool = False
    cpu_oracle: bool = True
    cpu_envelope: float = 1e-6
    output: Optional[str] = None


@dataclass
class DcnRecoverySpec:
    """Elastic fleet recovery (``dcn.recovery:`` YAML section, round 15 —
    parallel.dcn). Config-level spelling of the ``KSIM_DCN_RECOVER`` /
    ``KSIM_DCN_CKPT_EVERY`` / ``KSIM_DCN_MAX_CLAIMS`` env knobs: the CLI
    exports them (setdefault — an operator's explicit env wins) BEFORE
    ``jax.distributed`` bring-up, so the coordination-service failure
    detector is widened in the same run. ``checkpoint_every`` is the
    chunk cadence of compressed checkpoint publication (0 = off; a
    claimed block then re-executes from chunk 0); ``max_claims`` bounds
    the claim generations per dead block (a stale claimant's claim can
    be superseded that many times before the gather fails attributed)."""

    enable: bool = False
    checkpoint_every: int = 0
    max_claims: int = 2


@dataclass
class DcnWorkQueueSpec:
    """Work-stealing scenario-block queue (``dcn.workQueue:`` YAML
    section, round 18 — parallel.dcn). Config-level spelling of the
    ``KSIM_DCN_WORKQUEUE`` / ``KSIM_DCN_WQ_BLOCK`` /
    ``KSIM_DCN_SPECULATE`` / ``KSIM_DCN_STRAGGLER_S`` env knobs, exported
    by the CLI (setdefault) before ``jax.distributed`` bring-up.
    ``block_size`` is scenarios per lease (0 = auto: one block per worker
    — the static partition when nobody steals); ``speculate`` enables
    backup re-execution of straggling blocks (requires checkpoint
    publication via ``dcn.recovery.checkpointEvery`` to resume mid-block;
    validate_config refuses it without); ``straggler_s`` is the
    lease-renewal age past which a LIVE holder becomes
    speculation-eligible (0 = half the stall window)."""

    enable: bool = False
    block_size: int = 0
    speculate: bool = False
    straggler_s: float = 0.0


@dataclass
class DcnDurableSpec:
    """Durable ground (``dcn.durable:`` YAML section, round 20 —
    parallel.dcn). Config-level spelling of the ``KSIM_DCN_DURABLE_DIR``
    / ``KSIM_DCN_RESUME`` env knobs, exported by the CLI (setdefault)
    before ``jax.distributed`` bring-up. ``dir`` is the
    filesystem-backed durability journal the fleet mirrors its
    checkpoint blobs, work-queue results and done/lease ledger into
    (the writes ride the round-19 background publisher — the sync path
    gains no stall); ``resume: true`` seeds a fresh fleet's KV store
    from that journal on bring-up: completed blocks are adopted without
    re-execution, in-flight blocks resume from their newest complete
    durable cursor, and the end gather is byte-identical to an
    uninterrupted run. A bare string is shorthand for ``dir``.
    validate_config refuses a journal without a DCN fleet or without
    any checkpoint cadence — there would be nothing durable to mirror."""

    dir: Optional[str] = None
    resume: bool = False


@dataclass
class FlightRecorderSpec:
    """Flight recorder (``flightRecorder:`` YAML section, round 16 —
    sim.flight). ``path`` is the JSONL stream sink (suffixed per process
    under DCN); ``every`` is the chunk-row cadence (1 = every chunk
    boundary; page/checkpoint/fold events always emit). jax strategy
    only — the CPU engine has no chunk loop to record."""

    path: str = "flight.jsonl"
    every: int = 1


@dataclass
class FaultlineSpec:
    """Deterministic fleet fault injection (``faultline:`` YAML section,
    round 17 — parallel.faultline). Config-level spelling of the
    ``KSIM_FAULTLINE_*`` env knobs, exported by the CLI (setdefault)
    before ``jax.distributed`` bring-up. Rates are per-operation
    probabilities in [0, 1] drawn from seeded per-class streams; ``kill``
    is a SIGKILL schedule (``"1@run:0,*@recover:-1"`` — see
    ``faultline.parse_kill_schedule``). Off by default and only
    meaningful in multi-process (DCN) runs; enabling injection with
    ``dcn.recovery`` disabled is legal but warned — injected kills and
    give-ups then fail the fleet attributed instead of recovering."""

    enabled: bool = False
    seed: int = 0
    kv_error_rate: float = 0.0
    kv_delay_rate: float = 0.0
    kv_delay_s: float = 0.02
    torn_write_rate: float = 0.0
    stale_read_rate: float = 0.0
    kill: Optional[str] = None
    # Straggler schedule (round 18): "<pid>@<chunk>:<factor>" entries —
    # see faultline.parse_slow_schedule. Distinct from kill: the process
    # stays alive, each heartbeat just sleeps `factor` seconds.
    slow: Optional[str] = None


@dataclass
class OverlapSpec:
    """Overlap plane (``overlap:`` YAML section, round 19). Config-level
    spelling of the two stall-hiding gates — each defaults ON in the
    engines; a field left None inherits the engine/env default, an
    explicit false exports the opt-out BEFORE ``jax.distributed``
    bring-up (setdefault — an operator's explicit env wins):

    * ``pagerThread`` → ``KSIM_PAGER_THREAD`` (sim.jax_runtime): run the
      pod-page encode/pack + device_put on a background worker. Requires
      ``pagedWaves: true`` when explicitly enabled.
    * ``backgroundPublisher`` → ``KSIM_DCN_CKPT_ASYNC`` (parallel.dcn):
      single-flight newest-wins checkpoint publication off the loop
      thread. Requires a checkpoint cadence (``dcn.recovery:
      checkpointEvery >= 1`` or a work queue) when explicitly enabled.

    Both are bit-parity pinned (tests/test_overlap.py): placements,
    deterministic JSONL and checkpoint blobs are identical on vs off."""

    pager_thread: Optional[bool] = None
    background_publisher: Optional[bool] = None


@dataclass
class ServiceSpec:
    """Resident query service (``service:`` YAML section, round 22 —
    sim.service / the ``serve`` CLI subcommand). ``maxBatch`` is the
    number of query slots coalesced onto the scenario axis (the device
    batch is maxBatch + 1 — slot 0 is the clean baseline);
    ``batchDeadlineS`` is the admission-queue flush deadline;
    ``maxEngines`` caps the LRU engine pool (the
    ``KSIM_SERVICE_MAX_ENGINES`` env wins over this value);
    ``granularity`` is the default telemetry level of query results
    (queries may override per-request); ``retryBuffer`` sizes the kube
    boundary retry pass defrag drains evict through; ``input`` is an
    NDJSON query source (a file or named pipe; null = stdin). Results
    stream to the top-level ``output`` (null = stdout). Requires
    ``strategy: jax`` and ``devicePreemption: kube`` — validate_config
    refuses anything else."""

    max_batch: int = 3
    batch_deadline_s: float = 0.05
    max_engines: int = 4
    granularity: str = "summary"
    retry_buffer: int = 64
    input: Optional[str] = None


@dataclass
class TelemetrySpec:
    """Telemetry layer (``telemetry:`` YAML section, SURVEY.md §5).

    ``granularity`` is the collection knob (sim.telemetry docstring):
    off / summary (default; latency histogram + phase timers, zero
    device-program change) / series (+ rejection attribution and
    virtual-time depth series) / timeline (+ bind/preempt/evict/chaos
    events). ``timeline_out`` writes the simulated cluster timeline as a
    Chrome trace JSON (load in Perfetto) and implies ``timeline``."""

    granularity: str = "summary"
    timeline_out: Optional[str] = None


def _coerce_completions(v: object) -> Optional[bool]:
    """None stays None (default-on with warn); bool/int coerce to bool;
    everything else is a config error, not a truthy surprise."""
    if v is None:
        return None
    if isinstance(v, (bool, int)):
        return bool(v)
    raise ValueError(
        f"whatIf.completions: must be true or false, got {v!r}"
    )


#: The refusal for a YAML file that still asks for the removed axis.
_NODE_SHARDING_REMOVED = (
    "intra-scenario node sharding was removed: a 10,000-node cluster holds "
    "36.7 MB on the chip (0.2% of its memory), so splitting one scenario's "
    "nodes over devices bought nothing; delete the key"
)


@dataclass
class SimConfig:
    strategy: str = "cpu"
    cluster: SyntheticClusterSpec = field(default_factory=SyntheticClusterSpec)
    workload: Optional[SyntheticWorkloadSpec] = None
    borg: Optional[BorgWorkloadSpec] = None
    framework: FrameworkConfig = field(default_factory=FrameworkConfig)
    whatif: WhatIfSpec = field(default_factory=WhatIfSpec)
    tune: Optional[TuneSpec] = None
    chaos: Optional[ChaosSpec] = None
    drain: Optional[DrainSpec] = None
    dcn_recovery: Optional[DcnRecoverySpec] = None
    dcn_workqueue: Optional[DcnWorkQueueSpec] = None
    dcn_durable: Optional[DcnDurableSpec] = None
    faultline: Optional[FaultlineSpec] = None
    telemetry: TelemetrySpec = field(default_factory=TelemetrySpec)
    output: Optional[str] = None
    wave_width: int = 8
    chunk_waves: int = 1024
    # Device preemption (jax strategy / what-if): False, True/"tier" (the
    # in-scan tier approximation), or "kube" (exact minimal-victims
    # PostFilter at chunk boundaries; single-replay engine only — see
    # sim.greedy / sim.boundary docstrings).
    device_preemption: object = False
    # Big-scenario mode (round 14, jax strategy only): stream pod pages
    # host->device instead of whole-trace residency (`pagedWaves`).
    paged_waves: bool = False
    # Flight recorder (round 16, jax strategy only): streaming JSONL
    # observability for long replays (sim.flight). None = off (the
    # default — the recorder is bit-parity pinned but still costs a
    # stream).
    flight_recorder: Optional[FlightRecorderSpec] = None
    # Overlap plane (round 19): the two stall-hiding gates. None = all
    # engine defaults (on).
    overlap: Optional[OverlapSpec] = None
    # Resident query service (round 22, `serve` subcommand only). None =
    # the config is not a service config.
    service: Optional[ServiceSpec] = None

    @classmethod
    def from_dict(cls, d: dict) -> "SimConfig":
        cfg = cls()
        cfg.strategy = d.get("strategy", "cpu")
        cl = d.get("cluster", {})
        syn = cl.get("synthetic", cl) or {}
        cfg.cluster = SyntheticClusterSpec(
            nodes=int(syn.get("nodes", 100)),
            seed=int(syn.get("seed", 0)),
            taint_fraction=float(syn.get("taintFraction", 0.0)),
            zones=int(syn.get("zones", 8)),
            extended_resources=syn.get("extendedResources"),
        )
        wl = d.get("workload", {})
        if "borg" in wl:
            b = wl["borg"]
            cfg.borg = BorgWorkloadSpec(
                nodes=int(b.get("nodes", 10_000)),
                tasks=int(b.get("tasks", 1_000_000)),
                seed=int(b.get("seed", 0)),
                gang_fraction=float(b.get("gangFraction", 0.08)),
                max_gang=int(b.get("maxGang", 8)),
                num_apps=int(b.get("numApps", 48)),
                trace_path=b.get("tracePath"),
                instance_events=b.get("instanceEvents"),
                collection_events=b.get("collectionEvents"),
                cpu_scale=float(b.get("cpuScale", 8.0)),
                mem_scale=float(b.get("memScale", 16.0 * 2**30)),
                tasks_per_day=float(b.get("tasksPerDay", 0.0)),
                resident_fill=float(b.get("residentFill", 0.0)),
                resident_band=float(b.get("residentBand", 0.05)),
            )
        else:
            syn = wl.get("synthetic", wl) or {}
            cfg.workload = SyntheticWorkloadSpec(
                pods=int(syn.get("pods", 1000)),
                seed=int(syn.get("seed", 0)),
                affinity=bool(syn.get("affinity", False)),
                spread=bool(syn.get("spread", False)),
                tolerations=bool(syn.get("tolerations", False)),
                gang_fraction=float(syn.get("gangFraction", 0.0)),
                gang_size=int(syn.get("gangSize", 4)),
                arrival_rate=float(syn.get("arrivalRate", 100.0)),
                duration_mean=syn.get("durationMean"),
                num_apps=int(syn.get("numApps", 20)),
                extended_resource=syn.get("extendedResource"),
                gang_sizes=syn.get("gangSizes"),
                job_extended_resource=syn.get("jobExtendedResource"),
                job_durations=syn.get("jobDurations"),
            )
        prof = d.get("profile", {})
        plugins = prof.get("plugins")
        cfg.framework = FrameworkConfig(
            plugins=plugins,
            weights=prof.get("weights"),
            enable_preemption=bool(prof.get("preemption", True)),
        )
        wi = d.get("whatIf", {})
        cfg.whatif = WhatIfSpec(
            scenarios=int(wi.get("scenarios", 0)),
            seed=int(wi.get("seed", 0)),
            mesh=bool(wi.get("mesh", False)),
            node_down_p=float(wi.get("nodeDownP", 0.02)),
            capacity_p=float(wi.get("capacityP", 0.3)),
            taint_p=float(wi.get("taintP", 0.1)),
            # int 0/1 coerce to real bools — the engine distinguishes
            # None/True/False by IDENTITY (explicit True must hard-error
            # when unhonorable; 0 must actually disable). Anything else
            # (e.g. the string "yes") raises HERE rather than silently
            # behaving as default-on in engines built without CLI
            # validate_config.
            completions=_coerce_completions(wi.get("completions")),
            retry_buffer=int(wi.get("retryBuffer", 0)),
            retry_groups=bool(wi.get("retryGroups", False)),
            placements=bool(wi.get("placements", False)),
        )
        tu = d.get("tune")
        if tu is not None:
            sc = tu.get("scenarios", {}) or {}
            wb = tu.get("weightBounds")
            cfg.tune = TuneSpec(
                algo=str(tu.get("algo", "cem")),
                population=int(tu.get("population", 16)),
                rounds=int(tu.get("rounds", 6)),
                seed=int(tu.get("seed", 0)),
                elite_frac=float(tu.get("eliteFrac", 0.25)),
                objective=tu.get("objective"),
                constraints=tu.get("constraints"),
                evaluator=str(tu.get("evaluator", "auto")),
                train_scenarios=int(sc.get("train", 4)),
                heldout_scenarios=int(sc.get("heldout", 2)),
                scenario_seed=int(sc.get("seed", 0)),
                node_down_p=float(sc.get("nodeDownP", 0.02)),
                capacity_p=float(sc.get("capacityP", 0.3)),
                taint_p=float(sc.get("taintP", 0.1)),
                weight_bounds=(
                    [float(wb[0]), float(wb[1])] if wb is not None else None
                ),
                tune_strategy=bool(tu.get("tuneStrategy", True)),
                mesh=bool(tu.get("mesh", False)),
                cpu_oracle=bool(tu.get("cpuOracle", True)),
                cpu_envelope=float(tu.get("cpuEnvelope", 1e-6)),
                output=tu.get("output"),
            )
        ch = d.get("chaos")
        if ch is not None:
            cfg.chaos = ChaosSpec(
                enabled=bool(ch.get("enabled", True)),
                seed=int(ch.get("seed", 0)),
                mtbf=float(ch.get("mtbf", 200.0)),
                mttr=float(ch.get("mttr", 20.0)),
                node_fraction=float(ch.get("nodeFraction", 0.2)),
                horizon=(
                    float(ch["horizon"]) if ch.get("horizon") is not None
                    else None
                ),
                max_events=(
                    int(ch["maxEvents"]) if ch.get("maxEvents") is not None
                    else None
                ),
            )
        dr = d.get("drain")
        if dr is not None:
            cfg.drain = DrainSpec(
                enabled=bool(dr.get("enabled", True)),
                seed=int(dr.get("seed", 0)),
                step=int(dr.get("step", 25)),
                first=int(dr.get("first", 1)),
                grace=int(dr.get("grace", 2)),
                out_for=int(dr.get("outFor", 1)),
                max_unavailable_share=float(
                    dr.get("maxUnavailableShare", 0.005)),
            )
        dc = d.get("dcn")
        if dc is not None:
            rec = dc.get("recovery", dc) or {}
            cfg.dcn_recovery = DcnRecoverySpec(
                enable=bool(rec.get("enable", False)),
                checkpoint_every=int(rec.get("checkpointEvery", 0)),
                max_claims=int(rec.get("maxClaims", 2)),
            )
            wq = dc.get("workQueue")
            if wq is not None:
                cfg.dcn_workqueue = DcnWorkQueueSpec(
                    enable=bool(wq.get("enable", False)),
                    block_size=int(wq.get("blockSize", 0)),
                    speculate=bool(wq.get("speculate", False)),
                    straggler_s=float(wq.get("stragglerS", 0.0)),
                )
            du = dc.get("durable")
            if du is not None:
                if isinstance(du, str):
                    # Shorthand: `durable: /path` means `durable: {dir:
                    # /path}` — mirror-only, no resume.
                    du = {"dir": du}
                cfg.dcn_durable = DcnDurableSpec(
                    dir=du.get("dir"),
                    resume=bool(du.get("resume", False)),
                )
        fl = d.get("faultline")
        if fl is not None:
            cfg.faultline = FaultlineSpec(
                enabled=bool(fl.get("enabled", True)),
                seed=int(fl.get("seed", 0)),
                kv_error_rate=float(fl.get("kvErrorRate", 0.0)),
                kv_delay_rate=float(fl.get("kvDelayRate", 0.0)),
                kv_delay_s=float(fl.get("kvDelayS", 0.02)),
                torn_write_rate=float(fl.get("tornWriteRate", 0.0)),
                stale_read_rate=float(fl.get("staleReadRate", 0.0)),
                kill=fl.get("kill"),
                slow=fl.get("slow"),
            )
        tl = d.get("telemetry")
        if tl is not None:
            cfg.telemetry = TelemetrySpec(
                granularity=str(tl.get("granularity", "summary")),
                timeline_out=tl.get("timelineOut"),
            )
            if (
                cfg.telemetry.timeline_out
                and cfg.telemetry.granularity != "off"
            ):
                # A timeline sink needs timeline events collected.
                cfg.telemetry = TelemetrySpec(
                    granularity="timeline",
                    timeline_out=cfg.telemetry.timeline_out,
                )
        cfg.output = d.get("output")
        ww = d.get("waveWidth", 8)
        cfg.wave_width = ww if ww == "auto" else int(ww)
        cfg.chunk_waves = int(d.get("chunkWaves", 1024))
        # bool (legacy: true = tier) or the string "tier"/"kube".
        dp = d.get("devicePreemption", False)
        cfg.device_preemption = dp if isinstance(dp, str) else bool(dp)
        if int(d.get("nodeShards") or 0) > 1:
            raise ValueError("nodeShards: " + _NODE_SHARDING_REMOVED)
        cfg.paged_waves = bool(d.get("pagedWaves", False))
        fr = d.get("flightRecorder")
        if fr is not None:
            if isinstance(fr, str):
                fr = {"path": fr}
            cfg.flight_recorder = FlightRecorderSpec(
                path=str(fr.get("path", "flight.jsonl")),
                every=int(fr.get("every", 1)),
            )
        ov = d.get("overlap")
        if ov is not None:
            if "twoPhaseExchange" in ov:
                raise ValueError(
                    "overlap.twoPhaseExchange: " + _NODE_SHARDING_REMOVED
                )

            def _tristate(key: str) -> Optional[bool]:
                v = ov.get(key)
                if v is None:
                    return None
                if isinstance(v, (bool, int)):
                    return bool(v)
                raise ValueError(
                    f"overlap.{key}: must be true or false, got {v!r}"
                )

            cfg.overlap = OverlapSpec(
                pager_thread=_tristate("pagerThread"),
                background_publisher=_tristate("backgroundPublisher"),
            )
        sv = d.get("service")
        if sv is not None:
            if not isinstance(sv, dict):
                sv = {}
            cfg.service = ServiceSpec(
                max_batch=int(sv.get("maxBatch", 3)),
                batch_deadline_s=float(sv.get("batchDeadlineS", 0.05)),
                max_engines=int(sv.get("maxEngines", 4)),
                granularity=str(sv.get("granularity", "summary")),
                retry_buffer=int(sv.get("retryBuffer", 64)),
                input=sv.get("input"),
            )
        return cfg

    @classmethod
    def load(cls, path: str) -> "SimConfig":
        with open(path) as f:
            return cls.from_dict(yaml.safe_load(f) or {})


def build_case(cfg: SimConfig):
    """Materialize (cluster, pods) from a SimConfig."""
    from ..sim.synthetic import make_cluster, make_workload

    ext = None
    if cfg.cluster.extended_resources:
        ext = {k: tuple(v) for k, v in cfg.cluster.extended_resources.items()}
    cluster = make_cluster(
        cfg.cluster.nodes,
        seed=cfg.cluster.seed,
        num_zones=cfg.cluster.zones,
        taint_fraction=cfg.cluster.taint_fraction,
        extended_resources=ext,
    )
    if cfg.borg is not None:
        from ..sim.borg import make_borg_trace

        cluster, pods = make_borg_trace(cfg.borg)
        return cluster, pods
    wl = cfg.workload or SyntheticWorkloadSpec()
    pods, _ = make_workload(
        wl.pods,
        seed=wl.seed,
        arrival_rate=wl.arrival_rate,
        duration_mean=wl.duration_mean,
        with_affinity=wl.affinity,
        with_spread=wl.spread,
        with_tolerations=wl.tolerations,
        num_apps=wl.num_apps,
        gang_fraction=wl.gang_fraction,
        gang_size=wl.gang_size,
        extended_resource=(
            (str(wl.extended_resource[0]), int(wl.extended_resource[1]),
             float(wl.extended_resource[2]))
            if wl.extended_resource else None
        ),
        gang_sizes=wl.gang_sizes,
        job_extended_resource=wl.job_extended_resource,
        job_durations=wl.job_durations,
    )
    from ..plugins.builtin import inject_default_spread

    inject_default_spread(pods, cfg.framework)
    return cluster, pods


def build_encoded_case(cfg: SimConfig):
    """(EncodedCluster, EncodedPods) for any SimConfig. Borg workloads use
    the vectorized template-expansion fast path (the object-model builder
    caps at 200k tasks), optionally ingesting an external task-event trace
    file (``workload.borg.tracePath``); everything else goes through
    build_case + encode.

    Note: the fast path samples the trace columns vectorized, so a seeded
    borg config yields a DIFFERENT (equally Borg-shaped) trace than the
    pre-CLI object-model generator did — determinism holds per generator,
    not across them."""
    from ..models.encode import encode

    if cfg.borg is not None:
        from ..plugins.builtin import resolved_default_constraints
        from ..sim.borg import BorgSpec, load_trace_csv, make_borg_encoded

        if resolved_default_constraints(cfg.framework):
            import warnings

            warnings.warn(
                "PodTopologySpread cluster-default constraints apply only to "
                "object-model workloads; the encoded Borg fast path ignores "
                "them (Borg tasks carry no controller labels to select on).",
                stacklevel=2,
            )
        spec = BorgSpec.from_spec(cfg.borg)
        if getattr(cfg.borg, "instance_events", None):
            from ..sim.borg_etl import load_borg2019

            ec, ep, _ = load_borg2019(
                cfg.borg.instance_events, spec,
                collection_events=cfg.borg.collection_events,
                cpu_scale=cfg.borg.cpu_scale,
                mem_scale=cfg.borg.mem_scale,
            )
        elif cfg.borg.trace_path:
            ec, ep, _ = load_trace_csv(cfg.borg.trace_path, spec)
        else:
            ec, ep, _ = make_borg_encoded(spec)
        return ec, ep
    cluster, pods = build_case(cfg)
    return encode(cluster, pods)
