"""Policy tuner (round 9): batched scheduler-policy search over the
scenario axis.

The what-if engine's scenario axis is the framework's data-parallel axis —
until now it only carried CLUSTER perturbations, so the simulator could
replay a scheduler but not improve one. This module makes the simulator an
optimizer: the per-scenario policy vector (ops.tpu.POLICY_COLS — one Score
weight per plugin plus the NodeResourcesFit strategy selector) is a TRACED
input to the compiled chunk program, so a whole candidate population
evaluates in one vmapped/mesh-sharded sweep with no per-candidate
recompiles, and a host-side seeded search loop (random search or the
cross-entropy method) walks the weight space against a configurable scalar
objective.

Layout: a population of P candidate vectors × S_t train scenarios flattens
onto the scenario axis as (candidate-major) [P·S_t] rows — candidate i
owns rows [i·S_t, (i+1)·S_t). Between rounds only the VECTOR VALUES change
(`WhatIfEngine.set_policies`), so the search runs against exactly one
compiled executable (pinned by tests/test_tuner.py via
``_chunk_fn._cache_size()``).

The winner is re-evaluated two ways: on a HELD-OUT scenario split (one
extra 2·S_h-row sweep, winner vs the config's default policy) and on the
CPU event engine (``greedy_replay`` per held-out scenario over the
perturbed host clusters — the bit-parity oracle the device engines anchor
to), whose objective must match the device objective within a pinned
envelope.

The full search trajectory streams as schema-v3 JSONL rows (``run_type:
"tune"``; see scripts/check_metrics_schema.py) and is bit-deterministic
for a fixed seed + config: rows carry no wall-clock fields (pass
``stamp_ts=False`` to JsonlWriter — the determinism satellite pins
byte-identical files across runs).
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from ..framework.framework import FrameworkConfig
from ..ops import tpu as T
from ..plugins.builtin import (
    TUNABLE_FIT_STRATEGIES,
    tunable_parameters,
)
from ..utils.metrics import TUNE_SCHEMA_VERSION, log
from .whatif import Scenario, WhatIfEngine, uniform_scenarios

#: Objective terms every engine path provides. Terms outside this set need
#: specific what-if modes (latency quantiles / preemptions / evictions ride
#: the kube host mirrors) which the policy axis does not support yet — the
#: objective assembler raises an actionable error rather than scoring 0.
_ALWAYS_METRICS = ("placementRate", "unschedulable", "utilizationCpu")
_RESULT_METRICS = {
    "placementRate": None,  # computed from placed/unschedulable
    "unschedulable": "unschedulable",
    "utilizationCpu": "utilization_cpu",
    "preemptions": "preemptions",
    "retryDropped": "retry_dropped",
    "evictions": "evictions",
    "latencyP50": "latency_p50",
    "latencyP90": "latency_p90",
    "latencyP99": "latency_p99",
    # Utilization economics (round 13) — fragmentation gauges ride the
    # kube host mirrors on WhatIfResult and every ReplayResult; the
    # host (CPU event engine) evaluator provides them for any trace.
    "strandedCpu": "stranded_cpu",
    "fragIndexCpu": "frag_index_cpu",
    "packingEfficiency": "packing_efficiency",
}

#: Terms the CPU oracle (greedy_replay) can recompute exactly — the
#: envelope check is skipped (with a log note) for objectives outside it.
_ORACLE_METRICS = {
    "placementRate", "unschedulable", "utilizationCpu",
    "strandedCpu", "fragIndexCpu", "packingEfficiency",
}

DEFAULT_OBJECTIVE = {"placementRate": 1.0}


def _metric_series(res, key: str) -> np.ndarray:
    """Per-scenario [S] f64 series for one objective term, or raise with
    the engine mode the term needs."""
    if key == "placementRate":
        placed = np.asarray(res.placed, np.float64)
        unsched = np.asarray(res.unschedulable, np.float64)
        return placed / np.maximum(placed + unsched, 1.0)
    attr = _RESULT_METRICS[key]
    val = getattr(res, attr)
    if val is None:
        raise ValueError(
            f"objective term {key!r} is unavailable on this what-if path "
            "(latency quantiles / preemptions / evictions ride the kube "
            "host mirrors, which the policy axis does not support) — use "
            f"terms from {sorted(_ALWAYS_METRICS)}"
        )
    return np.asarray(val, np.float64)


def normalize_constraints(constraints) -> List[dict]:
    """Validate penalty-constraint specs (round 13). Each entry is
    ``{"metric": <term>, "max": x | "min": x, "penalty": p}`` — ``max``
    bounds the metric from above, ``min`` from below; ``penalty``
    (default 1.0, must be > 0) scales the hinge. Returns normalized
    copies (exactly one bound key, float values)."""
    out: List[dict] = []
    for i, c in enumerate(constraints or []):
        where = f"constraints[{i}]"
        if not isinstance(c, dict):
            raise ValueError(f"{where}: expected a mapping, got {c!r}")
        metric = c.get("metric")
        if metric not in _RESULT_METRICS:
            raise ValueError(
                f"{where}: unknown metric {metric!r} — known: "
                f"{sorted(_RESULT_METRICS)}"
            )
        has_max, has_min = "max" in c, "min" in c
        if has_max == has_min:
            raise ValueError(
                f"{where}: need exactly one of 'max' or 'min' (got "
                f"{sorted(set(c) & {'max', 'min'}) or 'neither'})"
            )
        penalty = float(c.get("penalty", 1.0))
        if not penalty > 0:
            raise ValueError(f"{where}: penalty must be > 0, got {penalty}")
        unknown = sorted(set(c) - {"metric", "max", "min", "penalty"})
        if unknown:
            raise ValueError(f"{where}: unknown key(s) {unknown}")
        norm = {"metric": metric, "penalty": penalty}
        norm["max" if has_max else "min"] = float(c["max" if has_max else "min"])
        out.append(norm)
    return out


def make_objective(
    weights: Optional[Dict[str, float]], constraints=None
) -> Tuple[Dict[str, float], List[dict], Callable]:
    """Validate an objective spec and return (weights, constraints, fn)
    where fn maps a WhatIfResult to a per-scenario [S] f64 objective
    (HIGHER IS BETTER — express costs with negative weights, e.g.
    ``{"placementRate": 1.0, "unschedulable": -0.01}``).

    ``constraints`` (round 13) turn the weighted sum into a penalty form:
    each violated bound subtracts ``penalty · relu(violation)`` — e.g.
    maximize ``utilizationCpu`` subject to ``latencyP99 <= 2.0``. A NaN
    constraint metric (a scenario that bound nothing has no latency
    quantiles) contributes zero violation."""
    w = dict(DEFAULT_OBJECTIVE if weights is None else weights)
    unknown = sorted(set(w) - set(_RESULT_METRICS))
    if unknown:
        raise ValueError(
            f"unknown objective term(s) {unknown} — known: "
            f"{sorted(_RESULT_METRICS)}"
        )
    if not w:
        raise ValueError("objective must contain at least one term")
    cons = normalize_constraints(constraints)

    def fn(res) -> np.ndarray:
        out = None
        for key, wt in w.items():
            term = float(wt) * _metric_series(res, key)
            out = term if out is None else out + term
        for c in cons:
            v = _metric_series(res, c["metric"])
            if "max" in c:
                viol = np.maximum(v - c["max"], 0.0)
            else:
                viol = np.maximum(c["min"] - v, 0.0)
            out = out - c["penalty"] * np.nan_to_num(viol, nan=0.0)
        return out

    return w, cons, fn


@dataclass(frozen=True)
class SearchSpace:
    """The searched dimensions, derived from the config's tunable-parameter
    surface (plugins.builtin.tunable_parameters). Weight columns of
    disabled plugins and an inert strategy selector are PINNED to their
    defaults — the device program statically dropped their rows, so
    searching them would only add noise dimensions."""

    lo: np.ndarray  # [5] per-weight-column lower bound
    hi: np.ndarray  # [5] upper bound
    defaults: np.ndarray  # [len(POLICY_COLS)] the config's own policy
    weight_mask: np.ndarray  # [5] bool — searched weight columns
    tune_strategy: bool  # search the fit_least selector?

    @classmethod
    def from_config(
        cls,
        config: Optional[FrameworkConfig],
        weight_bounds: Optional[Tuple[float, float]] = None,
        tune_strategy: bool = True,
    ) -> "SearchSpace":
        params = {p["name"]: p for p in tunable_parameters(config)}
        nW = len(T.POLICY_WEIGHT_COLS)
        lo = np.zeros(nW)
        hi = np.zeros(nW)
        mask = np.zeros(nW, bool)
        defaults = np.zeros(len(T.POLICY_COLS), np.float32)
        for i, name in enumerate(T.POLICY_WEIGHT_COLS):
            p = params[name]
            lo[i], hi[i] = p["lo"], p["hi"]
            if weight_bounds is not None:
                lo[i], hi[i] = weight_bounds
            mask[i] = p["enabled"]
            defaults[i] = p["default"]
        strat = params["NodeResourcesFit.strategy"]
        defaults[T.IDX_FIT_LEAST] = float(
            strat["default"] == "LeastAllocated"
        )
        if np.any(lo >= hi):
            raise ValueError(f"weight bounds must satisfy lo < hi, got {lo}..{hi}")
        return cls(
            lo=lo, hi=hi, defaults=defaults, weight_mask=mask,
            tune_strategy=bool(tune_strategy and strat["enabled"]),
        )

    def clip(self, vecs: np.ndarray) -> np.ndarray:
        """Project candidate vectors into the space: clip weights to
        bounds, binarize the selector, pin unsearched columns."""
        out = np.asarray(vecs, np.float32).copy()
        nW = len(T.POLICY_WEIGHT_COLS)
        out[:, :nW] = np.clip(out[:, :nW], self.lo, self.hi)
        out[:, ~np.concatenate([self.weight_mask, [self.tune_strategy]])] = (
            self.defaults[None, ~np.concatenate(
                [self.weight_mask, [self.tune_strategy]]
            )]
        )
        out[:, T.IDX_FIT_LEAST] = (out[:, T.IDX_FIT_LEAST] > 0.5).astype(
            np.float32
        )
        return out

    def describe(self, vec: np.ndarray) -> Dict[str, float]:
        """A policy vector as a {column: value} dict for JSONL/reporting
        (the selector reported as the strategy name)."""
        out = {
            name: round(float(vec[i]), 6)
            for i, name in enumerate(T.POLICY_WEIGHT_COLS)
        }
        out["fitStrategy"] = TUNABLE_FIT_STRATEGIES[
            int(vec[T.IDX_FIT_LEAST] > 0.5)
        ]
        return out


@dataclass
class TuneResult:
    best_policy: Dict[str, float]  # SearchSpace.describe of the winner
    best_vector: np.ndarray  # [len(POLICY_COLS)] f32
    train_objective: float
    heldout_objective: float
    default_heldout_objective: float
    rounds: int
    population: int
    evaluations: int  # candidate×train-scenario device evaluations
    wall_clock_s: float
    compile_count: Optional[int]  # chunk-program executables (pin: 1)
    cpu_objective: Optional[float] = None  # oracle mean over held-out
    cpu_envelope: Optional[float] = None  # |device − cpu|, None if skipped
    trajectory: List[dict] = field(default_factory=list)
    # Mesh provenance (round 10, no silent caps): the population the
    # caller ASKED for — ``population`` above is the fitted size after
    # parallel.mesh.fit_population rounded it up for mesh divisibility —
    # plus the device count the sweep actually ran on.
    population_requested: Optional[int] = None
    n_devices: int = 1
    mesh_shape: Optional[dict] = None  # {axis_name: size} or None
    # DCN provenance (round 11): processes that contributed candidate
    # blocks. The sweep engine gathers objectives exactly once per run()
    # (WhatIfEngine's end-of-replay gather), so every process scores the
    # identical full population and the search trajectory is
    # process-count-independent.
    process_count: int = 1
    # Constraint-aware objectives (round 13): the normalized penalty
    # constraints the search optimized under, and which evaluator scored
    # candidates — "device" (batched what-if sweep) or "cpu" (the CPU
    # event engine, required for latency/host-mirror terms).
    objective_constraints: List[dict] = field(default_factory=list)
    evaluator: str = "device"

    def improved(self) -> bool:
        return self.heldout_objective > self.default_heldout_objective


class PolicyTuner:
    """Seeded search over scheduler score policies against one trace.

    ``algo``: "cem" (cross-entropy method: Gaussian weight columns +
    Bernoulli strategy selector, elite refit with a std floor) or
    "random" (uniform in bounds). Both carry the incumbent best as
    candidate 0 of every round (round 0's incumbent is the config's own
    default policy, so the search can only match-or-beat the configured
    scheduler on the train split).
    """

    def __init__(
        self,
        ec,
        pods,
        config: Optional[FrameworkConfig] = None,
        *,
        algo: str = "cem",
        population: int = 16,
        rounds: int = 6,
        seed: int = 0,
        elite_frac: float = 0.25,
        objective: Optional[Dict[str, float]] = None,
        constraints: Optional[List[dict]] = None,
        evaluator: str = "auto",
        train_scenarios: int = 4,
        heldout_scenarios: int = 2,
        scenario_seed: int = 0,
        p_node_down: float = 0.02,
        p_capacity: float = 0.3,
        p_taint: float = 0.1,
        weight_bounds: Optional[Tuple[float, float]] = None,
        tune_strategy: bool = True,
        wave_width: int = 8,
        chunk_waves: int = 1024,
        completions: Optional[bool] = None,
        mesh=None,
        cpu_oracle: bool = True,
        cpu_envelope: float = 1e-6,
    ):
        if algo not in ("cem", "random"):
            raise ValueError(f"algo must be 'cem' or 'random', got {algo!r}")
        if rounds < 1 or population < 2:
            raise ValueError("need rounds >= 1 and population >= 2")
        if train_scenarios < 1 or heldout_scenarios < 1:
            raise ValueError(
                "need train_scenarios >= 1 and heldout_scenarios >= 1 "
                "(the acceptance check is on the held-out split)"
            )
        if not 0.0 < elite_frac <= 1.0:
            raise ValueError("elite_frac must be in (0, 1]")
        self.ec, self.pods, self.config = ec, pods, config
        self.algo = algo
        self.rounds = int(rounds)
        self.seed = int(seed)
        self.elite_frac = float(elite_frac)
        self.space = SearchSpace.from_config(
            config, weight_bounds=weight_bounds, tune_strategy=tune_strategy
        )
        (
            self.objective_weights,
            self.objective_constraints,
            self._objective,
        ) = make_objective(objective, constraints)
        # Evaluator selection (round 13). "device": the batched policy
        # sweep (one compiled executable, the round-9 fast path) —
        # restricted to _ALWAYS_METRICS because the policy axis has no
        # kube host mirrors. "cpu": score every candidate×scenario on the
        # CPU event engine, which carries EVERY metric (latency
        # quantiles, fragmentation gauges) exactly. "auto" picks device
        # when the terms allow it, else cpu.
        if evaluator not in ("auto", "device", "cpu"):
            raise ValueError(
                f"evaluator must be 'auto', 'device' or 'cpu', got "
                f"{evaluator!r}"
            )
        terms = set(self.objective_weights) | {
            c["metric"] for c in self.objective_constraints
        }
        needs_host = not terms <= set(_ALWAYS_METRICS)
        if evaluator == "device" and needs_host:
            raise ValueError(
                f"objective/constraint term(s) "
                f"{sorted(terms - set(_ALWAYS_METRICS))} ride the kube "
                "host mirrors, which the batched policy sweep does not "
                "support — use evaluator='cpu' (every candidate scored "
                "on the CPU event engine) or restrict terms to "
                f"{sorted(_ALWAYS_METRICS)}"
            )
        self.evaluator = "cpu" if (evaluator == "cpu" or needs_host) else "device"
        if self.evaluator == "cpu" and evaluator == "auto":
            log.info(
                "tune: objective terms %s need the host evaluator — "
                "scoring candidates on the CPU event engine",
                sorted(terms - set(_ALWAYS_METRICS)),
            )
        self.S_t = int(train_scenarios)
        self.S_h = int(heldout_scenarios)
        self.mesh = mesh
        from ..parallel.mesh import fit_population

        self.population_requested = int(population)
        self.population = fit_population(population, self.S_t, mesh)
        if self.population != population:
            log.info(
                "tune: population %d -> %d (flat population x train axis "
                "must divide over the mesh devices)",
                population, self.population,
            )
        # One scenario pool, split train/held-out: scenario 0 (the
        # unperturbed base) lands in TRAIN — the tuned policy must not
        # regress the nominal cluster; the held-out split is all-perturbed.
        pool = uniform_scenarios(
            ec, self.S_t + self.S_h, seed=scenario_seed,
            p_node_down=p_node_down, p_capacity=p_capacity, p_taint=p_taint,
        )
        self.train_split: List[Scenario] = list(pool[: self.S_t])
        self.heldout_split: List[Scenario] = list(pool[self.S_t :])
        self._engine_kw = dict(
            config=config, wave_width=wave_width, chunk_waves=chunk_waves,
            completions=completions, mesh=mesh,
        )
        self.cpu_oracle = bool(cpu_oracle)
        self.cpu_envelope = float(cpu_envelope)
        self._train_engine: Optional[WhatIfEngine] = None
        # Host-evaluator state: perturbed host clusters per split, and a
        # per-(split, vector) objective cache — the incumbent rides as
        # candidate 0 of EVERY round, so caching keeps the search loop
        # from re-replaying identical candidates.
        self._host_clusters: Dict[str, list] = {}
        self._host_cache: Dict[tuple, np.ndarray] = {}

    # -- population sampling ------------------------------------------------

    def _sample(self, rng, mean, std, theta) -> np.ndarray:
        P = self.population
        nW = len(T.POLICY_WEIGHT_COLS)
        vecs = np.tile(self.space.defaults, (P, 1)).astype(np.float32)
        if self.algo == "random":
            vecs[:, :nW] = rng.uniform(
                self.space.lo, self.space.hi, size=(P, nW)
            )
        else:
            vecs[:, :nW] = rng.normal(mean, std, size=(P, nW))
        if self.space.tune_strategy:
            p_least = 0.5 if self.algo == "random" else theta
            vecs[:, T.IDX_FIT_LEAST] = (
                rng.random(P) < p_least
            ).astype(np.float32)
        return self.space.clip(vecs)

    def _refit(self, elites, mean, std, theta):
        """CEM elite refit with a std floor (keeps exploration alive) —
        random search ignores the distribution state entirely."""
        if self.algo == "random":
            return mean, std, theta
        nW = len(T.POLICY_WEIGHT_COLS)
        floor = 0.05 * (self.space.hi - self.space.lo)
        mean = elites[:, :nW].astype(np.float64).mean(axis=0)
        std = np.maximum(elites[:, :nW].astype(np.float64).std(axis=0), floor)
        if self.space.tune_strategy:
            theta = float(
                np.clip(elites[:, T.IDX_FIT_LEAST].mean(), 0.05, 0.95)
            )
        return mean, std, theta

    # -- evaluation ---------------------------------------------------------

    def _flat_policies(self, cand: np.ndarray) -> np.ndarray:
        """[P, K] candidates → [P·S_t, K] candidate-major flat rows, the
        layout the train engine's scenario list was built with."""
        return np.repeat(cand, self.S_t, axis=0)

    def _policy_config(self, vec: np.ndarray) -> FrameworkConfig:
        """A candidate vector materialized as an ordinary FrameworkConfig
        (the host engines' policy carrier)."""
        desc = self.space.describe(vec)
        strategy = desc.pop("fitStrategy")
        base = self.config if self.config is not None else FrameworkConfig()
        return base.with_policy(
            desc, fit_strategy=strategy if self.space.tune_strategy else None
        )

    # -- host (CPU event engine) evaluator, round 13 -------------------------

    def _host_split_clusters(self, split_name: str) -> list:
        from .whatif import ScenarioSet

        clusters = self._host_clusters.get(split_name)
        if clusters is None:
            split = (
                self.train_split if split_name == "train"
                else self.heldout_split
            )
            clusters = ScenarioSet(
                self.ec, split, keep_host_stacks=True
            ).host_clusters(self.ec)
            self._host_clusters[split_name] = clusters
        return clusters

    def _host_row(self, ec_s, cfg: FrameworkConfig):
        """One scenario scored on the CPU event engine — the exact oracle:
        event-clock latencies, end-of-replay fragmentation gauges, every
        _RESULT_METRICS term present (len-1 arrays, WhatIfResult shape)."""
        from types import SimpleNamespace

        from .runtime import CpuReplayEngine

        r = CpuReplayEngine(ec_s, self.pods, cfg, telemetry="summary").replay()
        lat = r.telemetry.latency if r.telemetry is not None else None

        def q(k: str) -> np.ndarray:
            return np.array(
                [float(lat[k]) if lat else np.nan], np.float64
            )

        fr = r.fragmentation
        return SimpleNamespace(
            placed=np.array([float(r.placed)]),
            unschedulable=np.array([float(r.unschedulable)]),
            utilization_cpu=np.array([r.utilization.get("cpu", 0.0)]),
            preemptions=np.array([float(r.preemptions)]),
            retry_dropped=np.array([float(r.retry_dropped)]),
            evictions=np.array([float(r.evictions)]),
            latency_p50=q("p50"), latency_p90=q("p90"), latency_p99=q("p99"),
            stranded_cpu=np.array([fr["stranded"].get("cpu", 0.0)]),
            frag_index_cpu=np.array([fr["frag_index"].get("cpu", 0.0)]),
            packing_efficiency=np.array([fr["packing_efficiency"]]),
        )

    def _host_objective(self, vec: np.ndarray, split_name: str) -> np.ndarray:
        """Per-scenario objective of one candidate on one split, via the
        CPU event engine; cached by (split, vector bytes)."""
        key = (split_name, np.asarray(vec, np.float32).tobytes())
        hit = self._host_cache.get(key)
        if hit is not None:
            return hit
        cfg = self._policy_config(vec)
        rows = [
            self._host_row(ec_s, cfg)
            for ec_s in self._host_split_clusters(split_name)
        ]
        obj = np.concatenate([self._objective(r) for r in rows])
        self._host_cache[key] = obj
        return obj

    def _train_eval(self, cand: np.ndarray) -> np.ndarray:
        """Evaluate the whole population in ONE device sweep (host mode:
        one CPU event replay per candidate×scenario, cached); returns the
        [P] per-candidate objective (mean over its train scenarios)."""
        if self.evaluator == "cpu":
            return np.array([
                float(self._host_objective(cand[i], "train").mean())
                for i in range(self.population)
            ])
        flat = self._flat_policies(cand)
        if self._train_engine is None:
            self._train_engine = WhatIfEngine(
                self.ec, self.pods, self.train_split * self.population,
                policies=flat, **self._engine_kw,
            )
        else:
            self._train_engine.set_policies(flat)
        res = self._train_engine.run()
        per_scenario = self._objective(res)
        return per_scenario.reshape(self.population, self.S_t).mean(axis=1)

    def _heldout_eval(self, best_vec: np.ndarray):
        """One 2-policy sweep on the held-out split: winner vs the
        config's default policy. Returns (best_obj, default_obj,
        per-scenario winner objectives, engine)."""
        if self.evaluator == "cpu":
            best = self._host_objective(best_vec, "heldout")
            default = self._host_objective(self.space.defaults, "heldout")
            return float(best.mean()), float(default.mean()), best, None
        pol = np.concatenate([
            np.repeat(best_vec[None], self.S_h, axis=0),
            np.repeat(self.space.defaults[None], self.S_h, axis=0),
        ])
        eng = WhatIfEngine(
            self.ec, self.pods, self.heldout_split * 2,
            policies=pol, **self._engine_kw,
        )
        per_scenario = self._objective(eng.run())
        best = per_scenario[: self.S_h]
        default = per_scenario[self.S_h :]
        return float(best.mean()), float(default.mean()), best, eng

    def _oracle_eval(self, best_vec: np.ndarray, eng: WhatIfEngine):
        """Re-evaluate the winner on the CPU event engine per held-out
        scenario — the perturbed host clusters feed ``greedy_replay`` with
        the winning weights materialized as an ordinary FrameworkConfig."""
        from types import SimpleNamespace

        from .greedy import greedy_replay
        from .whatif import ScenarioSet

        if self.evaluator == "cpu":
            log.info(
                "tune: CPU-oracle check skipped — evaluation already ran "
                "on the CPU event engine"
            )
            return None
        terms = set(self.objective_weights) | {
            c["metric"] for c in self.objective_constraints
        }
        if not terms <= _ORACLE_METRICS:
            log.info(
                "tune: CPU-oracle check skipped — objective uses terms "
                "outside %s", sorted(_ORACLE_METRICS),
            )
            return None
        cfg = self._policy_config(best_vec)
        sset = ScenarioSet(self.ec, self.heldout_split, keep_host_stacks=True)
        chunk = eng.chunk_waves if eng.completions_on else None
        rows = []
        for ec_s in sset.host_clusters(self.ec):
            r = greedy_replay(
                ec_s, self.pods, cfg, wave_width=eng.wave_width,
                completions_chunk_waves=chunk,
            )
            placed, unsched = float(r.placed), float(r.unschedulable)
            fr = r.fragmentation or {}
            rows.append(SimpleNamespace(
                placed=np.array([placed]),
                unschedulable=np.array([unsched]),
                utilization_cpu=np.array([r.utilization.get("cpu", 0.0)]),
                preemptions=np.array([float(r.preemptions)]),
                retry_dropped=np.array([float(r.retry_dropped)]),
                evictions=np.array([float(r.evictions)]),
                latency_p50=None, latency_p90=None, latency_p99=None,
                stranded_cpu=np.array(
                    [fr.get("stranded", {}).get("cpu", 0.0)]
                ),
                frag_index_cpu=np.array(
                    [fr.get("frag_index", {}).get("cpu", 0.0)]
                ),
                packing_efficiency=np.array(
                    [fr.get("packing_efficiency", 1.0)]
                ),
            ))
        return np.concatenate([self._objective(r) for r in rows])

    # -- the search loop ----------------------------------------------------

    def run(self, writer=None) -> TuneResult:
        """Run the search. ``writer`` (utils.metrics.JsonlWriter) streams
        the trajectory; rows are written WITHOUT the wall-clock stamp so a
        fixed seed + config yields byte-identical files."""
        import time

        t0 = time.perf_counter()
        rng = np.random.default_rng(self.seed)
        nW = len(T.POLICY_WEIGHT_COLS)
        mean = self.space.defaults[:nW].astype(np.float64)
        std = (self.space.hi - self.space.lo) / 4.0
        theta = 0.5
        best_vec = self.space.clip(self.space.defaults[None])[0]
        best_obj = -math.inf
        trajectory: List[dict] = []

        def emit(row: dict) -> None:
            row = {"schema": TUNE_SCHEMA_VERSION, "run_type": "tune", **row}
            trajectory.append(row)
            if writer is not None:
                writer.write(row, stamp_ts=False)

        n_elite = max(1, int(math.ceil(self.elite_frac * self.population)))
        for rd in range(self.rounds):
            cand = self._sample(rng, mean, std, theta)
            # Elitism: the incumbent rides as candidate 0 (round 0's
            # incumbent is the config default) — the train best is
            # monotone and the default is always evaluated.
            cand[0] = best_vec
            objs = self._train_eval(cand)
            order = np.argsort(-objs, kind="stable")  # ties → lower index
            mean, std, theta = self._refit(
                cand[order[:n_elite]], mean, std, theta
            )
            if objs[order[0]] > best_obj:
                best_obj = float(objs[order[0]])
                best_vec = cand[order[0]].copy()
            for i in range(self.population):
                emit({
                    "kind": "tune-candidate", "round": rd, "candidate": i,
                    "policy": self.space.describe(cand[i]),
                    "objective": round(float(objs[i]), 9),
                    "split": "train",
                })
            emit({
                "kind": "tune-round", "round": rd,
                "best_objective": round(best_obj, 9),
                "round_best_objective": round(float(objs[order[0]]), 9),
                "mean_objective": round(float(objs.mean()), 9),
                "best_candidate": int(order[0]),
            })
            log.info(
                "tune: round %d/%d best=%.6f (incumbent %.6f)",
                rd + 1, self.rounds, float(objs[order[0]]), best_obj,
            )

        held_obj, held_default, held_rows, held_eng = self._heldout_eval(
            best_vec
        )
        cpu_obj = cpu_env = None
        if self.cpu_oracle:
            oracle_rows = self._oracle_eval(best_vec, held_eng)
            if oracle_rows is not None:
                cpu_obj = float(oracle_rows.mean())
                cpu_env = float(np.abs(oracle_rows - held_rows).max())
                if cpu_env > self.cpu_envelope:
                    log.warning(
                        "tune: CPU-oracle objective diverges from the "
                        "device objective by %.3g (> envelope %.3g)",
                        cpu_env, self.cpu_envelope,
                    )
        compile_count = (
            int(self._train_engine._chunk_fn._cache_size())
            if self._train_engine is not None
            else None  # host evaluator: no device executable
        )
        emit({
            "kind": "tune-result",
            "best_policy": self.space.describe(best_vec),
            "train_objective": round(best_obj, 9),
            "heldout_objective": round(held_obj, 9),
            "default_heldout_objective": round(held_default, 9),
            "cpu_objective": (
                round(cpu_obj, 9) if cpu_obj is not None else None
            ),
            "cpu_envelope": (
                round(cpu_env, 12) if cpu_env is not None else None
            ),
            "rounds": self.rounds,
            "population": self.population,
            "evaluations": self.rounds * self.population * self.S_t,
            "objective_weights": {
                k: float(v) for k, v in self.objective_weights.items()
            },
            "objective_constraints": self.objective_constraints,
            "evaluator": self.evaluator,
            "algo": self.algo,
            "seed": self.seed,
        })
        return TuneResult(
            best_policy=self.space.describe(best_vec),
            best_vector=best_vec,
            train_objective=best_obj,
            heldout_objective=held_obj,
            default_heldout_objective=held_default,
            rounds=self.rounds,
            population=self.population,
            evaluations=self.rounds * self.population * self.S_t,
            wall_clock_s=time.perf_counter() - t0,
            compile_count=compile_count,
            cpu_objective=cpu_obj,
            cpu_envelope=cpu_env,
            trajectory=trajectory,
            population_requested=self.population_requested,
            n_devices=(
                int(self.mesh.devices.size) if self.mesh is not None else 1
            ),
            mesh_shape=(
                dict(zip(
                    self.mesh.axis_names,
                    (int(d) for d in self.mesh.devices.shape),
                ))
                if self.mesh is not None
                else None
            ),
            process_count=jax.process_count(),
            objective_constraints=self.objective_constraints,
            evaluator=self.evaluator,
        )
