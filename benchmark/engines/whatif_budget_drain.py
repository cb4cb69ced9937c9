"""The what-if batch of a full cell under a maintenance drain that respects
disruption budgets: ``engines/whatif_backlog.py``'s adapter (one resident
``WhatIfEngine``, completions on, the device retry pass at every chunk
boundary, every task's node and the boundary that bound it asked for) whose
scenarios are the configuration's rollout plans (``budget_plans.sample``),
each handed to the program as a timeline of ``NodeEvent``s (``node_cordon``
for the plan's walk, ``node_down`` / ``node_up`` for its failure storm, at the
boundaries' start times from the plain schedule of
``references/backlog_scenarios.py``) and a ``DisruptionBudget`` (the trace's
``app_id``, ``max(1, floor(share x expected_a))`` an application, the plan's
``grace`` and ``outFor``). When a cordoned node goes out and comes back is the
program's to find, not the adapter's to say. A batch is one ``run()``, back
when the four answers of every plan are on the host: nodes, bind boundaries,
the eviction log (each row with its kind) and the boundary each node went out.

Refused before any batch: a run off the device-release path, at another chunk
or buffer, with a host mirror in use, or whose timelines and budgets the
program did not take as device data. Two batches that differ in any of the
four answers raise in ``answers``.
"""

from __future__ import annotations

import numpy as np

import budget_plans
from engines import whatif_backlog
from references import backlog_scenarios


def scenarios(config: dict, tasks: dict, app_id, zone, count: int,
              chunk_waves: int):
    """(plans, [(the program's ``NodeEvent`` list, its budget) of each plan])."""
    from kubernetes_simulator_tpu.sim.runtime import DisruptionBudget, NodeEvent

    sched = backlog_scenarios.schedule(
        tasks, config["engine"]["waveWidth"], chunk_waves)
    starts = sched["starts"]
    plans = budget_plans.sample(config, zone, count, len(starts))
    app_id = np.asarray(app_id, np.int32)
    expected = np.bincount(app_id, minlength=config["workload"]["numApps"])
    out = []
    for plan in plans:
        events = []
        for b, (ups, downs, cordons) in enumerate(
                budget_plans.moves(plan, len(starts))):
            t = float(starts[b])
            events += [NodeEvent(t, "node_up", int(n)) for n in ups]
            events += [NodeEvent(t, "node_down", int(n)) for n in downs]
            events += [NodeEvent(t, "node_cordon", int(n)) for n in cordons]
        budget = None
        if plan["step"] or plan["failures"]:
            budget = DisruptionBudget(
                app_id, budget_plans.max_unavailable(plan["share"], expected),
                grace=plan["grace"], out_for=plan["outFor"])
        out.append((events, budget))
    return plans, out


class Engine(whatif_backlog.Engine):
    def __init__(self, ec, ep, config: dict, traffic: dict, chunk_waves: int):
        from kubernetes_simulator_tpu.framework.framework import FrameworkConfig
        from kubernetes_simulator_tpu.sim.whatif import Scenario, WhatIfEngine

        eng = config["engine"]
        rehearsal = ec.num_nodes < config["cluster"]["nodes"]
        count = int((traffic["rehearse"] if rehearsal else traffic)["scenarios"])
        self.retry_buffer = int(traffic["rehearse"]["retryBuffer"] if rehearsal
                                else eng["retryBuffer"])
        self.offered = int((np.asarray(ep.bound_node) < 0).sum())
        self.scenarios_per_chip = count
        tasks = {"arrival": np.asarray(ep.arrival, np.float64),
                 "duration": np.asarray(ep.duration),
                 "group_id": np.asarray(ep.group_id),
                 "bound_node": np.asarray(ep.bound_node)}
        zone = np.arange(ec.num_nodes) % config["cluster"]["zones"]
        self.plans, given = scenarios(
            config, tasks, ep.app_id, zone, count, chunk_waves)
        make = lambda: [Scenario(events=tl, budget=b) for tl, b in given]
        self.engine = WhatIfEngine(
            ec, ep, make(), FrameworkConfig(), wave_width=eng["waveWidth"],
            chunk_waves=chunk_waves, completions=True,
            retry_buffer=self.retry_buffer, collect_assignments=True)
        path = getattr(self.engine, "release_path", None)
        if path != "device":
            raise RuntimeError(
                "the what-if engine is not on the device-release path "
                f"(release_path = {path!r}) with placements asked for")
        if getattr(self.engine, "kube", False) or not getattr(
                self.engine, "_events_dev", False) or not getattr(
                self.engine, "_budget_on", False):
            raise RuntimeError(
                "the what-if engine did not take the plans and their budgets "
                "as device data (a host mirror a scenario is minutes a batch)")
        if self.engine.chunk_waves != chunk_waves:
            raise RuntimeError(
                f"the program runs a chunk of {self.engine.chunk_waves} "
                f"waves, the configuration states {chunk_waves}")
        if self.engine.retry_buffer != self.retry_buffer:
            raise RuntimeError(
                f"the program runs a retry buffer of {self.engine.retry_buffer}, "
                f"the configuration states {self.retry_buffer}")
        self._chunk = chunk_waves
        self.chunk_waves = chunk_waves + self.retry_buffer // eng["waveWidth"]
        self._first_bind_boundary = None
        self._first = {}
        self._make = make

    def without_plans(self) -> dict:
        """Plan 0's two arrays from a batch of the same trace with no plan and
        no budget in any scenario, on the same engine at the same buffer (the
        scenario batch swapped out and back: the compiled programs stay)."""
        from kubernetes_simulator_tpu.sim.whatif import Scenario

        self.engine.set_scenarios([Scenario() for _ in self.plans])
        try:
            result = self.engine.run()
        finally:
            self.engine.set_scenarios(self._make())
        return {"assignments": np.asarray(result.assignments[0], np.int32),
                "bind_boundary": np.asarray(result.bind_boundary[0], np.int32)}

    def answers(self, result) -> dict:
        """``engines/whatif_backlog.py``'s and: the eviction log [S, E, 5]
        (boundary, task, the node it held, the boundary that had bound it or
        -1, the kind: 0 voluntary, 1 forced at a deadline, 2 forced by a
        failure; -1 rows pad) with each plan's count, and ``node_out_at``
        [S, N] (the boundary a cordoned node went out, -1 never)."""
        if result.eviction_log is None or result.node_out_at is None:
            raise RuntimeError(
                "the batch handed back no eviction log or no node_out_at")
        mine = {
            "eviction_log": np.ascontiguousarray(result.eviction_log, np.int32),
            "node_out_at": np.ascontiguousarray(result.node_out_at, np.int32),
        }
        if mine["eviction_log"].shape[-1] != 5:
            raise RuntimeError("the eviction log's rows carry no kind")
        for k, v in mine.items():
            if k not in self._first:
                self._first[k] = v
            elif not np.array_equal(v, self._first[k]):
                raise RuntimeError(f"two batches differ in {k}")
        got = super().answers(result)
        steps = got["retry"].get("pass_waves", {}).get("max")
        if steps is not None:
            self.chunk_waves = self._chunk + steps / max(got["retry"]["passes"], 1)
        return {**got, **mine,
                "evictions": [int(x) for x in result.evictions],
                "without_plans": self.without_plans}
