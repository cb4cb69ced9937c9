"""The what-if batch of a full cell under a rolling maintenance drain:
``engines/whatif_backlog.py``'s adapter (one resident ``WhatIfEngine``,
completions on, the device retry pass at every chunk boundary, every task's
node and the boundary that bound it asked for) whose scenarios are the
configuration's rollout plans (``drain_plans.sample``), each handed to the
program as a timeline of ``NodeEvent``s: at boundary ``b`` (its start time,
from the plain schedule of ``references/backlog_scenarios.py``) the plan's
next nodes go down and the ones whose time is up come back. A batch is one
``run()``, back when the three answers of every plan are on the host: nodes,
bind boundaries and the eviction log.

Refused before any batch: a run off the device-release path, at another
chunk or buffer, with a host mirror in use (``preemption="kube"``: minutes a
batch), or whose timelines the program did not take as device events. Two
batches that differ in ``bind_boundary`` or in the log raise in ``answers``.
"""

from __future__ import annotations

import numpy as np

import drain_plans
from engines import whatif_backlog
from references import backlog_scenarios


def timelines(config: dict, trace: dict, zone, count: int, chunk_waves: int):
    """(plans, [the program's ``NodeEvent`` list of each plan])."""
    from kubernetes_simulator_tpu.sim.runtime import NodeEvent

    sched = backlog_scenarios.schedule(
        trace, config["engine"]["waveWidth"], chunk_waves)
    starts = sched["starts"]
    plans = drain_plans.sample(config, zone, count)
    out = []
    for plan in plans:
        events = []
        for b, (leave, back) in enumerate(drain_plans.moves(plan, len(starts))):
            # back before leave: a node's up stands before a later down of it
            events += [NodeEvent(float(starts[b]), "node_up", int(n)) for n in back]
            events += [NodeEvent(float(starts[b]), "node_down", int(n)) for n in leave]
        out.append(events)
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
        self.plans, events = timelines(config, tasks, zone, count, chunk_waves)
        self.engine = WhatIfEngine(
            ec, ep, [Scenario(events=tl) for tl in events],
            FrameworkConfig(), wave_width=eng["waveWidth"],
            chunk_waves=chunk_waves, completions=True,
            retry_buffer=self.retry_buffer, collect_assignments=True)
        path = getattr(self.engine, "release_path", None)
        if path != "device":
            raise RuntimeError(
                "the what-if engine is not on the device-release path "
                f"(release_path = {path!r}) with placements asked for")
        if getattr(self.engine, "kube", False) or not getattr(
                self.engine, "_events_dev", False):
            raise RuntimeError(
                "the what-if engine did not take the plans as device events "
                "(a host mirror a scenario is minutes a batch)")
        if self.engine.chunk_waves != chunk_waves:
            raise RuntimeError(
                f"the program runs a chunk of {self.engine.chunk_waves} "
                f"waves, the configuration states {chunk_waves}")
        if self.engine.retry_buffer != self.retry_buffer:
            raise RuntimeError(
                f"the program runs a retry buffer of {self.engine.retry_buffer}, "
                f"the configuration states {self.retry_buffer}")
        # What the per-wave metrics divide a chunk call's device time by: the
        # chunk's waves and the pass's. A pass ends with the fullest plan's
        # last queued wave, so ``answers`` puts the mean the batch executed
        # in the place of this upper bound.
        self._chunk = chunk_waves
        self.chunk_waves = chunk_waves + self.retry_buffer // eng["waveWidth"]
        self._first_bind_boundary = None
        self._first_log = None
        self._events = events

    def without_plans(self) -> dict:
        """Plan 0's two arrays from a batch of the same trace with no plan in
        any scenario, on the same engine at the same buffer (the scenario
        batch swapped out and back: the compiled programs stay)."""
        from kubernetes_simulator_tpu.sim.whatif import Scenario

        self.engine.set_scenarios([Scenario() for _ in self._events])
        try:
            result = self.engine.run()
        finally:
            self.engine.set_scenarios([Scenario(events=tl) for tl in self._events])
        return {"assignments": np.asarray(result.assignments[0], np.int32),
                "bind_boundary": np.asarray(result.bind_boundary[0], np.int32)}

    def answers(self, result) -> dict:
        """``engines/whatif_backlog.py``'s and the third answer: the eviction
        log [S, E, 4] (boundary, task, the node it held, the boundary that
        had bound it or -1; -1 rows pad) with each plan's count."""
        if result.eviction_log is None:
            raise RuntimeError("the batch handed back no eviction log")
        log = np.ascontiguousarray(result.eviction_log, dtype=np.int32)
        if self._first_log is None:
            self._first_log = log
        elif not np.array_equal(log, self._first_log):
            raise RuntimeError("two batches differ in the eviction log")
        got = super().answers(result)
        steps = got["retry"].get("pass_waves", {}).get("max")
        if steps is not None:
            self.chunk_waves = self._chunk + steps / max(got["retry"]["passes"], 1)
        return {**got, "eviction_log": log,
                "evictions": [int(x) for x in result.evictions],
                "without_plans": self.without_plans}
