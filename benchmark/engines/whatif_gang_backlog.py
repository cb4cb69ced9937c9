"""The what-if batch of a shared GPU training cluster with a standing job
queue: ``engines/whatif_backlog.py``'s adapter (one resident ``WhatIfEngine``,
completions on, the device retry pass at every chunk boundary, every pod's
node AND the boundary that bound it handed back) with the scheduler profile's
``retry_groups`` on: a queue entry belongs to a JOB, a rolled-back job joins
whole and is tried again whole, a job wider than the wave is one transaction
in the pass as at its arrival.

Refuses, before any batch, a run off the device-release path, at another chunk
or buffer than the configuration's, or with the setting off; two batches that
differ in any answer (``bind_boundary`` or a counter; the harness compares
``assignments``) raise.

``chunk_waves`` is what the per-wave metrics divide a chunk call's device time
by. A pass here is as long as its queue (every job from a fresh wave), not the
buffer, so the adapter states the chunk's own waves alone: ``chunk_ms_per_wave``
is then a boundary's device time (both programs) over the chunk's ARRIVAL
waves, and the pass's own cost per executed wave is ``gangq_retry_roofline``'s
business (PERF.md §3).
"""

from __future__ import annotations

import numpy as np

import whatif_scenarios
from engines import whatif, whatif_backlog


class Engine(whatif_backlog.Engine):
    def __init__(self, ec, ep, config: dict, traffic: dict, chunk_waves: int):
        from kubernetes_simulator_tpu.framework.framework import FrameworkConfig
        from kubernetes_simulator_tpu.sim.whatif import WhatIfEngine

        eng = config["engine"]
        if not eng.get("retryGroups"):
            raise RuntimeError("the configuration does not turn retryGroups on")
        # A node table smaller than the deployment's is a rehearsal, which
        # takes its scenario count and its buffer from the traffic's
        # ``rehearse`` sizes.
        rehearsal = ec.num_nodes < config["cluster"]["nodes"]
        count = int((traffic["rehearse"] if rehearsal else traffic)["scenarios"])
        self.retry_buffer = int(traffic["rehearse"]["retryBuffer"] if rehearsal
                                else eng["retryBuffer"])
        self.offered = int(ep.num_pods)
        self.scenarios_per_chip = count
        self.chunk_waves = chunk_waves
        self.engine = WhatIfEngine(
            ec, ep,
            whatif.program_scenarios(
                config, whatif_scenarios.sample(config, ec.num_nodes, count)),
            FrameworkConfig(), wave_width=eng["waveWidth"],
            chunk_waves=chunk_waves, completions=True,
            retry_buffer=self.retry_buffer, retry_groups=True,
            collect_assignments=True)
        for what, runs, stated in (
            ("release path", self.engine.release_path, "device"),
            ("chunk", self.engine.chunk_waves, chunk_waves),
            ("retry buffer", self.engine.retry_buffer, self.retry_buffer),
            ("retry_groups", self.engine.retry_groups, True),
        ):
            if runs != stated:
                raise RuntimeError(f"the program runs {what} {runs!r}, the "
                                   f"configuration states {stated!r}")
        self._first_bind_boundary = self._first_groups = None
        self.classes = config["workload"]["jobSizeClasses"]

    def answers(self, result) -> dict:
        """``engines/whatif_backlog.py``'s and, per scenario, the counters of
        the job queue (``groups``: ``sim.waves.GROUP_COUNTERS``, the queue's
        greatest depth, the pods dropped, and the program's waits by job
        size (``sim.waves.job_waits``) summed into the deployment's size
        classes, ``workload.jobSizeClasses``: name -> the largest size)."""
        out = super().answers(result)
        if result.group_counts is None or result.job_waits is None:
            raise RuntimeError("the batch handed back no group_counts")
        retry = result.fleet_telemetry.summary()["retry"]
        groups = {k: np.asarray(v, np.int64)
                  for k, v in result.group_counts.items()}
        waits, above = result.job_waits, 0
        for name, largest in self.classes.items():
            of = (waits["size"] > above) & (waits["size"] <= largest)
            groups[f"bound_pass_{name}"] = waits["bound_pass"][:, of].sum(1)
            groups[f"wait_sum_{name}"] = waits["wait_sum"][:, of].sum(1)
            groups[f"wait_max_{name}"] = waits["wait_max"][:, of].max(
                1, initial=0)
            above = largest
        if self._first_groups is None:
            self._first_groups = groups
        elif any(not np.array_equal(groups[k], self._first_groups[k])
                 for k in groups):
            raise RuntimeError("two batches differ in a job-queue counter")
        return {**out, "groups": groups, "pass_waves": retry["pass_waves"]["max"]}
