"""The what-if batch of a cell that is full: ``engines/whatif.py``'s adapter
with the configuration's ``retryBuffer`` and the second answer array. One
resident ``WhatIfEngine`` over the configuration's scenario set, completions
on, the device retry pass at every chunk boundary, every task's node AND the
boundary that bound it asked for; a batch is one ``run()``, back when both
arrays of every scenario are on the host.

``offered`` counts the arriving tasks (the residents are bound before the
window). ``chunk_waves`` is what the per-wave metrics divide a chunk call's
device time by: a call executes the chunk's waves and the retry pass's
``retryBuffer / waveWidth`` wave steps, so it is their sum; the refusal on
the program's chunk holds the configuration's own.
"""

from __future__ import annotations

import numpy as np

import whatif_scenarios
from engines import whatif


class Engine(whatif.Engine):
    def __init__(self, ec, ep, config: dict, traffic: dict, chunk_waves: int):
        from kubernetes_simulator_tpu.framework.framework import FrameworkConfig
        from kubernetes_simulator_tpu.sim.whatif import WhatIfEngine

        eng = config["engine"]
        # A node table smaller than the deployment's is a rehearsal, which
        # takes its scenario count and its buffer from the traffic's
        # ``rehearse`` sizes.
        rehearsal = ec.num_nodes < config["cluster"]["nodes"]
        count = int((traffic["rehearse"] if rehearsal else traffic)["scenarios"])
        self.retry_buffer = int(traffic["rehearse"]["retryBuffer"] if rehearsal
                                else eng["retryBuffer"])
        self.offered = int((np.asarray(ep.bound_node) < 0).sum())
        self.scenarios_per_chip = count
        self.engine = WhatIfEngine(
            ec, ep,
            whatif.program_scenarios(
                config, whatif_scenarios.sample(config, ec.num_nodes, count)),
            FrameworkConfig(), wave_width=eng["waveWidth"],
            chunk_waves=chunk_waves, completions=True,
            retry_buffer=self.retry_buffer, collect_assignments=True)
        path = getattr(self.engine, "release_path", None)
        if path != "device":
            raise RuntimeError(
                "the what-if engine is not on the device-release path "
                f"(release_path = {path!r}) with placements asked for")
        if self.engine.chunk_waves != chunk_waves:
            raise RuntimeError(
                f"the program runs a chunk of {self.engine.chunk_waves} "
                f"waves, the configuration states {chunk_waves}")
        if self.engine.retry_buffer != self.retry_buffer:
            raise RuntimeError(
                f"the program runs a retry buffer of {self.engine.retry_buffer}, "
                f"the configuration states {self.retry_buffer}")
        self.chunk_waves = chunk_waves + self.retry_buffer // eng["waveWidth"]
        self._first_bind_boundary = None

    def answers(self, result) -> dict:
        """What a batch answered, as plain host data: ``engines/whatif.py``'s
        and, [S, P] beside the nodes, the boundary whose retry pass bound
        each task (``bind_boundary``; -1 its arrival wave, below that no
        node and why), with the buffer the program ran and its
        ``summary()["retry"]``. The harness compares ``assignments``
        batch against batch; two batches that differ in ``bind_boundary``
        raise here."""
        retry = result.fleet_telemetry.summary().get("retry")
        if retry is None:
            raise RuntimeError("the batch's summary() has no 'retry' block")
        if result.bind_boundary is None:
            raise RuntimeError("the batch handed back no bind_boundary")
        bind = np.ascontiguousarray(result.bind_boundary, dtype=np.int32)
        if self._first_bind_boundary is None:
            self._first_bind_boundary = bind
        elif not np.array_equal(bind, self._first_bind_boundary):
            raise RuntimeError("two batches differ in bind_boundary")
        return {**super().answers(result), "bind_boundary": bind,
                "retry_buffer": self.retry_buffer, "retry": retry}
