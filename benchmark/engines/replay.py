"""The single replay: one resident ``JaxReplayEngine``; a batch is one
``replay()`` of the whole trace, back when its results are on the host."""

from __future__ import annotations

import numpy as np


class Engine:
    def __init__(self, ec, ep, config: dict, traffic: dict, chunk_waves: int):
        from kubernetes_simulator_tpu.framework.framework import FrameworkConfig
        from kubernetes_simulator_tpu.sim.jax_runtime import JaxReplayEngine

        eng = config["engine"]
        self.offered = int(ep.num_pods)
        self.engine = JaxReplayEngine(
            ec, ep, FrameworkConfig(), wave_width=eng["waveWidth"],
            chunk_waves=chunk_waves,
            completions=True if eng.get("completions") else None)
        # The program's granularity guard may run a smaller chunk than it is
        # given, and says so only in a warning; the reference replays the
        # configuration's chunk, so such a run comes out not correct.
        self.chunk_waves = chunk_waves
        self.scenarios_per_chip = 1

    def batch(self):
        """The one timed call."""
        return self.engine.replay()

    def answers(self, result) -> dict:
        """What a batch answered, as plain host arrays: per scenario the
        placed and unschedulable counts, and every task's node (-1 none)."""
        return {
            "placed": [int(result.placed)],
            "unschedulable": [int(result.unschedulable)],
            "assignments": np.ascontiguousarray(result.assignments, dtype=np.int32),
        }
