"""The what-if batch: one resident ``WhatIfEngine`` over the configuration's
scenario set (``whatif_scenarios.sample``), completions on, every task's
node asked for; a batch is one ``run()``, back when the placements of every
scenario are on the host."""

from __future__ import annotations

import numpy as np

import whatif_scenarios


def program_scenarios(config: dict, plain: list) -> list:
    """The plain scenarios as the program's ``Scenario`` objects."""
    from kubernetes_simulator_tpu.sim.whatif import Perturbation, Scenario

    spec = config["scenarios"]
    out = []
    for sc in plain:
        pts = []
        if len(sc["down"]):
            pts.append(Perturbation("node_down", nodes=sc["down"]))
        if len(sc["scaled"]):
            pts.append(Perturbation("scale_capacity", nodes=sc["scaled"],
                                    resource="cpu", factor=sc["factor"]))
        if len(sc["tainted"]):
            pts.append(Perturbation(
                "add_taint", nodes=sc["tainted"], key=spec["taintKey"],
                value=spec["taintValue"], effect=spec["taintEffect"]))
        out.append(Scenario(pts))
    return out


class Engine:
    def __init__(self, ec, ep, config: dict, traffic: dict, chunk_waves: int):
        from kubernetes_simulator_tpu.framework.framework import FrameworkConfig
        from kubernetes_simulator_tpu.sim.whatif import WhatIfEngine

        eng = config["engine"]
        # A node table smaller than the deployment's is a rehearsal, which
        # takes its scenario count from the traffic's ``rehearse`` sizes.
        rehearsal = ec.num_nodes < config["cluster"]["nodes"]
        count = int((traffic["rehearse"] if rehearsal else traffic)["scenarios"])
        self.offered = int(ep.num_pods)
        self.chunk_waves = chunk_waves
        self.scenarios_per_chip = count
        self.engine = WhatIfEngine(
            ec, ep,
            program_scenarios(
                config, whatif_scenarios.sample(config, ec.num_nodes, count)),
            FrameworkConfig(), wave_width=eng["waveWidth"],
            chunk_waves=chunk_waves, completions=True,
            collect_assignments=True)
        # The cell times the device-release path at the configuration's
        # chunk, and the reference replays that chunk. Refuse here, before
        # any batch: a tree whose engine leaves that path when asked for
        # placements would run minutes of another program.
        path = getattr(self.engine, "release_path", None)
        if path != "device":
            raise RuntimeError(
                "the what-if engine is not on the device-release path "
                f"(release_path = {path!r}) with placements asked for")
        if self.engine.chunk_waves != chunk_waves:
            raise RuntimeError(
                f"the program runs a chunk of {self.engine.chunk_waves} "
                f"waves, the configuration states {chunk_waves}")

    def batch(self):
        """The one timed call."""
        return self.engine.run()

    def answers(self, result) -> dict:
        """What a batch answered, as plain host data: per scenario the
        placed and unschedulable counts (the device's own), and every
        task's node [S, P] (-1 none)."""
        return {
            "placed": [int(x) for x in result.placed],
            "unschedulable": [int(x) for x in result.unschedulable],
            "assignments": np.ascontiguousarray(result.assignments, dtype=np.int32),
        }
