"""The arrivals-only what-if batch: one resident ``WhatIfEngine`` over the
configuration's scenario set (``whatif_scenarios.sample``) on a trace with no
durations, every pod's node asked for; a batch is one ``run()``, back when
the placements of every scenario are on the host. The entry point the CLI's
``what-if`` uses for ``examples/config3_whatif_256.yaml``. ``batch`` and
``answers`` are ``engines/whatif.py``'s."""

from __future__ import annotations

import whatif_scenarios
from engines import whatif


class Engine(whatif.Engine):
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
            whatif.program_scenarios(
                config, whatif_scenarios.sample(config, ec.num_nodes, count)),
            FrameworkConfig(), wave_width=eng["waveWidth"],
            chunk_waves=chunk_waves, collect_assignments=True)
        # The cell times the v3 chunk program of the whole plugin set with
        # nothing released, at the configuration's chunk. Refuse here, before
        # any batch: the v2 fallback is another program four times slower,
        # and a release path means the trace grew durations.
        if self.engine.engine != "v3":
            raise RuntimeError(
                f"the what-if engine fell back to {self.engine.engine!r}; "
                "the cell runs the v3 engine")
        path = self.engine.release_path
        if path is not None:
            raise RuntimeError(
                f"the what-if engine releases on the {path!r} path; the "
                "configuration has no durations (arrivals only)")
        if self.engine.chunk_waves != chunk_waves:
            raise RuntimeError(
                f"the program runs a chunk of {self.engine.chunk_waves} "
                f"waves, the configuration states {chunk_waves}")
