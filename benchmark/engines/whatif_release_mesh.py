"""The what-if batch WITH completions over a mesh of chips: ``engines/whatif.py``'s
adapter (one resident ``WhatIfEngine`` over the configuration's scenario set,
completions on, every task's node asked for) with the scenario axis sharded
over the traffic's ``chips`` devices (``parallel.mesh.make_mesh``): the
device release program and the placement buffer's hand-back run under
``shard_map``, each chip on its own scenarios. A batch is one ``run()``, back
when the placements of every scenario are on the host. ``batch`` and
``answers`` are ``engines/whatif.py``'s."""

from __future__ import annotations

import whatif_scenarios
from engines import whatif


class Engine(whatif.Engine):
    def __init__(self, ec, ep, config: dict, traffic: dict, chunk_waves: int):
        import jax

        from kubernetes_simulator_tpu.framework.framework import FrameworkConfig
        from kubernetes_simulator_tpu.parallel.mesh import make_mesh
        from kubernetes_simulator_tpu.sim.whatif import WhatIfEngine

        eng, chips = config["engine"], int(traffic["chips"])
        # A node table smaller than the deployment's is a rehearsal, which
        # takes its scenario count from the traffic's ``rehearse`` sizes.
        rehearsal = ec.num_nodes < config["cluster"]["nodes"]
        count = int((traffic["rehearse"] if rehearsal else traffic)["scenarios"])
        have = jax.devices()
        if len(have) < chips:
            raise RuntimeError(
                f"the cell shards its scenarios over {chips} devices, JAX shows "
                f"{len(have)} x {have[0].platform!r}; for a rehearsal on the CPU "
                f"set XLA_FLAGS=--xla_force_host_platform_device_count={chips}")
        if count % chips:
            raise RuntimeError(
                f"{count} scenarios do not divide over {chips} devices")
        self.offered = int(ep.num_pods)
        self.chunk_waves = chunk_waves
        self.scenarios_per_chip = count // chips
        self.engine = WhatIfEngine(
            ec, ep,
            whatif.program_scenarios(
                config, whatif_scenarios.sample(config, ec.num_nodes, count)),
            FrameworkConfig(), wave_width=eng["waveWidth"],
            chunk_waves=chunk_waves, mesh=make_mesh(chips), completions=True,
            collect_assignments=True)
        # The cell times the device-release path under shard_map, at the
        # configuration's chunk, on a mesh of the cell's chips. Refuse here,
        # before any batch: off that path the release program is the host's,
        # and an engine that dropped or shrank its mesh measures one chip.
        path = getattr(self.engine, "release_path", None)
        if path != "device":
            raise RuntimeError(
                "the what-if engine is not on the device-release path "
                f"(release_path = {path!r}) under a mesh of {chips}")
        if self.engine.chunk_waves != chunk_waves:
            raise RuntimeError(
                f"the program runs a chunk of {self.engine.chunk_waves} "
                f"waves, the configuration states {chunk_waves}")
        mesh = self.engine.mesh
        held = 0 if mesh is None else int(mesh.devices.size)
        if held != chips:
            raise RuntimeError(
                f"the what-if engine's mesh holds {held} devices, the cell "
                f"runs on {chips}")
