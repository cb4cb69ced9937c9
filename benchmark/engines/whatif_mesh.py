"""The what-if batch over a mesh of chips: one resident ``WhatIfEngine`` over
the configuration's scenario set (``whatif_scenarios.sample``), the scenario
axis sharded over the traffic's ``chips`` devices (``parallel.mesh.make_mesh``),
on a trace with no durations, every pod's node asked for; a batch is one
``run()``, back when the placements of every scenario are on the host. The
entry point the CLI's ``what-if`` uses for
``examples/config5_multitenant_mesh.yaml`` (``whatIf.mesh: true``). ``batch``
and ``answers`` are ``engines/whatif.py``'s."""

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
            chunk_waves=chunk_waves, mesh=make_mesh(chips),
            collect_assignments=True)
        # The cell times the v3 chunk program under shard_map with nothing
        # released, at the configuration's chunk, on a mesh of the cell's
        # chips. Refuse here, before any batch: the v2 fallback is another
        # program, a release path means the trace grew durations, and an
        # engine that dropped or shrank its mesh measures one chip.
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
        mesh = self.engine.mesh
        held = 0 if mesh is None else int(mesh.devices.size)
        if held != chips:
            raise RuntimeError(
                f"the what-if engine's mesh holds {held} devices, the cell "
                f"runs on {chips}")
