"""TPU mesh + scenario sharding (SURVEY.md §2 parallelism mapping).

The what-if scenario axis is the framework's data-parallel axis: S perturbed
cluster states shard over a ``jax.sharding.Mesh`` of TPU devices
(`scenarios` axis), each device scanning the same pod stream against its
local scenarios. Collectives (the XLA-compiled equivalents of the
reference-world's NCCL) appear only at metric-gather time — one ``psum`` /
``all_gather`` over ICI per replay, exactly as SURVEY.md §5 prescribes.

Multi-host (DCN) scaling (round 11, parallel.dcn) localizes rather than
spans: ``init_distributed()`` brings up ``jax.distributed``, the engine
slices the scenario axis into contiguous per-process blocks and runs the
chunk loop over a process-LOCAL mesh (``dcn.localize_mesh``), and the
processes combine results exactly once per replay via a host-side gather
over the coordination service — still one collective per replay, now with
zero DCN traffic inside the chunk loop.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

SCENARIO_AXIS = "scenarios"

# Optimized-HLO op names of every XLA cross-device primitive (the start and
# done variants share these prefixes); point-to-point would serialize the
# scenario mesh just as a reduction would.
COLLECTIVE_OPS = (
    "all-reduce", "all-gather", "all-to-all", "collective-permute",
    "collective-broadcast", "reduce-scatter", "partition-id", "send", "recv",
)


def init_distributed(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> None:
    """Multi-host bring-up over DCN ([K8S]-world has no equivalent; this is
    the TPU-native answer to a distributed communication backend). No-op for
    single-process runs.

    With survivor recovery on (``KSIM_DCN_RECOVER``, round 15) the
    coordination service's OWN failure detector is widened past the
    gather deadline: its default ~100s tolerance would propagate a fatal
    error that aborts every healthy task while a survivor is still
    rebalancing the dead process's block. parallel.dcn's liveness
    beacons (KSIM_DCN_STALL_S) stay the fast detector. The round-18
    work-stealing queue widens it for the same reason: a straggling or
    deferred-join process must not be declared dead by the runtime while
    the queue is still racing a speculative re-execution against it."""
    if not (num_processes and num_processes > 1):
        return
    from . import dcn

    heartbeat_timeout_s = 100  # jax.distributed's own default
    if dcn.recover_enabled() or dcn.wq_enabled():
        import os

        # Twice the gather deadline, never below the runtime's default.
        timeout_s = float(os.environ.get("KSIM_DCN_TIMEOUT_S", "300"))
        heartbeat_timeout_s = max(int(2 * timeout_s), heartbeat_timeout_s)
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
        heartbeat_timeout_seconds=heartbeat_timeout_s,
    )


def make_mesh(num_devices: Optional[int] = None, axis: str = SCENARIO_AXIS) -> Mesh:
    """1-D device mesh over the scenario axis. ``num_devices`` defaults to
    all visible devices (TPU slice, or the CPU virtual devices in tests)."""
    devs = jax.devices()
    if num_devices is not None:
        devs = devs[:num_devices]
    return Mesh(np.array(devs), (axis,))


def spans_processes(mesh: Optional[Mesh]) -> bool:
    """True when ``mesh`` contains devices this process cannot address —
    i.e. it is a cross-process (DCN) mesh. The engine localizes such
    meshes (parallel.dcn.localize_mesh) before the chunk loop; result
    paths branch on this instead of the blunt ``process_count() > 1``
    (a local mesh inside a multi-process run is the common round-11
    case and needs no global-array plumbing)."""
    if mesh is None:
        return False
    me = jax.process_index()
    return any(d.process_index != me for d in mesh.devices.flat)


def scenario_sharding(mesh: Mesh, axis: str = SCENARIO_AXIS) -> NamedSharding:
    """Shard the leading (scenario) dimension; replicate the rest."""
    return NamedSharding(mesh, P(axis))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def _global_put(a, sh: NamedSharding):
    """Host copy → global array for a multi-process mesh. Built from each
    process's local data via make_array_from_callback: device_put's
    cross-process consistency check compares values with ``==``, which NaN
    entries (numeric-label slots) always fail even though every process
    holds identical bytes."""
    a = np.asarray(a)
    return jax.make_array_from_callback(a.shape, sh, lambda idx: a[idx])


def shard_scenario_tree(mesh: Mesh, tree, axis: str = SCENARIO_AXIS):
    """device_put every leaf with its leading dim sharded over the mesh.

    Multi-process (DCN): leaves are pulled back to host and re-emitted as
    global arrays — device_put from a single-device array to a sharding
    spanning non-addressable devices is not defined."""
    sh = scenario_sharding(mesh, axis)
    if spans_processes(mesh):
        return jax.tree.map(lambda a: _global_put(a, sh), tree)
    return jax.tree.map(lambda a: jax.device_put(a, sh), tree)


def replicate_tree(mesh: Mesh, tree):
    sh = replicated(mesh)
    if spans_processes(mesh):
        return jax.tree.map(lambda a: _global_put(a, sh), tree)
    return jax.tree.map(lambda a: jax.device_put(a, sh), tree)


def tree_bytes(tree) -> int:
    """Bytes of every leaf of ``tree``, from shapes: touches no buffer."""
    return sum(
        int(np.prod(a.shape)) * np.dtype(a.dtype).itemsize
        for a in jax.tree_util.tree_leaves(tree)
    )


def collective_lines(hlo_text: str) -> list:
    """The instructions of a compiled program's HLO text that cross
    devices (:data:`COLLECTIVE_OPS`). The scenario axis is embarrassingly
    parallel: the chunk, release and hand-back programs are expected to
    hold none (tests/test_mesh_hlo.py; ``summary()["mesh"]``)."""
    return [
        ln.strip()
        for ln in hlo_text.splitlines()
        if any(f" {op}" in ln or ln.lstrip().startswith(op)
               for op in COLLECTIVE_OPS)
    ]


def fit_population(population: int, per_candidate: int, mesh: Optional[Mesh]) -> int:
    """Smallest population ≥ ``population`` whose FLAT sweep axis
    (population × per_candidate scenarios) divides over the mesh devices.

    The policy tuner (round 9, sim.tuner) evaluates its whole candidate
    population in one sweep by flattening (candidate, train-scenario)
    pairs onto the scenario axis — the same data-parallel axis the
    perturbation sweeps shard. A mesh requires that flat axis to divide
    evenly over devices (WhatIfEngine raises otherwise), so the tuner
    rounds the population UP here and fills the extra rows with fresh
    samples rather than failing or silently truncating — and LOGS the
    padding (no silent caps): callers surface the requested vs. fitted
    sizes in their result metadata (TuneResult.population_requested,
    WhatIfResult.n_devices).

    DCN case (round 11): the flat axis must divide
    ``process_count × local_devices`` — each process takes a contiguous
    1/process_count block of the flat axis, and its LOCAL slice must in
    turn divide its local mesh devices. A mesh that already spans
    processes counts its devices once; a process-local mesh in a
    multi-process run is scaled by ``process_count``; even a mesh-less
    DCN sweep must divide ``process_count`` for the slicing to be even.
    The padding log names the DCN factorization so operators see why the
    population grew."""
    requested = population = max(int(population), 1)
    nproc = jax.process_count()
    if mesh is None:
        if nproc <= 1:
            return population
        ndev, label = nproc, f"{nproc} processes (no mesh)"
    elif spans_processes(mesh):
        ndev = int(mesh.devices.size)
        label = f"{ndev} mesh devices across {nproc} processes"
    elif nproc > 1:
        local = int(mesh.devices.size)
        ndev = local * nproc
        label = f"{nproc} processes x {local} local mesh devices = {ndev}"
    else:
        ndev = int(mesh.devices.size)
        label = f"{ndev} mesh devices"
    while (population * per_candidate) % ndev:
        population += 1
    if population != requested:
        from ..utils.metrics import log

        log.info(
            "fit_population: padded population %d -> %d (+%d rows) so the "
            "flat axis (%d x %d) divides over %s",
            requested, population, population - requested,
            population, per_candidate, label,
        )
    return population
