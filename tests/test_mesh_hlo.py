"""The ×8 projection's missing evidence link (VERDICT r4 next #5 /
missing #1): the compiled mesh-sharded chunk program must contain NO
cross-scenario collective — a hidden all-reduce inside the chunk scan
would serialize the scenario mesh and the single-chip → v5e-8 projection
would die. SURVEY §5 asserts "collectives appear only at metric-gather
time"; this lowers the actual program on the virtual 8-device CPU mesh
(conftest forces XLA_FLAGS=--xla_force_host_platform_device_count=8) and
string-matches the optimized, SPMD-partitioned HLO. No TPU needed: the
partitioner that would insert collectives runs at compile time.

Round 10 made the mesh the DEFAULT headline configuration (8 devices ×
1024 scenarios) and moved the mesh chunk program to the
device-gather src signature with device-side releases — so this suite
now lowers those exact programs, at the headline scenario count as well
as the small smoke shape, plus the bucketed release program."""


import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubernetes_simulator_tpu.framework.framework import FrameworkConfig
from kubernetes_simulator_tpu.models.encode import PAD, encode
from kubernetes_simulator_tpu.parallel.mesh import (
    make_mesh,
    scenario_sharding,
    shard_scenario_tree,
)
from kubernetes_simulator_tpu.sim.synthetic import make_cluster, make_workload
from kubernetes_simulator_tpu.sim.whatif import WhatIfEngine, uniform_scenarios

# Optimized-HLO op names for every XLA cross-device primitive (start/done
# variants share these prefixes).
COLLECTIVE_OPS = (
    "all-reduce",
    "all-gather",
    "all-to-all",
    "collective-permute",
    "collective-broadcast",
    "reduce-scatter",
    "partition-id",
    "send",  # point-to-point would be just as serializing
    "recv",
)


def test_detector_catches_real_collective():
    """Positive control: on this same mesh, a genuine cross-shard
    reduction MUST show up as an all-reduce in the compiled text — else
    the no-collectives assertions below would be vacuous (they were,
    until the mesh size guard: a 1-device mesh compiles everything
    collective-free)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from kubernetes_simulator_tpu.parallel.mesh import SCENARIO_AXIS

    mesh = make_mesh()
    assert mesh.devices.size == 8, "virtual 8-device mesh missing"
    f = jax.jit(
        lambda x: jnp.sum(x, axis=0),
        in_shardings=(NamedSharding(mesh, P(SCENARIO_AXIS)),),
        out_shardings=NamedSharding(mesh, P()),
    )
    txt = f.lower(jax.ShapeDtypeStruct((8, 16), jnp.float32)).compile().as_text()
    assert "all-reduce" in txt
    # the program's own counter (``summary()["mesh"]["collectives"]``) sees
    # it too, and every primitive this file looks for
    from kubernetes_simulator_tpu.parallel import mesh as M

    assert any("all-reduce" in ln for ln in M.collective_lines(txt))
    assert set(COLLECTIVE_OPS) == set(M.COLLECTIVE_OPS)


def _mesh_engine(S: int, with_durations: bool) -> WhatIfEngine:
    cluster = make_cluster(12, seed=21, taint_fraction=0.2)
    # Durations short enough (and the pod stream long enough) that at
    # least one static release bucket lands inside the chunk horizon —
    # the release program below must have something to lower.
    pods, _ = make_workload(
        96 if with_durations else 48, seed=21, with_affinity=True,
        with_spread=True, with_tolerations=True,
        duration_mean=10.0 if with_durations else None,
    )
    ec, ep = encode(cluster, pods)
    scen = uniform_scenarios(ec, S, seed=21, p_capacity=0.5, p_taint=0.3)
    mesh = make_mesh()
    assert mesh.devices.size == 8, "virtual 8-device mesh missing"
    return WhatIfEngine(
        ec, ep, scen, FrameworkConfig(), mesh=mesh, chunk_waves=4
    )


def _chunk_args(eng: WhatIfEngine, with_durations: bool):
    """Reproduce run()'s first-chunk argument assembly for the mesh src
    path (round 10: device-gathered slots, device-side releases when
    durations are on) — dc/states scenario-sharded, sources replicated."""
    idx = eng.waves.idx
    C = min(eng.chunk_waves, max(idx.shape[0], 1))
    pad_to = ((idx.shape[0] + C - 1) // C) * C
    if pad_to != idx.shape[0]:
        idx = np.concatenate(
            [idx, np.full((pad_to - idx.shape[0], idx.shape[1]), PAD, np.int32)]
        )
    dc = shard_scenario_tree(eng.mesh, eng.sset.dc)
    states = shard_scenario_tree(eng.mesh, eng._init_states())
    srcs = eng._slot_srcs
    assert srcs is not None, "v3 mesh engine should pre-stage slot sources"
    idx0 = jnp.asarray(idx[:C])
    if not with_durations:
        return (dc, states, srcs[0], srcs[1], idx0), None
    # Completions-on (the north-star semantics): since round 10 the mesh
    # takes the DEVICE-release path — releases must not push the chunk
    # program into host folds, and must themselves stay collective-free.
    assert eng._completions_dev, (
        "device-release path should engage under a mesh (round 10)"
    )
    stg = eng._stage_dev_rel(idx, C)
    vassign = jax.jit(
        lambda a: jnp.broadcast_to(a[None], (eng.S,) + a.shape),
        out_shardings=scenario_sharding(eng.mesh),
    )(stg["va"])
    args = (dc, states, srcs[0], srcs[1], idx0, stg["b_c"][0], vassign)
    rel = None
    for rc in stg["rel_calls"]:
        if rc is not None:
            rounds = jax.device_put(
                jnp.zeros(eng.S, jnp.int32), scenario_sharding(eng.mesh)
            )
            rel = (states, vassign, rounds) + rc
            break
    return args, rel


def _assert_no_collectives(txt: str) -> None:
    assert "ENTRY" in txt  # sanity: this is real HLO, not an empty string
    lines = txt.splitlines()
    hits = [
        ln.strip()
        for ln in lines
        for op in COLLECTIVE_OPS
        if f" {op}" in ln or ln.lstrip().startswith(op)
    ]
    assert not hits, (
        "mesh chunk program contains cross-device collectives — the "
        f"scenario axis is no longer embarrassingly parallel:\n"
        + "\n".join(hits[:10])
    )


# 8 = smoke shape; 1024 = the round-10 headline (8 devices × 128
# scenarios/device). The partitioner runs at compile time, so this pins
# the SHIPPED configuration collective-free, not just a toy.
@pytest.mark.parametrize(
    "S", [8, pytest.param(1024, marks=pytest.mark.slow)])
def test_mesh_chunk_program_has_no_collectives(S):
    eng = _mesh_engine(S, with_durations=False)
    args, _ = _chunk_args(eng, with_durations=False)
    _assert_no_collectives(eng._chunk_fn.lower(*args).compile().as_text())


@pytest.mark.parametrize(
    "S", [8, pytest.param(1024, marks=pytest.mark.slow)])
def test_mesh_chunk_program_no_collectives_with_completions(S):
    """The completions-on shape (the north-star semantics): releases run
    on-device under mesh since round 10, so both the chunk program and
    the bucketed release program must be collective-free."""
    eng = _mesh_engine(S, with_durations=True)
    args, rel = _chunk_args(eng, with_durations=True)
    _assert_no_collectives(eng._chunk_fn.lower(*args).compile().as_text())
    assert rel is not None, "expected at least one static release bucket"
    rel_fn = eng._release_fn(rel[2].shape[0])
    _assert_no_collectives(rel_fn.lower(*rel).compile().as_text())


def test_mesh_handback_program_has_no_collectives():
    """The arrivals-only hand-back under a mesh (``collect_assignments`` on a
    trace with no durations): the chunks' choices, sharded on the scenario
    axis, strung together and put into task order by one static map. It is
    jitted plainly, so the partitioner decides: it has to keep every
    scenario's row on its device. The engine's own count, read once from the
    compiled programs, agrees."""
    cluster = make_cluster(12, seed=21, taint_fraction=0.2)
    pods, _ = make_workload(48, seed=21, with_tolerations=True,
                            gang_fraction=0.2, gang_size=4,
                            extended_resource=("google.com/tpu", 8, 0.2))
    ec, ep = encode(cluster, pods)
    scen = uniform_scenarios(ec, 8, seed=21, p_capacity=0.5, p_taint=0.3)
    eng = WhatIfEngine(ec, ep, scen, FrameworkConfig(), mesh=make_mesh(),
                       chunk_waves=4, collect_assignments=True,
                       telemetry="summary")
    res = eng.run()
    assert res.fleet_telemetry.summary()["mesh"]["collectives"] == {
        "chunk": 0, "handback": 0, "gather": 1}
    args, _ = _chunk_args(eng, with_durations=False)
    _, out = eng._chunk_fn(*args)
    n_chunks = eng.waves.idx.shape[0] // 4 + bool(eng.waves.idx.shape[0] % 4)
    txt = eng._run_jits["handback"].lower([out] * n_chunks).compile().as_text()
    _assert_no_collectives(txt)
    assert "s32[1,48]" in txt  # one scenario's row a device, in task order


def test_mesh_retry_chunk_program_has_no_collectives():
    """A batch with a ``retry_buffer`` under a mesh: every retry pass ends
    with the fullest scenario's last queued wave, a ``pmax`` over the vmapped
    scenarios of ONE device's slice (``shard_map`` outside, ``vmap`` inside),
    so each device's passes end on their own and NEITHER of the boundary's
    two programs, the pass ``jit_per_scenario_retry`` and the arrival scan
    ``jit_per_scenario_arrivals`` (PR 47), holds a cross-device instruction;
    the engine's own count, read from the compiled programs, agrees."""
    cluster = make_cluster(3, seed=11)
    pods, _ = make_workload(140, seed=11, arrival_rate=60.0, duration_mean=1.5,
                            with_spread=True, with_tolerations=True)
    ec, ep = encode(cluster, pods)
    scen = uniform_scenarios(ec, 16, seed=21, p_capacity=0.5, p_taint=0.3)
    eng = WhatIfEngine(ec, ep, scen, FrameworkConfig(), mesh=make_mesh(),
                       wave_width=4, chunk_waves=4, retry_buffer=16,
                       collect_assignments=True, telemetry="summary")
    assert eng.release_path == "device"
    calls = {}
    for attr in ("_retry_fn", "_chunk_fn"):
        real = getattr(eng, attr)

        def program(*args, _attr=attr, _real=real):
            if _attr not in calls:  # taken before the call: it donates its buffers
                calls[_attr] = _real.lower(*args)
            return _real(*args)

        program.lower = real.lower  # the engine's own count lowers it again
        setattr(eng, attr, program)
    got = eng.run().fleet_telemetry.summary()
    assert got["mesh"]["devices"] == 8 and got["mesh"]["scenarios_per_device"] == 2
    # (the retry hand-back is one program over the whole batch, PR 43: it is
    # not among the programs the engine counts)
    assert got["mesh"]["collectives"] == {"retry": 0, "chunk": 0}
    # the passes ran, and not to the end of the buffer
    retry = got["retry"]
    assert 0 < retry["pass_waves"]["max"] < retry["passes"] * 16 // 4
    assert retry["retry_placed"]["max"] > 0
    assert "jit_per_scenario_retry" in calls["_retry_fn"].as_text()
    assert "jit_per_scenario_arrivals" in calls["_chunk_fn"].as_text()
    for lowered in calls.values():
        _assert_no_collectives(lowered.compile().as_text())
