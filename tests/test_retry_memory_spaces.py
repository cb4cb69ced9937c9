"""Where the compiler puts the wave step's node planes in the two programs
of a retry boundary (PR 47), with no chip: the drain cell's engine, and the
budgeted drain's (PR 49), at the
cell's own size (128 plans x 10,000 nodes, ``retry_buffer`` 8,192), both
programs compiled for a DESCRIBED v5e, and the memory space of the planes
the state-carrying loop of each carries read from the compiled text
(``utils.profiling.loop_memory_spaces``). Held in ONE program, the pass loop
followed by the arrival scan left ``used``, ``allocatable`` and the
toleration class mask in HBM, and every slot's node-wide reduce read 35.8 MB
at HBM speed (a flat 47-50 us a slot on the chip; PERF.md §5, §7).

The TPU's library loads in one process at a time: the topology is described
inside a fixture, in the test's own process, and the test skips where that
cannot be done. This is the one file that does it."""

import sys
import time
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "benchmark")]

import run as bench  # noqa: E402

from kubernetes_simulator_tpu.utils import profiling  # noqa: E402

CELLS = ("borg10k-drain128", "borg10k-budget128", "pai1800-gangqueue256")
LIMIT_S = 900.0  # prepare 5 s + compiles of 10 s and 40 s on a free host


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        # keyword arguments: jax 0.9.0 raises NotImplementedError on
        # positional ones
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


class _Captured(Exception):
    pass


def _first_boundary(eng):
    """{attr: (the jitted program, its first call's arguments as shapes)}
    of the boundary's two programs: the first answers with the shapes of its
    outputs (nothing runs at the cell's size on the CPU), the second ends
    the batch."""
    calls = {}
    for attr in ("_retry_fn", "_record_fn", "_chunk_fn"):
        real = getattr(eng, attr)
        if real is None:  # ``_record_fn``: only under ``retry_groups``
            continue

        def spy(*args, _attr=attr, _real=real):
            calls[_attr] = (_real, profiling.shape_structs(args))
            if _attr == "_chunk_fn":
                raise _Captured
            return jax.eval_shape(_real, *calls[_attr][1])

        spy.__name__ = real.__name__
        setattr(eng, attr, spy)
    with pytest.raises(_Captured):
        eng.run()
    return calls


@pytest.mark.parametrize("cell", CELLS)
def test_each_program_of_a_retry_boundary_keeps_the_node_planes_on_chip(
        one_chip, cell):
    """The drain cell's engine, and the budgeted drain's: its eviction
    program alone holds the planes that say which nodes are out and until
    when, so the two loop programs read the ONE ``down`` mask they read in
    the drain cell (a fourth plane carried into them is what tipped the
    compiler, PR 47). And the job-queue cell's (PR 54), whose pass loop
    carries the open transaction's ``[R, N]`` plane beside ``used`` (the one
    shape twice in the tuple: the lesser space is read); the pass's row is
    appended to the record's log by a third program between the two, because
    that write, made in the pass program, put ``allocatable`` in HBM."""
    deadline = time.monotonic() + LIMIT_S
    _, _, config, traffic = bench.load_cell(cell)
    _, _, adapter = bench.prepare(config, traffic, 7, False, {})
    eng = adapter.engine
    calls = _first_boundary(eng)
    S, N, R = calls["_chunk_fn"][1][0].allocatable.shape
    K = jax.tree.leaves(eng.rep_slots[0])[0].shape[0]
    planes = {"used": f"f32[{S},{R},{N}]", "allocatable": f"f32[{S},{N},{R}]",
              "class mask": f"bf16[{S},{K},{N}]"}
    loops = {"_retry_fn": "jit(per_scenario_retry)/vmap(ksim.retry)/while",
             "_chunk_fn": "jit(per_scenario_arrivals)/vmap()/while"}
    for attr in loops:  # the record's append (PR 54) carries no wave step
        fn, structs = calls[attr]
        if time.monotonic() > deadline:
            pytest.skip(f"over {LIMIT_S:.0f} s on this host before {attr}")
        on_chip = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), structs)
        try:
            text = fn.lower(*on_chip).compile().as_text()
        except Exception as e:
            if "libtpu" in str(e) or "lockfile" in str(e):
                pytest.skip(f"another process holds the TPU's library: {e}")
            raise
        got = profiling.loop_memory_spaces(text, planes.values())
        # ONE loop of the program carries all three planes: the wave step's
        assert set(got) == {loops[attr]}, (attr, got)
        assert got[loops[attr]] == {shape: 1 for shape in planes.values()}, (
            f"{attr}: a node plane of the wave step's loop is not in S(1), "
            f"the chip's on-chip memory: {got}")
