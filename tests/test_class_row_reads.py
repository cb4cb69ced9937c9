"""A slot's toleration / node-affinity class row (ops.tpu3): ``class_row``
in the form ``class_row_reads`` names. ``"slice"`` (a dynamic index by the
class id) wherever the slots are scenario-shared and in the single replay;
``"select"`` (a chain of selects among the plane's few static rows) only in
a step whose slots differ by scenario, the what-if retry pass, and only
while the planes have at most ``CLASS_SELECT_MAX`` rows. Both pick a row, so
they agree to the bit; the programs of every step built without
``slots_by_scenario`` are the parent's to the letter."""

import dataclasses
import hashlib

import numpy as np
import pytest

from kubernetes_simulator_tpu.framework.framework import FrameworkConfig
from kubernetes_simulator_tpu.models.core import (
    MatchExpression, NodeAffinitySpec, NodeSelectorTerm,
    PreferredSchedulingTerm,
)
from kubernetes_simulator_tpu.models.encode import PAD, encode
from kubernetes_simulator_tpu.ops import tpu3 as V3
from kubernetes_simulator_tpu.sim.jax_runtime import JaxReplayEngine, StepSpec
from kubernetes_simulator_tpu.sim.synthetic import make_cluster, make_workload
from kubernetes_simulator_tpu.sim.whatif import (
    Perturbation, Scenario, WhatIfEngine,
)

BOUND = V3.CLASS_SELECT_MAX
ROWS = [1, 2, 3, BOUND, BOUND + 1]


def _two_class_planes(seed=3, n_nodes=12, n_pods=96):
    """A tainted cluster whose pods fall into two toleration classes (30%
    tolerate ``dedicated=batch``) and two node-affinity classes (every
    third pod prefers ``tier=hot``), with short durations: both class
    planes are in the chunk program, two rows each."""
    cluster = make_cluster(n_nodes, seed=seed, taint_fraction=0.3)
    pods, _ = make_workload(
        n_pods, seed=seed, arrival_rate=60.0, duration_mean=1.5,
        with_spread=True, with_tolerations=True,
    )
    for i, node in enumerate(cluster.nodes):
        node.labels["tier"] = "hot" if i % 4 == 0 else "cold"
    prefer_hot = NodeAffinitySpec(preferred=(PreferredSchedulingTerm(
        10, NodeSelectorTerm((MatchExpression.make("tier", "In", ["hot"]),))
    ),))
    for pod in pods[::3]:
        pod.node_affinity = prefer_hot
    return encode(cluster, pods)


def _static(rows, plane):
    """The static facts of the two-class trace with ``rows`` classes in one
    of the two planes (the representatives alone are what the form reads)."""
    ec, ep = _two_class_planes()
    st = V3.V3Static.build(ec, ep, StepSpec.from_config(ec, FrameworkConfig(), ep))
    assert len(st.tol_rep) == len(st.na_rep) == 2
    return dataclasses.replace(
        st, **{f"{plane}_rep": np.arange(rows, dtype=np.int32)}
    )


@pytest.mark.parametrize("plane", ["tol", "na"])
@pytest.mark.parametrize("rows", ROWS)
def test_class_row_reads_form_follows_how_the_step_is_built(rows, plane):
    """``"slice"`` for every caller that is there (the single replay, the
    step under a scenario axis with scenario-shared slots), ``"select"``
    only for slots that differ by scenario and a plane of few rows."""
    st = _static(rows, plane)
    assert V3.class_row_reads(st) == "slice"
    assert V3.class_row_reads(st, scenario_axis=True) == "slice"
    # no scenario axis, nothing to differ by
    assert V3.class_row_reads(st, False, slots_by_scenario=True) == "slice"
    want = "select" if rows <= BOUND else "slice"
    assert V3.class_row_reads(st, True, slots_by_scenario=True) == want


def test_class_row_reads_counts_only_the_planes_the_step_reads(monkeypatch):
    """Past ``MAX_CLASSES`` the step reads no class plane of that kind at
    all (the per-wave evaluation), so its representatives do not count
    towards the bound: the other plane's rows decide."""
    st = _static(BOUND + 1, "tol")
    assert V3.class_row_reads(st, True, slots_by_scenario=True) == "slice"
    monkeypatch.setattr(V3.V3Static, "MAX_CLASSES", BOUND)
    assert not st.use_tol_classes and st.use_na_classes
    assert V3.class_row_reads(st, True, slots_by_scenario=True) == "select"


def _plane(rows, dtype, n=37, seed=0):
    """A class plane as ``class_masks`` builds it: 0/1 in bfloat16, or a
    float32 raw plane, here with the values a sum would not give back
    (-0.0, inf, a NaN with a payload, denormals)."""
    import jax.numpy as jnp

    rng = np.random.default_rng(seed + rows)
    if dtype == "bfloat16":
        return jnp.asarray(rng.integers(0, 2, (rows, n)), jnp.bfloat16)
    x = rng.normal(size=(rows, n)).astype(np.float32)
    x[:, 0], x[:, 1], x[:, 2] = -0.0, np.inf, 1e-42
    x[:, 3] = np.array([0x7FC00123], np.uint32).view(np.float32)[0]
    return jnp.asarray(x)


def _bits(x):
    x = np.asarray(x)
    return x.view({2: np.uint16, 4: np.uint32}[x.dtype.itemsize])


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("rows", ROWS)
def test_selected_class_row_is_the_dynamic_index_to_the_bit(rows, dtype):
    """Every class id a slot can carry, an empty slot's among them (its id
    is PAD and ``gather_extra_device`` hands it task 0's class): the same
    row, bit for bit, alone and with the id mapped over a scenario axis as
    the retry pass maps it."""
    import jax
    import jax.numpy as jnp

    plane = _plane(rows, dtype)
    class_of = jnp.asarray((np.arange(40) + rows - 1) % rows, jnp.int32)
    none = jnp.zeros((40, 0), jnp.int32)
    empty = V3.gather_extra_device(
        V3.ExtraSource(none, none, class_of, class_of, class_of),
        jnp.asarray([PAD]),
    ).tol_class[0]
    assert int(empty) == int(class_of[0]) == rows - 1
    ids = jnp.asarray([int(empty), *range(rows), rows - 1, 0], jnp.int32)
    for c in ids:
        got = V3.class_row(plane, c, "select")
        want = V3.class_row(plane, c, "slice")
        assert got.dtype == want.dtype == plane.dtype
        np.testing.assert_array_equal(_bits(got), _bits(want))
    # per scenario: its own plane and its own class id
    planes = jnp.stack([_plane(rows, dtype, seed=s) for s in range(len(ids))])
    by = {form: jax.jit(jax.vmap(lambda p, c, f=form: V3.class_row(p, c, f)))
          for form in ("slice", "select")}
    np.testing.assert_array_equal(
        _bits(by["select"](planes, ids)), _bits(by["slice"](planes, ids))
    )


def _primitives(jaxpr, out=None):
    """Names of the primitives of a jaxpr and of every jaxpr nested in it."""
    out = set() if out is None else out
    for eqn in jaxpr.eqns:
        out.add(eqn.primitive.name)
        for v in eqn.params.values():
            for x in v if isinstance(v, (list, tuple)) else (v,):
                sub = getattr(x, "jaxpr", x)
                if hasattr(sub, "eqns"):
                    _primitives(sub, out)
    return out


@pytest.mark.parametrize("rows", [2, BOUND])
def test_select_by_a_per_scenario_class_id_gathers_nothing(rows):
    """What the form is for: with the class id mapped over the scenario axis
    the dynamic index becomes a gather, the select chain stays elementwise
    over static slices."""
    import jax
    import jax.numpy as jnp

    planes = jnp.stack([_plane(rows, "bfloat16", seed=s) for s in range(4)])
    ids = jnp.asarray([0, rows - 1, 0, rows - 1], jnp.int32)

    def prims(form):
        fn = jax.vmap(lambda p, c: V3.class_row(p, c, form))
        return _primitives(jax.make_jaxpr(fn)(planes, ids).jaxpr)

    assert "gather" in prims("slice")
    assert not prims("select") & {"gather", "dynamic_slice", "dot_general"}


# --- the programs built without ``slots_by_scenario`` ---------------------
# sha256 of ``Lowered.as_text()`` (jax 0.9.0) of the chunk programs of the
# two-class trace, taken on the parent of PR 42 (4d95e87), whose step reads
# every class row by ``dynamic_index_in_dim``: the single replay, the
# arrivals-only what-if batch (``jit_per_scenario_src``) and the
# device-release batch (``jit_per_scenario_rel``). A PR that changes these
# steps on purpose re-pins them; one that meant to leave them alone has
# found a leak.
_PARENT_PROGRAMS = {
    "replay": "ae26f72f3c1c005c0a1b7d52ceffa8a72f2e74ff16e5af23345fc13b3dc468ca",
    "whatif-arrivals": "9eaaa2a2da118c711ad2f49d9d07adba2b48e82c0ac725d8c1c4d7d6c663edc0",
    "whatif-release": "5f0da9fd755f79f225d4844858e08049be435fdff93ed2c9201d4c69926a7a81",
}

W, C = 4, 4


def _scenarios(n_nodes):
    """The base cluster, one with half its cpu, and one with a taint that
    no pod tolerates on a third of its nodes: per-scenario class planes."""
    return [
        Scenario(),
        Scenario([Perturbation("scale_capacity", nodes=np.arange(n_nodes),
                               resource="cpu", factor=0.5)]),
        Scenario([Perturbation("add_taint", nodes=np.arange(0, n_nodes, 3),
                               key="whatif", value="cordon")]),
    ]


def _lowered(program):
    """The first chunk call of a program kind on the two-class trace,
    lowered: the engine's own jitted function over the arguments its own
    batch hands it."""
    ec, ep = _two_class_planes()
    cfg = FrameworkConfig()
    if program == "replay":
        eng = JaxReplayEngine(ec, ep, cfg, wave_width=W,
                              chunk_waves=C)
        name = "chunk_fn"
    else:
        kw = {"whatif-arrivals": dict(completions=False),
              "whatif-release": dict(completions=True),
              "whatif-retry": dict(completions=True, retry_buffer=16)}[program]
        eng = WhatIfEngine(ec, ep, _scenarios(ec.num_nodes), cfg, wave_width=W,
                           chunk_waves=C, collect_assignments=True, **kw)
        assert eng.engine == "v3"
        assert eng.release_path == (None if program == "whatif-arrivals"
                                    else "device")
        # a boundary with a queue dispatches the pass first (PR 47)
        name = "_retry_fn" if program == "whatif-retry" else "_chunk_fn"
    real, box = getattr(eng, name), {}

    class Captured(Exception):
        pass

    def capture(*args):
        box["args"] = args
        raise Captured

    setattr(eng, name, capture)
    with pytest.raises(Captured):
        eng.replay() if program == "replay" else eng.run()
    return real.lower(*box["args"])


@pytest.mark.parametrize("program", sorted(_PARENT_PROGRAMS))
def test_steps_with_shared_slots_keep_the_parents_program(program):
    """No step but the retry pass's is built with ``slots_by_scenario``: the
    single replay's chunk program and both what-if chunk programs without a
    queue lower to the parent's text, the text of the ``"slice"`` form."""
    text = _lowered(program).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == _PARENT_PROGRAMS[program]


def test_the_retry_program_loses_the_pass_gathers_and_nothing_else(monkeypatch):
    """The pass program (``jit_per_scenario_retry``; since PR 47 the arrival
    scan is a program of its own) holds the step that walks each scenario's
    own queue. Under ``vmap`` every
    dynamic index of a class plane is written as a gather (one whose index
    every scenario shares folds to a dynamic slice in the compiler; one by
    the queue's per-scenario class id does not). As shipped the COMPILED
    program has the pass's W slots x 3 live planes fewer of them than with
    the pass's read named ``"slice"``, the parent's form: ``tol_ok``,
    ``na_ok`` and ``na_raw`` (``tol_raw`` is dead without a PreferNoSchedule
    taint).

    Since PR 46 the pass is a ``while`` whose trip count is read from the
    queue, and the LOWERED text differs by W x 4: jax 0.9.0 has a dead-code
    rule for ``scan`` (which pruned the dead ``tol_raw`` read from the
    pass's body before lowering, so the lowered text read W x 3 while the
    pass was a scan) and none for ``while``, whose body is lowered as
    written; XLA drops the read when it compiles. Both counts are held:
    the lowered one says all four planes' reads took the select form, the
    compiled one what the device is spared."""
    from jax._src.interpreters import partial_eval as pe
    from jax._src.lax.control_flow import loops

    assert loops.scan_p in pe.dce_rules and loops.while_p not in pe.dce_rules
    lowered = lambda text: text.count('"stablehlo.gather"(')
    compiled = lambda text: text.count(" gather(")
    shipped = _lowered("whatif-retry")
    monkeypatch.setattr(V3, "class_row_reads", lambda *a, **k: "slice")
    parents = _lowered("whatif-retry")
    assert lowered(parents.as_text()) - lowered(shipped.as_text()) == 4 * W
    assert (compiled(parents.compile().as_text())
            - compiled(shipped.compile().as_text())) == 3 * W
    # the arrival scan's reads are in both
    assert lowered(_lowered("whatif-release").as_text()) >= 3 * W
