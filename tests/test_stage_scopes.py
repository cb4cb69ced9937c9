"""Stage scopes of the device programs and the replay's host spans
(utils.profiling STAGES / register_program / stage_tables; telemetry
PHASE_NAMES): the join a traced run makes between device op events and the
program's stages, and the phases that cover a replay() call."""

import contextlib
import hashlib
import json
import os
import re
import subprocess
import sys
import time
from pathlib import Path

import jax.numpy as jnp
import pytest

from kubernetes_simulator_tpu.framework.framework import FrameworkConfig
from kubernetes_simulator_tpu.sim.borg import BorgSpec, make_borg_encoded
from kubernetes_simulator_tpu.sim.jax_runtime import JaxReplayEngine
from kubernetes_simulator_tpu.sim.telemetry import PHASE_NAMES
from kubernetes_simulator_tpu.utils import profiling


@pytest.fixture(scope="module")
def borg():
    """The benchmark cell's rehearsal size: Borg-shaped tasks with gangs,
    taints and a zone spread, completions on."""
    ec, ep, _ = make_borg_encoded(BorgSpec(nodes=64, tasks=4096, seed=0))
    return ec, ep


def _engine(borg, completions=True):
    return JaxReplayEngine(
        *borg, FrameworkConfig(), wave_width=8, chunk_waves=16,
        completions=completions,
    )


def test_stage_tables_after_an_armed_replay(borg, tmp_path, monkeypatch):
    profiling._PROGRAMS.clear()
    monkeypatch.setenv("KSIM_PROFILE_DIR", str(tmp_path))
    eng = _engine(borg)
    eng.replay()
    monkeypatch.delenv("KSIM_PROFILE_DIR")
    # What was registered lowers to the module the replay's own call gives
    # (the trace's instruction names are that module's).
    call = (eng.dc, eng._init_dev_state(), eng._slot_src, eng._extra_src,
            jnp.asarray(eng.waves.idx[:16]))
    assert (profiling._PROGRAMS["jit_chunk_fn"]().as_text()
            == eng.chunk_fn.lower(*call).as_text())
    tables = profiling.stage_tables()
    assert set(tables) == {"jit_chunk_fn", "jit_release_subtract"}
    assert set(tables["jit_release_subtract"].values()) == {"", "ksim.release"}
    chunk = tables["jit_chunk_fn"]
    ran = {path.split("/")[0] for path in chunk.values()} - {""}
    # every stage this configuration runs: no preemption, releases apart
    # (nor a pod group wider than the wave: tests/test_wide_gangs.py)
    # nor a retry pass: tests/test_retry_device.py; nor a what-if timeline's
    # eviction program: tests/test_whatif_events_device.py
    assert ran == set(profiling.STAGES) - {
        "ksim.preempt", "ksim.release", "ksim.gang_txn", "ksim.gang_rollback",
        "ksim.retry", "ksim.evict"}
    assert profiling.STAGES.index("ksim.evict") < profiling.STAGES.index(
        "ksim.release")  # program order: a boundary's events come first
    assert {"ksim.filter_score/NodeResourcesFit",
            "ksim.filter_score/TaintToleration",
            "ksim.filter_score/PodTopologySpread"} <= set(chunk.values())
    # The scan sits under ksim.gather, which its own slicing keeps; inside
    # its body the wave step's primitives (op_name: .../while/body/
    # closed_call/<scopes>/<primitive>) have to carry a stage of the step's
    # own and may not fall back to the scan's.
    text = profiling._PROGRAMS["jit_chunk_fn"]().compile().as_text()
    step = [m.group(1).split("/while/body/closed_call/", 1)[1]
            for m in re.finditer(r'op_name="([^"]*)"', text)
            if "/while/body/closed_call/" in m.group(1)]
    staged = [op for op in step if "ksim." in op]
    assert len(step) > 1000 and len(staged) >= 0.9 * len(step)


def test_parse_stage_table_takes_the_innermost_scope():
    text = """
  %p.1 = f32[8]{0} parameter(0)
  ROOT %fusion.3 = s32[]{:T(128)} fusion(%a), kind=kLoop, metadata={op_name="jit(chunk_fn)/ksim.gather/while/body/closed_call/ksim.filter_score/NodeResourcesFit/jit(_where)/select_n" source_file="x.py"}
  %dynamic-slice.7 = f32[1,8]{1,0} dynamic-slice(%b, %i), metadata={op_name="jit(chunk_fn)/ksim.gather/while/body/dynamic_slice"}
  copy.9 = f32[8]{0} copy(%p.1), metadata={op_name="jit(chunk_fn)/vmap()/while/body/closed_call/ksim.select/reduce"}
"""
    assert profiling.parse_stage_table(text) == {
        "p.1": "", "fusion.3": "ksim.filter_score/NodeResourcesFit",
        "dynamic-slice.7": "ksim.gather", "copy.9": "ksim.select",
    }
    # a pass that wraps whole wave steps stays in front of their stages
    retry = """
  %fusion.5 = s32[]{:T(128)} fusion(%a), kind=kLoop, metadata={op_name="jit(f)/vmap()/ksim.retry/while/body/closed_call/ksim.select/reduce"}
  %sort.2 = s32[8]{0} sort(%k), metadata={op_name="jit(f)/vmap()/ksim.retry/sort"}
"""
    assert profiling.parse_stage_table(retry) == {
        "fusion.5": "ksim.retry/ksim.select", "sort.2": "ksim.retry"}
    # a pass's own sub-stage, opened inside the pass, names the pass once;
    # a stage's sub-stage inside the stage is the innermost scope as ever
    nested = """
  %gather.4 = s32[8]{0} gather(%t, %i), metadata={op_name="jit(f)/vmap(ksim.retry/ksim.retry/Gather)/gather"}
  %fusion.6 = s32[8]{0} fusion(%a), kind=kLoop, metadata={op_name="jit(f)/vmap(ksim.retry/ksim.retry/Record)/select_n"}
  %fusion.7 = s32[]{:T(128)} fusion(%a), kind=kLoop, metadata={op_name="jit(f)/vmap()/ksim.retry/while/body/closed_call/ksim.filter_score/NodeResourcesFit/add"}
  %sort.8 = s32[8]{0} sort(%k), metadata={op_name="jit(f)/ksim.evict/vmap(ksim.evict/Join)/sort"}
  %broadcast.9 = s32[8]{0} broadcast(%c), metadata={op_name="jit(f)/vmap(ksim.retry)/broadcast_in_dim;jit(f)/vmap(ksim.retry)/broadcast_in_dim"}
"""
    assert profiling.parse_stage_table(nested) == {
        "gather.4": "ksim.retry/Gather", "fusion.6": "ksim.retry/Record",
        "fusion.7": "ksim.retry/ksim.filter_score/NodeResourcesFit",
        "sort.8": "ksim.evict/Join", "broadcast.9": "ksim.retry"}
    with pytest.raises(ValueError, match="unknown stage"):
        profiling.stage("ksim.pick")


def test_loop_memory_spaces_reads_the_whiles_that_carry_every_shape():
    """Lines as the TPU compiler prints them: a layout's ``S(1)`` is the
    on-chip memory, none is HBM; a loop that carries only some of the shapes
    is no answer, and of a shape carried twice the lesser space counts."""
    text = """
  %while.98 = (s32[]{:T(128)}, f32[128,3,10000]{0,2,1:T(8,128)}, f32[10000,8]{0,1:T(8,128)}) while(%tuple.1), condition=%c.1, body=%b.1, metadata={op_name="jit(f)/vmap(ksim.release)/while" stack_frame_id=8}
  %while.100 = (s32[]{:T(128)}, f32[128,3,10000]{0,2,1:T(8,128)}, f32[128,10000,3]{0,1,2:T(8,128)S(1)}, bf16[128,2,10000]{0,2,1:T(8,128)(2,1)S(1)}, f32[10000]{0:T(1024)S(1)}) while(%tuple.2), condition=%c.2, body=%b.2, metadata={op_name="jit(f)/vmap(ksim.retry)/while" stack_frame_id=140}
  ROOT %while.99 = (f32[128,3,10000]{0,2,1:T(8,128)S(1)}, f32[128,10000,3]{0,1,2:T(8,128)S(1)}, f32[128,10000,3]{0,1,2:T(8,128)S(1)}, bf16[128,2,10000]{0,2,1:T(8,128)(2,1)S(1)}) while(%tuple.3), condition=%c.3, body=%b.3, metadata={op_name="jit(f)/vmap()/while"}
  %while.7 = (f32[128,3,10000]{0,2,1:T(8,128)S(1)}, f32[128,10000,3]{0,1,2:T(8,128)}, f32[128,10000,3]{0,1,2:T(8,128)S(1)}, bf16[128,2,10000]{0,2,1}) while(%tuple.4), condition=%c.4, body=%b.4
  %fusion.1 = f32[128,3,10000]{0,2,1:T(8,128)S(1)} fusion(%while.99), kind=kLoop
"""
    used, alloc, mask = "f32[128,3,10000]", "f32[128,10000,3]", "bf16[128,2,10000]"
    assert profiling.loop_memory_spaces(text, [used, alloc, mask]) == {
        "jit(f)/vmap(ksim.retry)/while": {used: 0, alloc: 1, mask: 1},
        "jit(f)/vmap()/while": {used: 1, alloc: 1, mask: 1},
        "%while.7": {used: 1, alloc: 0, mask: 0},
    }
    assert set(profiling.loop_memory_spaces(text, [used])) == {
        "jit(f)/vmap(ksim.release)/while", "jit(f)/vmap(ksim.retry)/while",
        "jit(f)/vmap()/while", "%while.7"}
    assert profiling.loop_memory_spaces(text, ["f32[7]"]) == {}


@pytest.mark.parametrize("completions", [False, True],
                         ids=["plain", "completions"])
def test_phases_cover_the_replay_call(borg, completions):
    """The phases are sequential on one thread; what replay() spends
    outside them (argument checks, the guard, building the result) is
    small. The best of three calls, so that a stall of the machine in the
    untimed part does not fail it."""
    eng = _engine(borg, completions)
    eng.replay()  # compiles
    shares = []
    for _ in range(3):
        t = time.perf_counter()
        res = eng.replay()
        wall = time.perf_counter() - t
        phases = res.telemetry.phases
        assert set(phases) <= set(PHASE_NAMES)
        assert {"stage", "dispatch", "device_wait", "gather"} <= set(phases)
        assert ("host_mirror" in phases) == completions
        assert ("boundary_fold" in phases) == completions
        shares.append(sum(phases.values()) / wall)
    assert max(shares) >= 0.95, shares
    assert res.telemetry.summary()["chunk_waves"] == 16


def test_summary_records_the_chunk_width_the_guard_left(borg):
    """Asked for 256 waves a chunk on a trace whose tasks live for about 21
    waves, the guard shrinks the chunk and says so only in a warning; the
    width that ran is in the result."""
    eng = JaxReplayEngine(
        *borg, FrameworkConfig(), wave_width=8, chunk_waves=256,
    )
    ran = eng.replay().telemetry.summary()["chunk_waves"]
    assert eng.chunk_waves == 256 and 0 < ran < 256


_STALE = """
import contextlib, json, sys
import jax, jax.numpy as jnp
from kubernetes_simulator_tpu.utils import profiling
jax.config.update("jax_compilation_cache_dir", sys.argv[1])
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
scope = profiling.stage if sys.argv[2] == "scoped" else (
    lambda name: contextlib.nullcontext())
def f(x):
    with scope("ksim.reads"):
        return jnp.sin(x) * 2 + x
f, x = jax.jit(f), jnp.ones(1024)
f(x).block_until_ready()  # the scoped tree loads what the other compiled
profiling.register_program("jit_f", lambda: f.lower(x))
print(json.dumps(profiling.stage_tables()))
"""


def test_stage_tables_see_through_a_cache_filled_by_another_tree(tmp_path):
    """The persistent cache's key leaves metadata out: a tree with scopes
    loads the executable a tree without them compiled (the chip's machine
    comes with such a cache), and ``Lowered.compile()`` hands that same
    executable back. ``stage_tables()`` has to get the scopes anyway."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    stages = []
    for tree in ("plain", "scoped"):
        out = subprocess.run(
            [sys.executable, "-c", _STALE, str(tmp_path), tree], env=env,
            capture_output=True, text=True, timeout=120, check=True,
            cwd=os.path.dirname(os.path.dirname(__file__)),
        ).stdout
        stages.append(set(json.loads(out.splitlines()[-1])["jit_f"].values()))
        assert list(tmp_path.glob("jit_f-*"))
    assert stages == [{""}, {"", "ksim.reads"}]


@pytest.mark.parametrize("kind", ("drain", "budget"))
def test_the_eviction_programs_search_carries_its_own_scope(kind):
    """``ksim.evict/Search`` (PR 50): the one candidate search of both
    eviction programs, so that a traced run of either splits into the
    search, the admission (``ksim.evict/Budget``, the budgeted program's
    alone) and the rest under ``ksim.evict``."""
    import test_evict_search as searched

    assert "ksim.evict/Search" in profiling.SUB_STAGES
    _, fn, structs = searched.program(kind)
    profiling._PROGRAMS.clear()
    profiling.register_program("jit_whatif_evict", lambda: fn.lower(*structs))
    table = profiling.stage_tables()["jit_whatif_evict"]
    profiling._PROGRAMS.clear()
    assert set(table.values()) - {""} == {
        "ksim.evict", "ksim.evict/Search", "ksim.evict/Sort",
        "ksim.evict/Rewind", "ksim.evict/Join", "ksim.evict/Write"} | (
        {"ksim.evict/Budget"} if kind == "budget" else set())
    # the search's ops: a slot's two gathers are filed under it
    text = fn.lower(*structs).compile(
        compiler_options={"xla_dump_disable_metadata": False}).as_text()
    assert len([m for m in re.finditer(r'op_name="([^"]*)"', text)
                if "ksim.evict/Search" in m.group(1)
                and m.group(1).endswith("/gather")]) >= 2


# -- the programs around the wave step: their own sub-scopes (PR 52) ----------

EVICT_SCOPES = ("ksim.evict/Sort", "ksim.evict/Rewind", "ksim.evict/Join",
                "ksim.evict/Write")
PASS_SCOPES = ("ksim.retry/Gather", "ksim.retry/Record")
RETRY_PROGRAMS = ("jit_whatif_evict", "jit_per_scenario_retry",
                  "jit_per_scenario_arrivals", "jit_whatif_handback_retry")


def _boundary_programs(kind, tmp_path, sub_scopes=True):
    """One armed batch of ``test_evict_search.small_engine(kind)``: (the
    stage tables of what it registered, sha256 of the lowered text of a
    boundary's programs and of the hand-back's). Without ``sub_scopes`` the
    six scopes of PR 52 are not opened: the tree before it."""
    import test_evict_search as searched
    from kubernetes_simulator_tpu.sim import whatif

    eng = searched.small_engine(kind)
    handback, kept = eng._handback_retry, {}

    def keep_the_call(*args):  # (span, vassign_d, rq, retry_placed)
        kept["structs"] = profiling.shape_structs(args[-3:-1])
        return handback(*args)

    eng._handback_retry = keep_the_call
    profiling._PROGRAMS.clear()
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("KSIM_PROFILE_DIR", str(tmp_path))
        if not sub_scopes:
            mp.setattr(whatif, "stage", lambda name: (
                contextlib.nullcontext() if name in EVICT_SCOPES + PASS_SCOPES
                else profiling.stage(name)))
        eng.run()
        tables = profiling.stage_tables()
    lowered = {name: profiling._PROGRAMS[name]() for name in RETRY_PROGRAMS[:3]}
    lowered[RETRY_PROGRAMS[3]] = eng._run_jits["handback_retry"].lower(
        *kept["structs"])
    profiling._PROGRAMS.clear()
    return tables, {name: hashlib.sha256(low.as_text().encode()).hexdigest()
                    for name, low in lowered.items()}


@pytest.fixture(scope="module")
def boundary_programs(tmp_path_factory):
    return {kind: _boundary_programs(kind, tmp_path_factory.mktemp(kind))
            for kind in ("drain", "budget")}


@pytest.mark.parametrize("kind", ("drain", "budget"))
@pytest.mark.parametrize("scope", EVICT_SCOPES + PASS_SCOPES)
def test_a_program_around_the_wave_step_files_its_sub_scope(
        boundary_programs, scope, kind):
    """Every sub-scope of PR 52 is a path of its own in the table of the
    program that should carry it, in the plain and in the budgeted batch; a
    pass's sub-scope names the pass once."""
    tables, _ = boundary_programs[kind]
    module = ("jit_whatif_evict" if scope in EVICT_SCOPES
              else "jit_per_scenario_retry")
    filed = [i for i, path in tables[module].items() if path == scope]
    assert filed, sorted(set(tables[module].values()))
    for table in tables.values():
        assert not any("ksim.retry/ksim.retry" in path
                       or "ksim.evict/ksim.evict" in path
                       for path in table.values())
    # the loop's wave steps still file under the pass, the upkeep under it bare
    assert "ksim.retry/ksim.select" in tables["jit_per_scenario_retry"].values()
    assert not any(path.startswith("ksim.retry/") for path in
                   tables["jit_per_scenario_arrivals"].values())


def test_the_admissions_instructions_are_what_they_were(
        boundary_programs, tmp_path):
    """``layer_metrics/_budget.py`` reads ``ksim.evict/Budget`` by its exact
    path: the sub-scopes around it take no instruction from it and give it
    none, nothing is nested inside it, and ``ksim.evict/Search`` keeps its
    own too."""
    tables, _ = boundary_programs["budget"]
    before, _ = _boundary_programs("budget", tmp_path, sub_scopes=False)
    under = lambda t, scope: {i for i, path in t["jit_whatif_evict"].items()
                              if path == scope}
    for scope in ("ksim.evict/Budget", "ksim.evict/Search"):
        assert under(tables, scope) == under(before, scope) != set()
    assert not any(path.startswith("ksim.evict/Budget/")
                   for path in tables["jit_whatif_evict"].values())
    assert not set(before["jit_whatif_evict"].values()) & set(EVICT_SCOPES)
    # what the sub-scopes name was under the bare stage, or fused under none
    named = {i for i, path in tables["jit_whatif_evict"].items()
             if path in EVICT_SCOPES}
    assert named <= under(before, "ksim.evict") | under(before, "")


# sha256 of ``Lowered.as_text()`` (jax 0.9.0) of the programs of a boundary of
# ``test_evict_search.small_engine(kind)`` and of its hand-back, on the parent
# of PR 52 (23aecca): a scope is metadata, and the text does not print it.
# The pass program's are PR 53's, which reads a queued task's rows in one
# gather (until then f0eec650...2272eb85 and a5dd11e0...85e06964, 23aecca's);
# the three programs around it keep 23aecca's text.
PARENT_PROGRAMS = {
    "drain": {
        "jit_whatif_evict":
            "8db066e6e3041db5de07586b45286a9409583794377db500df5bd31c231fb53b",
        "jit_per_scenario_retry":
            "8050b0426432ede7228d1ac7bc230a68de19bb4b68282db3210be292d23c5b91",
        "jit_per_scenario_arrivals":
            "ffa8e586b75963a8d1bad138d472554567caacdff7954b45d62dcf206b8b0751",
        "jit_whatif_handback_retry":
            "7f06c9eaa593b1d4d099c051a12842bbe7944c4285544b761f17b6829f5c51b2",
    },
    "budget": {
        "jit_whatif_evict":
            "f440d471a39c2b60bbf553963b5d8e5dc933417764c86c656e2c3d976f58355b",
        "jit_per_scenario_retry":
            "4942f089d1bbc06fb05487267ad7bdba96846a64401f0b506454b32d625b9ee1",
        "jit_per_scenario_arrivals":
            "ffa8e586b75963a8d1bad138d472554567caacdff7954b45d62dcf206b8b0751",
        "jit_whatif_handback_retry":
            "7f06c9eaa593b1d4d099c051a12842bbe7944c4285544b761f17b6829f5c51b2",
    },
}


@pytest.mark.parametrize("program", RETRY_PROGRAMS)
@pytest.mark.parametrize("kind", ("drain", "budget"))
def test_the_sub_scopes_leave_the_lowered_programs_as_they_were(
        boundary_programs, kind, program):
    _, sha = boundary_programs[kind]
    assert sha[program] == PARENT_PROGRAMS[kind][program]


def test_tracing_is_code_every_scope_and_span_has_a_reader():
    """Every sub-scope of the vocabulary and both spans of the retry
    hand-back are named by a file under ``benchmark/layer_metrics/``, and no
    stage scope is written outside the vocabulary."""
    from kubernetes_simulator_tpu.sim import telemetry

    root = Path(__file__).resolve().parents[1]
    readers = "".join(p.read_text() for p in sorted(
        (root / "benchmark" / "layer_metrics").glob("*.py")))
    for name in profiling.SUB_STAGES + ("handback_wait", "handback_fetch"):
        assert f'"{name}"' in readers, name
    assert {"handback_wait", "handback_fetch"} < set(telemetry.HOST_SPAN_NAMES)
    literal = re.compile(r"named_scope\(\s*[\"']ksim\.")
    outside = [str(p.relative_to(root)) for p in sorted(
        (root / "kubernetes_simulator_tpu").rglob("*.py"))
        if p.name != "profiling.py" and literal.search(p.read_text())]
    assert not outside
