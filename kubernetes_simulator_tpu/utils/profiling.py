"""Profiling hooks (SURVEY.md §5 tracing/profiling).

- ``device_trace(dir)``: jax.profiler trace (TensorBoard/Perfetto) around a
  replay, with the program's host spans armed for its extent.
- ``profiling_active()`` / ``make_span(timers)``: the device-profiler hook
  contract. ``KSIM_PROFILE_DIR`` arms the program's host spans, and
  ``make_span`` is their one primitive: a factory both engines consult
  once per ``replay()`` / ``run()`` for ``span(name, **counts)``, the
  phase timer under a ``jax.profiler.TraceAnnotation`` when armed, so that
  what the host does lies on the device trace's clock. Off by default;
  spans never change results (pinned in tests/test_telemetry.py).
  The names a span can carry are ``sim.telemetry``'s, beside
  ``PHASE_NAMES``: ``HOST_SPAN_NAMES`` / ``CHUNK_SPAN`` / ``ROOT_SPANS``.
- ``STAGES`` / ``stage(name)``: the one vocabulary of the device programs'
  stages, written into the HLO by ``jax.named_scope`` (trace-time metadata,
  no op). Beneath ``ksim.filter_score`` a plugin's own work sits under its
  registry name (``ksim.filter_score/PodTopologySpread``).
- ``register_program(module_name, lower)`` / ``register_call(fn, args)`` /
  ``stage_tables()``: device op
  events in a trace carry the HLO instruction's name (``fusion.628``), the
  scope sits in the executable's HLO text; the engines hand the programs of
  an armed replay over by XLA module name, and ``stage_tables()`` joins the
  two after the run.
- ``loop_memory_spaces(hlo_text, shapes)``: which memory space the compiler
  gave the arrays a ``while`` of a compiled program carries (``S(1)`` in a
  layout: the TPU's on-chip memory; none: HBM). A loop whose planes sit in
  HBM reads them at HBM speed in every iteration.
"""

from __future__ import annotations

import contextlib
import os
import re
from typing import Callable, Dict, Optional

#: Stage scopes of the device programs (chunk, release), in program order.
STAGES = (
    "ksim.gather",        # slot / extra gathers inside the chunk jit
    "ksim.derive",        # Derived.build, class_masks: per chunk
    "ksim.reads",         # wave-start reads of the wave step
    "ksim.corrections",   # exact in-wave corrections from pods j<k
    "ksim.filter_score",  # fused Filter + Score, one sub-scope per plugin
    "ksim.select",        # the pick: extrema, tie-break, pack-select
    "ksim.preempt",       # victim ranking and eviction marking
    "ksim.commit",        # wave-end commit, gang rollback mask
    "ksim.gang_txn",      # a wide pod group's carried transaction: upkeep, verdict
    "ksim.gang_rollback", # its binds given back where it closes
    "ksim.evict",         # a boundary's eviction program (what-if timelines)
    "ksim.release",       # boundary release programs
    "ksim.retry",         # a boundary's retry pass: its scan and the queue's upkeep
)

#: A stage's own sub-scopes, opened inside it (a plugin's beneath
#: ``ksim.filter_score`` carries the plugin's registry name and is not
#: listed): a stage path names them as ``parse_stage_table`` reads them.
SUB_STAGES = (
    "ksim.evict/Search",  # the eviction program's candidate search (sim.whatif.evict_search)
    "ksim.evict/Budget",  # the eviction program's admission under disruption budgets
    "ksim.evict/Sort",    # the candidates sorted into BoundaryOps.evict_node's order
    "ksim.evict/Rewind",  # the leaving tasks' rows read by task, the release core
    "ksim.evict/Join",    # the queue's join, its stable sort, the cut to the buffer
    "ksim.evict/Write",   # binds cleared where they stand, the node planes, the log
    "ksim.retry/Gather",  # the pass program's slot gathers over the queue's ids
    "ksim.retry/Record",  # the pass's row written into the record, its counters
    "ksim.retry/Layout",  # retry_groups: the queue's jobs laid out from fresh waves
    "ksim.retry/Close",   # ... a pass wave's tile, and its job's verdict (commit or give-back)
    "ksim.retry/Join",    # ... the chunk's rolled-back jobs joining the queue whole
)

#: Scopes that wrap whole wave steps, not a stage of one: an instruction
#: inside one is filed under ``<pass>/<its stage path>`` (``ksim.retry/
#: ksim.select``), so the pass's time can be told from the arrival waves'.
PASSES = ("ksim.retry",)

# A stage path inside an HLO ``op_name``: the ``ksim.`` component and the
# CamelCase components after it (plugin names). JAX's own components
# (primitives, ``jit(..)``, ``while/body``) are lower case.
_STAGE_PATH = re.compile(r"ksim\.\w+(?:/[A-Z]\w*)*")
_HLO_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s")
_HLO_OP_NAME = re.compile(r'op_name="([^"]*)"')

_HLO_WHILE = re.compile(r"=\s*\((.*)\)\s+while\(")
_HLO_ARRAY = re.compile(r"(\w+\[[\d,]*\])\{([^}]*)\}")
_HLO_SPACE = re.compile(r"S\((\d+)\)")

# XLA module name -> thunk lowering the jitted program on the shapes of the
# call an armed replay made.
_PROGRAMS: Dict[str, Callable] = {}


def profile_dir() -> Optional[str]:
    """The device-profiler sink (``KSIM_PROFILE_DIR``), or None when
    profiling is off."""
    return os.environ.get("KSIM_PROFILE_DIR") or None


def profiling_active() -> bool:
    """True when profiler hooks should annotate. One env-dict lookup — the
    engines consult this per ``replay()`` / ``run()`` (not per chunk),
    through :func:`make_span`."""
    return bool(profile_dir())


#: The no-op context every unarmed, untimed span is (one shared object).
NULL_SPAN = contextlib.nullcontext()


@contextlib.contextmanager
def _stacked(outer, inner):
    with outer, inner:
        yield


class Span:
    """``span(name, **counts)``: a context for one phase of a call. The
    phase timer (``timers.tick(name)``) when ``timers`` collect, under a
    ``jax.profiler.TraceAnnotation(name, **counts)`` when armed (``counts``
    become the trace event's stats); armed or not, with no timers, the
    timer falls away, and with neither it is the shared no-op context.
    ``span.mark(name, **counts)`` is the same span for a name that is no
    phase (``chunk:<i>``, ``mesh_put``, a root): never timed. Built by
    :func:`make_span`; ``timers`` may be bound after the root is open."""

    __slots__ = ("_annotation", "timers")

    def __init__(self, annotation, timers=None):
        self._annotation = annotation
        self.timers = timers

    @property
    def armed(self) -> bool:
        return self._annotation is not None

    def __call__(self, name: str, **counts):
        if self.timers is None:
            return self.mark(name, **counts)
        tick = self.timers.tick(name)
        if self._annotation is None:
            return tick
        return _stacked(self._annotation(name, **counts), tick)

    def mark(self, name: str, **counts):
        if self._annotation is None:
            return NULL_SPAN
        return self._annotation(name, **counts)


def make_span(timers=None) -> Span:
    """The span primitive of one ``replay()`` / ``run()`` call.
    :func:`profiling_active` is read ONCE, here, and nowhere per chunk:
    unarmed, a span is exactly the phase timer (two ``perf_counter`` reads)
    or, with no ``timers`` (``PhaseTimers``), the shared no-op."""
    annotation = None
    if profiling_active():
        import jax

        annotation = jax.profiler.TraceAnnotation
    return Span(annotation, timers)


@contextlib.contextmanager
def device_trace(log_dir: Optional[str]):
    """``jax.profiler.trace(log_dir)`` with ``KSIM_PROFILE_DIR`` set to it
    for its extent (and restored after): the trace holds the program's
    host spans beside the device's ops. Nothing for an empty ``log_dir``."""
    import jax

    if not log_dir:
        yield
        return
    before = os.environ.get("KSIM_PROFILE_DIR")
    os.environ["KSIM_PROFILE_DIR"] = str(log_dir)
    try:
        with jax.profiler.trace(log_dir):
            yield
    finally:
        if before is None:
            os.environ.pop("KSIM_PROFILE_DIR", None)
        else:
            os.environ["KSIM_PROFILE_DIR"] = before


def stage(name: str):
    """``jax.named_scope`` for a member of :data:`STAGES` or of
    :data:`SUB_STAGES`."""
    import jax

    if name not in STAGES and name not in SUB_STAGES:
        raise ValueError(f"unknown stage {name!r} (have {STAGES})")
    return jax.named_scope(name)


def register_program(module_name: str, lower: Callable) -> None:
    """Remember a device program for :func:`stage_tables`: its XLA module
    name as a trace prints it (``jit_chunk_fn``) and a thunk that lowers
    the same jitted function on ``ShapeDtypeStruct``s of the same
    arguments. Stores the closure; lowers nothing."""
    _PROGRAMS[module_name] = lower


def shape_structs(tree):
    """``ShapeDtypeStruct`` tree of a call's arguments, for
    :func:`register_program` thunks: holds no device buffer. A sharding
    is kept only where it spans devices; on one device it would add
    attributes to the module that the call itself does not have."""
    import jax

    def struct(a):
        sharding = getattr(a, "sharding", None)
        if sharding is not None and len(sharding.device_set) == 1:
            sharding = None
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding)

    return jax.tree.map(struct, tree)


def register_call(fn, args) -> None:
    """:func:`register_program` for a jitted ``fn`` about to be called
    with ``args``, under the module name jit gives it. The shapes are
    taken now: the call may donate its buffers."""
    structs = shape_structs(args)
    register_program(f"jit_{fn.__name__}", lambda: fn.lower(*structs))


def parse_stage_table(hlo_text: str) -> Dict[str, str]:
    """{instruction name: stage path} of one executable's HLO text: the
    innermost ``ksim.`` scope of its ``op_name`` (a scan issued under
    ``ksim.gather`` keeps that stage for its own slicing only), behind the
    pass that wraps it where one does (:data:`PASSES`); an instruction
    under no ``ksim.`` scope maps to ``""``."""
    table: Dict[str, str] = {}
    for line in hlo_text.splitlines():
        m = _HLO_INSTRUCTION.match(line)
        if not m:
            continue
        op = _HLO_OP_NAME.search(line)
        paths = _STAGE_PATH.findall(op.group(1)) if op else ()
        path = paths[-1] if paths else ""
        outer = next((p for p in paths[:-1] if p in PASSES), None)
        if outer and path != outer and not path.startswith(outer + "/"):
            path = f"{outer}/{path}"
        table[m.group(1)] = path
    return table


def loop_memory_spaces(hlo_text: str, shapes) -> Dict[str, Dict[str, int]]:
    """{``op_name`` of a ``while``: {shape: memory space}} for every
    ``while`` of one compiled program's text whose carried tuple holds an
    array of EACH of ``shapes`` (``"f32[128,3,10000]"``: dtype and
    dimensions as the text prints them). The space is the ``S(n)`` of the
    element's layout, 0 where it has none: on a TPU 1 is the on-chip memory
    and 0 HBM; the CPU backend's text names no space. Of a shape the tuple
    holds twice, the lesser."""
    found: Dict[str, Dict[str, int]] = {}
    for line in hlo_text.splitlines():
        m = _HLO_WHILE.search(line)
        if not m:
            continue
        spaces: Dict[str, int] = {}
        for shape, layout in _HLO_ARRAY.findall(m.group(1)):
            if shape in shapes:
                s = _HLO_SPACE.search(layout)
                spaces[shape] = min(
                    spaces.get(shape, 1 << 30), int(s.group(1)) if s else 0
                )
        if len(spaces) == len(set(shapes)):
            op = _HLO_OP_NAME.search(line)
            found[op.group(1) if op else line.split("=")[0].strip()] = spaces
    return found


def stage_tables() -> Dict[str, Dict[str, str]]:
    """{module name: {instruction name: stage path}} for every registered
    program: each is lowered and compiled again and its optimized HLO
    parsed. Two caches stand between a program and its scopes. The
    persistent cache's key leaves metadata out, so a hit hands back an
    executable compiled from whichever tree filled the entry, with other
    scopes or none: here the key takes the metadata in. And
    ``Lowered.compile()`` hands back the executable the process already
    holds for the module unless it is given compiler options: it gets one
    at its default value. That costs one compile per program and cache
    directory (a later call in the same run loads what the first compiled:
    0.4-0.6 s for the seven to nine programs of a retry cell on the chip,
    PERF.md §5). Instruction names are the same in all of them: metadata
    steers no pass."""
    import jax

    key = "jax_compilation_cache_include_metadata_in_key"
    before = getattr(jax.config, key)
    jax.config.update(key, True)
    try:
        return {
            name: parse_stage_table(
                lower().compile(
                    compiler_options={"xla_dump_disable_metadata": False}
                ).as_text()
            )
            for name, lower in _PROGRAMS.items()
        }
    finally:
        jax.config.update(key, before)
