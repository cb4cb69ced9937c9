"""The program's host spans (``utils.profiling.make_span``), on the CPU under
a real ``jax.profiler.trace``, read back through the benchmark's reader
(``benchmark/layer_metrics/_program_spans.py``): a rehearsal-size engine of
each kind the benchmark's cells run (the single replay with completions, the
what-if on the device-release path, the arrivals-only what-if, the meshed
what-if over 4 of conftest's host devices, the budgeted drain with its retry
buffer and four answers), three armed batches each, the engine's first among
them."""

import collections
import contextlib
import os
import sys
import types
from pathlib import Path

import jax
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "benchmark")]

import run as bench  # noqa: E402
from layer_metrics import _program_spans  # noqa: E402

from kubernetes_simulator_tpu.sim import telemetry  # noqa: E402
from kubernetes_simulator_tpu.utils import profiling  # noqa: E402

CELLS = {  # cell: (root, the phases a batch of it has to tick)
    "borg10k-replay1": ("replay", {
        "stage", "dispatch", "device_wait", "boundary_fold", "host_mirror",
        "gather"}),
    "borg10k-whatif128": ("whatif_run", {
        "stage", "dispatch", "device_wait", "boundary_fold", "gather",
        "handback"}),
    "k8s5k-whatif256": ("whatif_run", {
        "stage", "dispatch", "device_wait", "gather", "handback"}),
    "multitenant-mesh4": ("whatif_run", {
        "stage", "dispatch", "device_wait", "gather", "handback"}),
    "borg10k-budget128": ("whatif_run", {
        "stage", "dispatch", "device_wait", "boundary_fold", "gather",
        "handback"}),
}
RETRY_ANSWERS = ("assignments", "bind_boundary", "eviction_log", "node_out_at")
BATCHES = 3


def inside(inner, outer):
    return outer[1] <= inner[1] and inner[1] + inner[2] <= outer[1] + outer[2]


@pytest.fixture(scope="module", params=sorted(CELLS))
def traced(request, tmp_path_factory):
    """BATCHES armed batches of one engine under one trace, each in a
    ``bench:batch:<i>`` span as the harness writes it, then one unarmed;
    every tick of a phase timer counted beside them."""
    cell = request.param
    _, _, config, traffic = bench.load_cell(cell)
    _, _, adapter = bench.prepare(config, traffic, 11, True, {})
    out = tmp_path_factory.mktemp("trace")
    ticks = [collections.Counter() for _ in range(BATCHES)]
    at = [0]
    tick = telemetry.PhaseTimers.tick

    def counted(self, phase):
        ticks[at[0]][phase] += 1
        return tick(self, phase)

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    results = []
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(telemetry.PhaseTimers, "tick", counted)
        mp.setenv("KSIM_PROFILE_DIR", str(out))
        with jax.profiler.trace(str(out), profiler_options=opts):
            for i in range(BATCHES):
                at[0] = i
                with jax.profiler.TraceAnnotation(f"bench:batch:{i}"):
                    results.append(adapter.batch())
    assert not profiling.profiling_active()
    unarmed = adapter.batch()
    ctx = {"trace_dir": out,
           "trace": types.SimpleNamespace(window=(0, 2 ** 62))}
    return types.SimpleNamespace(
        cell=cell, adapter=adapter, results=results, unarmed=unarmed,
        ticks=ticks, spans=_program_spans.read(ctx), ctx=ctx)


def test_one_root_per_call_with_the_calls_ordinal(traced):
    root, _ = CELLS[traced.cell]
    got = traced.spans
    assert [b["root"][0] for b in got["batches"]] == [
        f"{root}:{i}" for i in range(BATCHES)]
    roots = [e for e in got["events"] if got["root"].match(e[0])]
    assert len(roots) == BATCHES  # and no other: a root per call, no more
    assert len({e[3] for e in roots}) == 1  # all on the calling thread


def test_every_phase_ticked_is_a_span_inside_the_root(traced):
    _, expected = CELLS[traced.cell]
    for batch, ticks in zip(traced.spans["batches"], traced.ticks):
        spans = collections.Counter(
            e[0] for e in batch["children"] if e[0] in telemetry.PHASE_NAMES
            or e[0] == "checkpoint")
        assert spans == ticks
        assert expected <= set(spans)
        assert all(inside(e, batch["root"]) for e in batch["children"])
    # and none of them lies outside a root
    names = set(telemetry.HOST_SPAN_NAMES)
    loose = [e for e in traced.spans["events"] if e[0] in names
             and not any(e in b["children"] for b in traced.spans["batches"])]
    assert not loose


def test_each_dispatch_holds_its_chunk_marker(traced):
    for batch in traced.spans["batches"]:
        dispatch = [e for e in batch["children"] if e[0] == "dispatch"]
        chunks = [e for e in batch["children"] if e[0].startswith("chunk:")]
        assert [e[0] for e in chunks] == [
            f"chunk:{i}" for i in range(len(dispatch))]
        assert all(inside(c, d) for c, d in zip(chunks, dispatch))


def test_the_children_cover_the_root(traced):
    """At least 95% of a call lies under a span: the best of the batches
    after the first (which compiles), at the rehearsal's size, where the
    half millisecond that builds the result weighs most."""
    reader = bench.load_part("layer_metrics", "host_untraced_share")
    assert reader.read(dict(traced.ctx)) is not None
    shares = []
    for b in traced.spans["batches"][1:]:
        one = dict(traced.ctx, **{_program_spans.KEY: {
            **traced.spans, "batches": [b]}})
        shares.append(reader.read(one))
    assert min(shares) <= 5.0, shares


def test_the_mesh_spans_carry_the_bytes_the_summary_counts(traced):
    names = {e[0] for e in traced.spans["events"]}
    if traced.cell != "multitenant-mesh4":
        assert not names & {"mesh_put", "mesh_fetch"}
        return
    for i, (batch, res) in enumerate(zip(traced.spans["batches"],
                                         traced.results)):
        mesh = res.fleet_telemetry.summary()["mesh"]
        spans = {n: [e for e in batch["children"] if e[0] == n]
                 for n in ("stage", "handback", "mesh_put", "mesh_fetch")}
        (stage,), (handback,) = spans["stage"], spans["handback"]
        # an engine's first run puts the static trees on the devices
        assert bool(spans["mesh_put"]) == (i == 0)
        assert all(inside(e, stage) for e in spans["mesh_put"])
        assert sum(e[4]["bytes"] for e in spans["mesh_put"]) == mesh["put_bytes"]
        (fetch,) = spans["mesh_fetch"]
        assert inside(fetch, handback)
        assert fetch[4] == {"bytes": mesh["fetch_bytes"]}
        assert mesh["fetch_bytes"] == res.assignments.nbytes


def test_the_retry_handback_is_a_wait_and_one_fetch_an_answer(traced):
    """Inside ``handback`` of a batch with a ``retry_buffer``: the hand-back
    program's ``handback_wait``, then one ``handback_fetch`` an answer in the
    order they come to the host, each naming its answer and its bytes; the
    two metrics over them read the trace, and together hold most of the
    span. No other batch writes either name."""
    names = {e[0] for e in traced.spans["events"]}
    if traced.cell != "borg10k-budget128":
        assert not names & {"handback_wait", "handback_fetch"}
        return
    read = lambda m: bench.load_part("layer_metrics", m).read(dict(traced.ctx))
    for batch, res in zip(traced.spans["batches"], traced.results):
        of = lambda n: [e for e in batch["children"] if e[0] == n]
        (handback,), (wait,) = of("handback"), of("handback_wait")
        fetches = of("handback_fetch")
        assert inside(wait, handback) and all(
            inside(f, handback) and f[1] >= wait[1] + wait[2] for f in fetches)
        assert tuple(f[4]["answer"] for f in fetches) == RETRY_ANSWERS
        for f in fetches:
            answer = getattr(res, f[4]["answer"])
            # the log comes whole to a multiple of 1,024 rows and is cut here
            assert f[4]["bytes"] >= answer.nbytes if (
                f[4]["answer"] == "eviction_log") else (
                f[4]["bytes"] == answer.nbytes)
    wait_ms, fetch_ms = (read(f"retry_handback_{k}_ms_per_batch")
                         for k in ("wait", "fetch"))
    whole = read("budget_handback_ms_per_batch")
    assert 0 < wait_ms and 0 < fetch_ms and wait_ms + fetch_ms <= whole


def test_armed_and_unarmed_answer_the_same(traced):
    want = traced.adapter.answers(traced.unarmed)
    for res in traced.results:
        got = traced.adapter.answers(res)
        assert got["placed"] == want["placed"]
        assert got["unschedulable"] == want["unschedulable"]
        np.testing.assert_array_equal(got["assignments"], want["assignments"])
        if traced.cell == "borg10k-budget128":  # every answer, byte for byte
            for answer in RETRY_ANSWERS:
                a, b = getattr(res, answer), getattr(traced.unarmed, answer)
                assert a.dtype == b.dtype and a.tobytes() == b.tobytes(), answer
            summary = lambda r: {
                k: v for k, v in r.fleet_telemetry.summary().items()
                if k != "phases"}
            assert summary(res) == summary(traced.unarmed)


def test_the_new_metrics_read_the_rehearsal(traced):
    """Each span metric that lists the cell reads a number off the CPU
    trace (the idle share needs device planes, which the CPU has none of)."""
    listed = {m["name"] for m in bench.load_json(ROOT / "BENCHMARK.json")[
        "per_layer"] if m["source"] == "program_span"
        and traced.cell in m.get("workloads", [])}
    listed -= {"idle_unattributed_share", "release_host_ms_per_boundary"}
    assert len(listed) >= 4
    for name in sorted(listed):
        value = bench.load_part("layer_metrics", name).read(dict(traced.ctx))
        assert value is not None and value >= 0, name


class Spy:
    """In ``jax.profiler.TraceAnnotation``'s place: what was opened, inside
    what, and what is still open."""

    opened, stack, counts = [], [], []

    def __init__(self, name, **counts):
        self.name = name
        Spy.counts.append((name, counts))

    def __enter__(self):
        Spy.opened.append((self.name, tuple(Spy.stack)))
        Spy.stack.append(self.name)

    def __exit__(self, *exc):
        assert Spy.stack.pop() == self.name


@pytest.mark.parametrize("cell", ["borg10k-replay1", "borg10k-whatif128"])
def test_a_call_that_raises_mid_chunk_leaves_no_span_open(
        cell, monkeypatch, tmp_path):
    _, _, config, traffic = bench.load_cell(cell)
    _, _, adapter = bench.prepare(config, traffic, 11, True, {})
    eng = adapter.engine
    adapter.batch()
    monkeypatch.setenv("KSIM_PROFILE_DIR", str(tmp_path))
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Spy)
    monkeypatch.setattr(Spy, "opened", [])
    monkeypatch.setattr(Spy, "stack", [])
    attr = "chunk_fn" if cell == "borg10k-replay1" else "_chunk_fn"
    sound, calls = getattr(eng, attr), []

    def second_chunk_fails(*args):
        calls.append(1)
        if len(calls) == 2:
            raise RuntimeError("mid-chunk")
        return sound(*args)

    monkeypatch.setattr(eng, attr, second_chunk_fails)
    with pytest.raises(RuntimeError, match="mid-chunk"):
        adapter.batch()
    names = [n for n, _ in Spy.opened]
    assert names[0].endswith(":1") and "chunk:1" in names
    assert dict(Spy.opened)["chunk:1"] == (names[0], "dispatch")
    assert not Spy.stack
    # the engine is whole: the next call is the next ordinal, all closed
    monkeypatch.setattr(eng, attr, sound)
    del Spy.opened[:]
    adapter.batch()
    assert Spy.opened[0][0].endswith(":2") and not Spy.stack
    # the armed runs registered the stub as a program; nothing can lower it
    profiling._PROGRAMS.clear()


def test_each_handback_fetch_carries_the_bytes_its_copy_brought(
        monkeypatch, tmp_path):
    """``bytes`` on a ``handback_fetch`` span is the ``nbytes`` of the array
    that very ``_fetch`` handed the host (the log's before the host cuts it
    to the longest scenario's), and the spans open inside ``handback``."""
    from kubernetes_simulator_tpu.sim.whatif import WhatIfEngine

    _, _, config, traffic = bench.load_cell("borg10k-budget128")
    _, _, adapter = bench.prepare(config, traffic, 11, True, {})
    adapter.batch()
    monkeypatch.setenv("KSIM_PROFILE_DIR", str(tmp_path))
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Spy)
    for attr in ("opened", "stack", "counts"):
        monkeypatch.setattr(Spy, attr, [])
    fetch, brought = WhatIfEngine._fetch, []

    def fetch_and_keep(self, x):
        out = fetch(self, x)
        if Spy.stack[-1:] == ["handback_fetch"]:
            brought.append(out.nbytes)
        return out

    monkeypatch.setattr(WhatIfEngine, "_fetch", fetch_and_keep)
    res = adapter.batch()
    profiling._PROGRAMS.clear()
    root = Spy.opened[0][0]
    inside_handback = [n for n, stack in Spy.opened
                       if stack == (root, "handback")]
    assert inside_handback == ["handback_wait"] + ["handback_fetch"] * 4
    spans = [c for n, c in Spy.counts if n == "handback_fetch"]
    assert [c["answer"] for c in spans] == list(RETRY_ANSWERS)
    assert [c["bytes"] for c in spans] == brought
    assert brought[0] == res.assignments.nbytes == brought[1]
    assert brought[2] >= res.eviction_log.nbytes > 0
    assert not Spy.stack


def test_the_exported_names_hold_every_phase():
    assert set(telemetry.PHASE_NAMES) < set(telemetry.HOST_SPAN_NAMES)
    assert {"checkpoint", "mesh_put", "mesh_fetch", "handback_wait",
            "handback_fetch"} < set(telemetry.HOST_SPAN_NAMES)
    kept, root = _program_spans.span_names()
    assert all(kept.match(n) for n in telemetry.HOST_SPAN_NAMES)
    assert kept.match("chunk:12") and kept.match("bench:batch:0")
    assert root.match("replay:0") and root.match("whatif_run:31")
    assert not kept.match("chunk") and not root.match("replay")


def test_unarmed_spans_are_the_timer_or_the_shared_noop(monkeypatch):
    monkeypatch.delenv("KSIM_PROFILE_DIR", raising=False)
    span = profiling.make_span()
    assert not span.armed
    assert span("stage") is profiling.NULL_SPAN is span.mark("chunk:0")
    timers = telemetry.PhaseTimers()
    span = profiling.make_span(timers)
    assert isinstance(span("stage"), telemetry.PhaseTimers._Tick)
    assert span.mark("chunk:0", bytes=3) is profiling.NULL_SPAN
    with span("stage"), span.mark("mesh_put", bytes=1):
        pass
    assert set(timers.acc) == {"stage"}


def test_device_trace_arms_the_spans_for_its_extent(monkeypatch, tmp_path):
    """``--profile-dir``'s entry: KSIM_PROFILE_DIR is set inside and put
    back after, whatever it was, also when the body raises."""
    seen = []

    @contextlib.contextmanager
    def fake_trace(log_dir):
        seen.append((log_dir, os.environ.get("KSIM_PROFILE_DIR")))
        yield

    monkeypatch.setattr(jax.profiler, "trace", fake_trace)
    monkeypatch.delenv("KSIM_PROFILE_DIR", raising=False)
    with profiling.device_trace(None):
        assert not profiling.profiling_active()
    with profiling.device_trace(str(tmp_path)):
        assert profiling.profiling_active()
    assert not profiling.profiling_active()
    monkeypatch.setenv("KSIM_PROFILE_DIR", "before")
    with pytest.raises(KeyError):
        with profiling.device_trace(str(tmp_path)):
            assert profiling.profile_dir() == str(tmp_path)
            raise KeyError("body")
    assert profiling.profile_dir() == "before"
    assert seen == [(str(tmp_path), str(tmp_path))] * 2


# -- the paths no cell runs: every site that ticks a timer writes the span ----


def small_case():
    from kubernetes_simulator_tpu.framework.framework import FrameworkConfig
    from kubernetes_simulator_tpu.models.encode import encode
    from kubernetes_simulator_tpu.sim.synthetic import make_cluster, make_workload

    cluster = make_cluster(8, seed=3, taint_fraction=0.2)
    pods, _ = make_workload(
        200, seed=3, arrival_rate=12.0, duration_mean=20.0,
        with_tolerations=True)
    return (*encode(cluster, pods), FrameworkConfig())


def whatif_host_fold(ec, ep, cfg, tmp_path, fork):
    from kubernetes_simulator_tpu.sim.whatif import Scenario, WhatIfEngine

    eng = WhatIfEngine(ec, ep, [Scenario(), Scenario()], cfg, wave_width=4,
                       chunk_waves=4, completions=True,
                       fork_checkpoint=fork(ec, ep))
    assert eng.release_path == "host"
    return eng.run


def whatif_kube(ec, ep, cfg, tmp_path, fork):
    from kubernetes_simulator_tpu.sim.whatif import Scenario, WhatIfEngine

    return WhatIfEngine(ec, ep, [Scenario(), Scenario()], cfg, wave_width=4,
                        chunk_waves=4, preemption="kube", retry_buffer=64).run


def whatif_block_checkpoints(ec, ep, cfg, tmp_path, fork):
    """A work-queue block engine publishes a checkpoint every chunk (to
    nobody: one process) and drains the publisher before its gather."""
    from kubernetes_simulator_tpu.sim.whatif import Scenario, WhatIfEngine

    return WhatIfEngine(
        ec, ep, [Scenario(), Scenario()], cfg, wave_width=4, chunk_waves=4,
        completions=True,
        _dcn_recovery={"wq": {"block": 0}, "block": (0, 2), "for_pid": -1,
                       "gen": 0}).run


def replay_boundary(ec, ep, cfg, tmp_path, fork):
    from kubernetes_simulator_tpu.sim.jax_runtime import JaxReplayEngine

    return JaxReplayEngine(ec, ep, cfg, wave_width=4, chunk_waves=4,
                           preemption="kube", retry_buffer=64).replay


def replay_checkpointing(ec, ep, cfg, tmp_path, fork):
    from kubernetes_simulator_tpu.sim.jax_runtime import JaxReplayEngine

    eng = JaxReplayEngine(ec, ep, cfg, wave_width=4, chunk_waves=4)
    return lambda: eng.replay(checkpoint_path=str(tmp_path / "ck.npz"),
                              checkpoint_every=2)


PATHS = {
    whatif_host_fold: {"stage", "dispatch", "host_mirror", "boundary_fold",
                       "device_wait", "gather"},
    whatif_kube: {"stage", "dispatch", "host_mirror", "device_wait", "gather"},
    whatif_block_checkpoints: {"stage", "dispatch", "checkpoint",
                               "boundary_fold", "device_wait", "gather"},
    replay_boundary: {"stage", "dispatch", "gather"},
    replay_checkpointing: {"stage", "dispatch", "host_mirror", "device_wait",
                           "gather"},
}


@pytest.mark.parametrize("path", PATHS, ids=lambda p: p.__name__)
def test_every_site_that_ticks_a_timer_writes_the_span(
        path, monkeypatch, tmp_path, fork_at_start):
    monkeypatch.setenv("KSIM_DCN_CKPT_EVERY", "1")
    monkeypatch.setenv("KSIM_DCN_HEARTBEAT_EVERY", "0")
    call = path(*small_case(), tmp_path, fork_at_start)
    call()  # unarmed: compiles
    ticks, tick = collections.Counter(), telemetry.PhaseTimers.tick

    def counted(self, phase):
        ticks[phase] += 1
        return tick(self, phase)

    monkeypatch.setattr(telemetry.PhaseTimers, "tick", counted)
    monkeypatch.setenv("KSIM_PROFILE_DIR", str(tmp_path))
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Spy)
    monkeypatch.setattr(Spy, "opened", [])
    monkeypatch.setattr(Spy, "stack", [])
    call()
    assert not Spy.stack
    (root, outer), *rest = Spy.opened
    assert root.split(":")[0] in telemetry.ROOT_SPANS and outer == ()
    assert all(o[0] == root for _, o in rest)
    spans = collections.Counter(
        n for n, _ in rest if not n.startswith("chunk:"))
    assert spans == ticks and PATHS[path] <= set(spans)
    assert set(spans) <= set(telemetry.HOST_SPAN_NAMES)


def test_the_clis_profile_dir_gives_a_trace_with_the_spans(tmp_path, capsys):
    """``--profile-dir`` is the operator's entry: the trace it writes holds
    the call's root and phases, not device ops alone."""
    import yaml

    from kubernetes_simulator_tpu.cli import main

    config = tmp_path / "whatif.yaml"
    config.write_text(yaml.safe_dump({
        "strategy": "jax",
        "cluster": {"synthetic": {"nodes": 16, "seed": 0}},
        "workload": {"synthetic": {"pods": 256, "seed": 0}},
        "whatIf": {"scenarios": 2, "seed": 0},
    }))
    out = tmp_path / "trace"
    assert main(["what-if", str(config), "--profile-dir", str(out)]) == 0
    capsys.readouterr()
    assert not profiling.profiling_active()
    kept, root = _program_spans.span_names()
    import trace_reduce

    events = _program_spans.events_from_xplane(
        trace_reduce.find_xplane(out), kept)
    names = [e[0] for e in events]
    assert names[0] == "whatif_run:0" and sum(map(bool, map(root.match, names))) == 1
    assert {"stage", "dispatch", "chunk:0", "device_wait", "gather"} <= set(names)
    assert all(inside(e, events[0]) for e in events)
