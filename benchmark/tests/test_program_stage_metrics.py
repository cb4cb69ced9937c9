"""The readers of PR 52 on the CPU:

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q

``layer_metrics/_program_stages.py`` (a program's device time by stage path,
a boundary a run of the module) and the eleven metrics over it on a hand-made
trace beside hand-made stage tables, as ``test_stage_metrics.py`` does for the
chunk readers; the two metrics over the retry hand-back's spans on a recorded
cut (``testdata/retry_handback_spans_cut.json``: the one traced batch of
``borg10k-backlog128``, my chip run, PR 52, seed 2147652003, as
``_program_spans.py --cut`` writes it, with what that run's result line read
under ``expect``).
"""

import copy
import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path[:0] = [str(ROOT), str(BENCH)]

import run  # noqa: E402
import trace_reduce  # noqa: E402
from kubernetes_simulator_tpu.sim import telemetry  # noqa: E402
from kubernetes_simulator_tpu.utils import profiling  # noqa: E402
from layer_metrics import _program_stages  # noqa: E402

US = 1000  # the trace's clock is in ns
RETRY_CELLS = ["borg10k-backlog128", "borg10k-drain128", "borg10k-budget128"]
TABLES = {
    "jit_whatif_evict": {
        "fusion.1": "ksim.evict/Search", "fusion.2": "ksim.evict/Sort",
        "fusion.3": "ksim.evict/Budget", "fusion.4": "ksim.evict/Rewind",
        "sort.5": "ksim.evict/Join", "scatter.6": "ksim.evict/Write",
        "fusion.7": "ksim.evict", "copy.8": "", "while.20": "ksim.evict/Rewind"},
    "jit_per_scenario_retry": {
        "fusion.1": "ksim.release", "fusion.2": "ksim.retry/Gather",
        "fusion.3": "ksim.retry/ksim.select", "fusion.4": "ksim.retry/ksim.commit",
        "fusion.5": "ksim.retry/Record", "fusion.6": "ksim.retry",
        "fusion.7": "ksim.derive", "while.9": "ksim.retry"},
    "jit_per_scenario_arrivals": {
        "fusion.1": "ksim.select", "sort.2": "ksim.retry",
        "fusion.3": "ksim.release"},
}
# us an execution, by instruction: the same names mean other things in other
# modules, as in a real trace
BODIES = {
    "jit_whatif_evict": [
        ("fusion.1", 80), ("fusion.2", 6), ("fusion.3", 14), ("fusion.4", 20),
        ("sort.5", 12), ("scatter.6", 9), ("fusion.7", 3), ("copy.8", 16),
        ("fusion.99", 4)],  # in no table: under no scope
    "jit_per_scenario_retry": [
        ("fusion.1", 50), ("fusion.2", 30), ("fusion.3", 70), ("fusion.4", 40),
        ("fusion.5", 25), ("fusion.6", 2), ("fusion.7", 11)],
    "jit_per_scenario_arrivals": [
        ("fusion.1", 100), ("sort.2", 45), ("fusion.3", 5)],
}
METRICS = {  # name: (ms a run on the hand-made trace, the cells that list it)
    "evict_search_ms_per_boundary": (0.080, RETRY_CELLS[1:]),
    "evict_sort_ms_per_boundary": (0.006, RETRY_CELLS[1:]),
    "evict_rewind_ms_per_boundary": (0.020, RETRY_CELLS[1:]),
    "evict_join_ms_per_boundary": (0.012, RETRY_CELLS[1:]),
    "evict_write_ms_per_boundary": (0.009, RETRY_CELLS[1:]),
    # bare ksim.evict 3 us of the 144 under a scope; copy.8 and fusion.99 in neither
    "evict_unscoped_share": (100 * 3 / 144, RETRY_CELLS[1:]),
    "retry_gather_ms_per_boundary": (0.030, RETRY_CELLS),
    "retry_steps_ms_per_boundary": (0.110, RETRY_CELLS),
    "retry_record_ms_per_boundary": (0.025, RETRY_CELLS),
    "retry_upkeep_ms_per_boundary": (0.045, RETRY_CELLS),
    "retry_due_release_ms_per_boundary": (0.050, RETRY_CELLS),
}
SPAN_METRICS = ("retry_handback_wait_ms_per_batch",
                "retry_handback_fetch_ms_per_batch")


def events(boundaries=2):
    """One traced batch: ``boundaries`` times the eviction program, the pass
    (its loop's ``while`` event over the steps' ops) and the arrival program,
    then a third pass that the window's end cuts."""
    ops, modules, t = [], [], 100
    order = ["jit_whatif_evict", "jit_per_scenario_retry",
             "jit_per_scenario_arrivals"] * boundaries + ["jit_per_scenario_retry"]
    for module in order:
        start = t
        for name, us in BODIES[module]:
            if module.endswith("retry") and name == "fusion.3":
                ops.append(["%while.9 = (s32[], f32[3,64]) while(%tuple.1)",
                            t * US, 111 * US])  # spans fusion.3 and fusion.4
            ops.append([f"%{name} = s32[]{{:T(128)}} fusion(%a, %b)",
                        t * US, us * US])
            t += us + 1
        modules.append([f"{module}({len(modules)})", start * US, (t - start) * US])
        t += 20
    end = modules[-1][1] + 10 * US  # inside the last pass: it does not count
    return {"devices": [{"modules": modules, "ops": ops, "dropped": [end]}],
            "host": [["bench:batch:0", 0, (t + 100) * US]]}


def context(ev=None):
    return {"trace": trace_reduce.Reduced(ev or events()), "shape": {}}


def read_all(ctx, names=METRICS):
    return {m: run.load_part("layer_metrics", m).read(ctx) for m in names}


def test_every_new_metric_has_a_reader_and_an_entry():
    b = json.loads((ROOT / "BENCHMARK.json").read_text())
    entries = {m["name"]: m for m in b["per_layer"]}
    layers = {m["layer"] for m in b["per_layer"] if m["name"] not in METRICS
              and m["name"] not in SPAN_METRICS}
    listed = dict({k: v[1] for k, v in METRICS.items()},
                  **dict.fromkeys(SPAN_METRICS, RETRY_CELLS))
    assert len(listed) == 13
    for name, cells in listed.items():
        m = entries[name]
        assert m["moves"] == "placements_per_s" and m["better"] == "lower"
        assert m["workloads"] == cells and m["layer"] in layers
        assert m["source"] == ("program_span" if name in SPAN_METRICS
                               else "device_trace")
        assert m["unit"] == ("%" if name.endswith("_share") else "ms")
        doc = (BENCH / "layer_metrics" / f"{name}.py").read_text()
        assert doc.startswith(f'"""{name}: ')


def test_program_stage_metrics_on_a_hand_made_trace(monkeypatch, capsys):
    monkeypatch.setattr(profiling, "stage_tables", lambda: TABLES, raising=False)
    ctx = context()
    got = read_all(ctx)
    assert got == pytest.approx({k: v[0] for k, v in METRICS.items()}, rel=1e-9)
    for module, body in BODIES.items():
        kept = _program_stages.read(ctx, module)
        # a boundary is a run of the module; the pass the window cuts is none
        assert kept["runs"] == 2
        # the while event is not counted: the leaves are all of the op time
        assert kept["op_seconds"] == pytest.approx(
            2 * sum(us for _, us in body) * 1e-6)
    evict = _program_stages.read(ctx, "jit_whatif_evict")["seconds"]
    assert evict[""] == pytest.approx(2 * (16 + 4) * 1e-6)
    assert _program_stages.read(ctx, "jit_whatif_release_k256") is None
    err = capsys.readouterr().err
    assert "program jit_whatif_evict ksim.evict/Search 0.080 ms a run" in err
    assert "jit_whatif_evict: 0.020 ms a run under no scope, 0.144 under" in err
    # one walk of the op events for all of them
    assert err.count("program jit_per_scenario_retry: 2 runs") == 1


def test_a_program_that_lacks_a_scope_reads_none_for_it_alone(monkeypatch):
    """The parent of PR 52: the eviction program has its search and its
    admission, the pass its steps and its releases, the arrival program its
    upkeep; what PR 52 adds reads nothing and nothing raises."""
    new = {"ksim.evict/Sort", "ksim.evict/Rewind", "ksim.evict/Join",
           "ksim.evict/Write", "ksim.retry/Gather", "ksim.retry/Record"}
    older = {m: {i: (p.rsplit("/", 1)[0] if p in new else p)
                 for i, p in t.items()} for m, t in TABLES.items()}
    monkeypatch.setattr(profiling, "stage_tables", lambda: older, raising=False)
    got = read_all(context())
    silent = {k for k, v in got.items() if v is None}
    assert silent == {"evict_sort_ms_per_boundary", "evict_rewind_ms_per_boundary",
                      "evict_join_ms_per_boundary", "evict_write_ms_per_boundary",
                      "retry_gather_ms_per_boundary",
                      "retry_record_ms_per_boundary"}
    assert got["evict_search_ms_per_boundary"] == pytest.approx(0.080)
    assert got["evict_unscoped_share"] == pytest.approx(100 * 50 / 144)
    assert got["retry_steps_ms_per_boundary"] == pytest.approx(0.110)


@pytest.mark.parametrize("tables", ["absent", "empty", "raises", "none ran"])
def test_program_stage_metrics_read_nothing_without_a_table(monkeypatch, tables):
    def boom():
        raise RuntimeError("no executable text")

    ev = events()
    if tables == "absent":
        monkeypatch.delattr(profiling, "stage_tables", raising=False)
    elif tables == "none ran":
        monkeypatch.setattr(profiling, "stage_tables", lambda: TABLES,
                            raising=False)
        ev["devices"][0]["modules"] = [["jit_whatif_handback_retry(3)", 0, US]]
    else:
        monkeypatch.setattr(profiling, "stage_tables",
                            dict if tables == "empty" else boom, raising=False)
    assert read_all(context(ev)) == dict.fromkeys(METRICS)


# -- the retry hand-back's two span metrics, on a recorded batch ---------------


def recorded():
    doc = json.loads(
        (BENCH / "testdata" / "retry_handback_spans_cut.json").read_text())
    return copy.deepcopy(doc["cut"]), doc["expect"]


def span_context(cut):
    return {"trace": trace_reduce.Reduced(cut), "shape": {},
            "program_span_events": cut["program_span_events"]}


def test_the_handback_span_metrics_on_a_recorded_batch(capsys):
    cut, expect = recorded()
    ctx = span_context(cut)
    got = read_all(ctx, SPAN_METRICS)
    assert got == pytest.approx(expect["metrics"], rel=1e-9)
    # what the accepted metric reads from outside holds both, and little else
    whole = run.load_part("layer_metrics", "backlog_handback_ms_per_batch").read(ctx)
    assert whole == pytest.approx(expect["handback_ms"], rel=1e-9)
    assert 0.9 * whole <= sum(got.values()) <= whole
    err = capsys.readouterr().err
    for answer, size in expect["answers"].items():
        assert f"benchmark: handback_fetch {answer} {size} bytes in " in err
    assert err.count("GB/s") == len(expect["answers"])


def test_the_span_metrics_read_the_whole_batch_past_the_windows_end():
    """In both eviction cells the device's trace buffer ends the window before
    the hand-back: the metrics read the program's spans over the whole traced
    batch (``_drain.whole``), the host's planes lose nothing."""
    cut, expect = recorded()
    handback = next(e for e in cut["program_span_events"] if e[0] == "handback")
    cut["devices"][0]["dropped"] = [handback[1] - 1000]
    ctx = span_context(cut)
    assert ctx["trace"].window[1] < handback[1]
    assert read_all(ctx, SPAN_METRICS) == pytest.approx(
        expect["metrics"], rel=1e-9)
    # the reader bound to the window sees no batch at all
    assert run.load_part(
        "layer_metrics", "host_handback_ms_per_batch").read(ctx) is None


@pytest.mark.parametrize("how", ["names not exported", "no such span",
                                 "span outside handback"])
def test_a_tree_without_the_handback_spans_reads_none(how, monkeypatch):
    cut, _ = recorded()
    new = ("handback_wait", "handback_fetch")
    if how == "names not exported":
        monkeypatch.setattr(telemetry, "HOST_SPAN_NAMES", tuple(
            n for n in telemetry.HOST_SPAN_NAMES if n not in new))
    elif how == "no such span":
        cut["program_span_events"] = [
            e for e in cut["program_span_events"] if e[0] not in new]
    else:
        cut["program_span_events"] = [
            e for e in cut["program_span_events"] if e[0] != "handback"]
    assert read_all(span_context(cut), SPAN_METRICS) == dict.fromkeys(SPAN_METRICS)
