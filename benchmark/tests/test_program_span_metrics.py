"""The seven metrics over the program's host spans
(``layer_metrics/_program_spans.py``), on the CPU:

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q

``testdata/program_spans_cut.json`` holds two traced batches cut from chip
runs (PR 35) as ``_program_spans.py --cut`` writes them: ``one_chip``, the
one traced batch of ``borg10k-whatif128`` (seed 2147483903), and
``two_device``, the two traced batches of ``multitenant-mesh4`` (seed
2147487001) as devices 0 and 1 of its four saw them; each with the device
planes' program executions and merged busy intervals (``trace_reduce.cut``)
and the program's span events with their stats, and under ``expect`` what
the result line of that run read.
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
from layer_metrics import _program_spans  # noqa: E402

METRICS = {  # name: the cells that list it
    "idle_unattributed_share": 4, "host_untraced_share": 4,
    "host_stage_ms_per_batch": 4, "host_dispatch_ms_per_batch": 4,
    "host_gather_ms_per_batch": 4, "host_handback_ms_per_batch": 3,
    "mesh_fetch_ms_per_batch": 1,
}
US = 1000  # the trace's clock is in ns


def recorded(which):
    doc = json.loads((BENCH / "testdata" / "program_spans_cut.json").read_text())
    return copy.deepcopy(doc[which]), doc["expect"][which]


def context(cut):
    return {"trace": trace_reduce.Reduced(cut), "shape": {},
            "program_span_events": cut["program_span_events"]}


def read_all(ctx):
    return {m: run.load_part("layer_metrics", m).read(ctx) for m in METRICS}


def test_every_new_metric_has_a_reader_and_an_entry_at_the_end():
    b = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = [w["name"] for w in b["workloads"]]
    layers = {m["layer"] for m in b["per_layer"][:-len(METRICS)]}
    tail = b["per_layer"][-len(METRICS):]
    assert [m["name"] for m in tail] == list(METRICS)
    for m in tail:
        assert m["source"] == "program_span" and m["better"] == "lower"
        assert m["moves"] == "placements_per_s" and m["layer"] in layers
        assert len(m["workloads"]) == METRICS[m["name"]]
        assert set(m["workloads"]) <= set(cells)
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        doc = (BENCH / "layer_metrics" / f"{m['name']}.py").read_text()
        assert doc.startswith(f'"""{m["name"]}: ')
    assert "borg10k-replay1" not in tail[5]["workloads"]
    assert tail[6]["workloads"] == ["multitenant-mesh4"]


@pytest.mark.parametrize("which", ["one_chip", "two_device"])
def test_the_metrics_on_a_recorded_batch(which, capsys):
    cut, expect = recorded(which)
    got = read_all(context(cut))
    assert got == pytest.approx(expect["metrics"], rel=1e-9, abs=1e-9)
    err = capsys.readouterr().err
    chips = len(cut["devices"])
    assert f"idle over {chips} chip(s) under handback: " in err
    if which == "two_device":
        assert "benchmark: mesh_fetch 40960000 bytes in " in err
        assert got["mesh_fetch_ms_per_batch"] < got["host_handback_ms_per_batch"]
    else:
        assert got["mesh_fetch_ms_per_batch"] is None
    # every idle nanosecond of every chip is in the table once
    red = trace_reduce.Reduced(cut)
    idle = sum((red.window[1] - red.window[0]) - sum(e - s for s, e in b)
               for b in red.busy)
    assert sum(_program_spans.idle(context(cut)).values()) == idle


@pytest.mark.parametrize("which", ["one_chip", "two_device"])
def test_the_recorded_spans_tile_their_root(which):
    """One root a traced batch, on one thread, its ordinal after the
    warm-up's; the phases inside it; ``chunk:<i>`` inside ``dispatch``."""
    cut, expect = recorded(which)
    got = _program_spans.read(context(cut))
    assert [b["root"][0] for b in got["batches"]] == expect["roots"]
    for b in got["batches"]:
        names = [e[0] for e in b["children"]]
        assert {"stage", "dispatch", "device_wait", "gather", "handback"} <= set(names)
        assert names.count("stage") == names.count("gather") == 1
        assert names.count("dispatch") == sum(n.startswith("chunk:") for n in names)
        assert all(e[3] == b["root"][3] for e in b["children"])


@pytest.mark.parametrize("which", ["one_chip", "two_device"])
def test_outer_gaps_are_the_gaps_in_no_program_execution(which):
    """The bisecting search finds what a walk over every gap finds."""
    cut, _ = recorded(which)
    red = trace_reduce.Reduced(cut)
    for dev, busy in zip(red.devices, red.busy):
        mods = [(s, s + d) for _, s, d in dev["modules"]]
        walked = [(g0, g1) for g0, g1 in red._gaps(busy)
                  if not any(s <= g0 and g1 <= e for s, e in mods)]
        assert _program_spans.outer_gaps(busy, mods, red.window) == walked
        assert walked


@pytest.mark.parametrize("how", ["no names", "no trace file", "no root"])
def test_a_tree_without_the_spans_reads_none_and_the_run_ends(
        how, monkeypatch, tmp_path):
    """An older tree exports no span names and writes no root: every new
    metric reads None, nothing raises, and the line is made without them."""
    cut, _ = recorded("one_chip")
    ctx = context(cut)
    if how == "no names":
        monkeypatch.delattr(telemetry, "HOST_SPAN_NAMES")
    elif how == "no trace file":
        del ctx["program_span_events"]
        ctx["trace_dir"] = tmp_path
    else:
        ctx["program_span_events"] = [
            e for e in cut["program_span_events"]
            if not e[0].startswith("whatif_run:")]
    assert read_all(ctx) == dict.fromkeys(METRICS)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["per_layer"] = bench["per_layer"][-len(METRICS):]
    for cell in bench["workloads"]:
        assert run.read_layer_metrics(bench, cell, dict(ctx)) == {}


def test_a_gap_on_device_1_alone_is_counted():
    """Batch 0 with its ``device_wait`` span taken away, so that the chunk
    loop's stretch lies under the root alone; then device 1 skips its second
    chunk program while device 0 runs it. The gap is the root's, by exactly
    the busy time device 1 lost; device 0 alone (as ``breakdown.idle_gaps``
    reads a trace) sees no change at all."""
    cut, _ = recorded("two_device")
    wait = next(e for e in cut["program_span_events"] if e[0] == "device_wait")
    cut["program_span_events"].remove(wait)
    skipped = copy.deepcopy(cut)
    dev = skipped["devices"][1]
    name, s, d = sorted(m for m in dev["modules"]
                        if m[0].startswith("jit_per_scenario"))[1]
    assert wait[1] < s and s + d < wait[1] + wait[2]
    dev["modules"].remove([name, s, d])
    kept = [op for op in dev["ops"] if op[1] + op[2] <= s or op[1] >= s + d]
    lost = sum(op[2] for op in dev["ops"]) - sum(op[2] for op in kept)
    dev["ops"] = kept
    a, b = (_program_spans.idle(context(c)) for c in (cut, skipped))
    assert lost > 30e6 and sum(b.values()) - sum(a.values()) == lost
    moved = a["in-program"] - b["in-program"]  # idle inside the skipped run
    assert b["root"] - a.get("root", 0) == lost + moved
    assert {k: v for k, v in a.items() if k not in ("root", "in-program")} == {
        k: v for k, v in b.items() if k not in ("root", "in-program")}
    share = run.load_part("layer_metrics", "idle_unattributed_share")
    assert share.read(context(skipped)) == pytest.approx(
        100 * b["root"] / sum(b.values()))
    assert share.read(context(skipped)) > 20 > 5 > share.read(context(cut))
    first = lambda c: context(dict(c, devices=c["devices"][:1]))
    assert _program_spans.idle(first(cut)) == _program_spans.idle(first(skipped))


def test_the_metrics_on_a_hand_made_batch():
    """Two chips, one batch 0..1000 us, the root 10..990: chip 1 starts 20
    us after chip 0; 45 us of the root lie under no span."""
    def chip(shift):
        at = lambda us: (us + shift) * US
        return {"modules": [["jit_per_scenario_src(1)", at(100), 200 * US],
                            ["jit_per_scenario_src(1)", at(400), 200 * US]],
                "ops": [["%fusion.1 = f32[] fusion()", at(100), 90 * US],
                        ["%fusion.2 = f32[] fusion()", at(200), 100 * US],
                        ["%fusion.1 = f32[] fusion()", at(400), 200 * US]],
                "dropped": []}

    spans = [["bench:batch:0", 0, 1000 * US, 1, {}],
             ["whatif_run:1", 10 * US, 980 * US, 1, {}],
             ["stage", 12 * US, 80 * US, 1, {}],
             ["mesh_put", 20 * US, 10 * US, 1, {"bytes": 5}],
             ["dispatch", 95 * US, 10 * US, 1, {}],
             ["chunk:0", 96 * US, 8 * US, 1, {}],
             ["dispatch", 110 * US, 10 * US, 1, {}],
             ["chunk:1", 111 * US, 8 * US, 1, {}],
             ["device_wait", 125 * US, 500 * US, 1, {}],
             ["gather", 630 * US, 50 * US, 1, {}],
             ["handback", 700 * US, 280 * US, 1, {}],
             ["mesh_fetch", 720 * US, 200 * US, 1, {"bytes": 4000}],
             ["mesh_fetch", 5 * US, 2 * US, 2, {"bytes": 1}],  # another thread
             ["PjitFunction(f)", 300 * US, 5 * US, 1, {}]]  # not the program's
    cut = {"devices": [chip(0), chip(20)],
           "host": [["bench:batch:0", 0, 1000 * US]],
           "program_span_events": spans}
    assert read_all(context(cut)) == pytest.approx({
        # chip 0 idles 0..100 (stage), 190..200 (in-program), 300..400
        # (device_wait), 600..1000 (middle 800: mesh_fetch); chip 1 the same
        # 20 us later: nothing under the root alone
        "idle_unattributed_share": 0.0,
        "host_untraced_share": 100 * (980 - 80 - 10 - 10 - 500 - 50 - 280) / 980,
        "host_stage_ms_per_batch": 0.08, "host_dispatch_ms_per_batch": 0.02,
        "host_gather_ms_per_batch": 0.05, "host_handback_ms_per_batch": 0.28,
        "mesh_fetch_ms_per_batch": 0.2})
    # without device_wait the 100 us between the chunk programs are the
    # root's on both chips: 200 of 1220 idle us
    cut["program_span_events"] = [e for e in spans if e[0] != "device_wait"]
    share = run.load_part("layer_metrics", "idle_unattributed_share")
    assert share.read(context(cut)) == pytest.approx(100 * 200 / 1220)
