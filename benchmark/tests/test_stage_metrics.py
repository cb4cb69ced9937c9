"""The stage metrics' readers on a small hand-made trace, on the CPU:

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q

The recorded ``testdata/trace_cut.json`` has its op events merged and carries
no instruction names, so the events here are made by hand and passed through
``trace_reduce.Reduced`` as a real trace's are, beside a hand-made stage table
in the place of ``utils.profiling.stage_tables()``.
"""

import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path[:0] = [str(ROOT), str(BENCH)]

import run  # noqa: E402
import trace_reduce  # noqa: E402
from kubernetes_simulator_tpu.utils import profiling  # noqa: E402

US = 1000  # the trace's clock is in ns
TABLE = {"jit_chunk_fn": {
    "fusion.1": "ksim.reads", "dynamic-slice.2": "ksim.gather",
    "fusion.3": "ksim.corrections",
    "fusion.4": "ksim.filter_score/NodeResourcesFit",
    "fusion.5": "ksim.filter_score", "fusion.6": "ksim.select",
    "fusion.7": "ksim.commit", "copy.8": "", "fusion.10": "ksim.derive",
}}
STAGE_METRICS = {  # two executions of two waves: four waves
    "chunk_ops_per_wave": 20 / 4,
    "chunk_reads_ms_per_wave": 2 * (10 + 5 + 6) * 1e-3 / 4,
    "chunk_corrections_ms_per_wave": 2 * 20 * 1e-3 / 4,
    "chunk_filter_score_ms_per_wave": 2 * (30 + 10) * 1e-3 / 4,
    "chunk_select_ms_per_wave": 2 * 15 * 1e-3 / 4,
    "chunk_commit_ms_per_wave": 2 * 8 * 1e-3 / 4,
    # copy.8 (no scope) and fusion.9 (in no table) of 112 us an execution
    "chunk_unattributed_share": 100 * (4 + 4) / 112,
}
OTHER_METRICS = {
    "release_host_ms_per_boundary": 0.05,  # median of 30, 50, 70 us
    "release_device_ms_per_boundary": 0.003,
    "stage_ms_per_batch": 0.1,
    "gather_ms_per_batch": 0.25,
}


def events():
    """One traced batch, 0..1000 us: two executions of the chunk program
    (100..300 and 400..600 us), the release program between them."""
    ops, modules = [], []
    body = [("fusion.1", 10), ("dynamic-slice.2", 5), ("fusion.3", 20),
            ("fusion.4", 30), ("fusion.5", 10), ("fusion.6", 15),
            ("fusion.7", 8), ("copy.8", 4), ("fusion.9", 4)]
    for start in (100, 400):
        modules.append(["jit_chunk_fn(7)", start * US, 200 * US])
        t = start
        ops.append([f"%fusion.10 = f32[64]{{0}} fusion(%p)", t * US, 6 * US])
        t += 10
        ops.append(["%while.3 = (s32[], f32[3,64]) while(%tuple.1)",
                    t * US, 150 * US])
        for name, us in body:  # the loop's ops lie inside the loop's event
            ops.append([f"%{name} = s32[]{{:T(128)}} fusion(%a, %b)",
                        t * US, us * US])
            t += us + 1
    modules.append(["jit_release_subtract(9)", 320 * US, 3 * US])
    ops.append(["%fusion.1 = f32[3,64]{1,0} fusion(%s, %d)", 320 * US, 3 * US])
    host = [["bench:batch:0", 0, 850 * US],
            ["host_mirror", 50 * US, 30 * US], ["host_mirror", 310 * US, 70 * US],
            ["host_mirror", 610 * US, 50 * US], ["dispatch", 90 * US, 5 * US]]
    return {"devices": [{"modules": modules, "ops": ops, "dropped": []}],
            "host": sorted(host, key=lambda e: e[1])}


def read_all(names):
    ctx = {"trace": trace_reduce.Reduced(events()), "shape": {"chunk_waves": 2}}
    return {m: run.load_part("layer_metrics", m).read(ctx) for m in names}


def test_every_new_metric_has_a_reader_and_an_entry():
    b = json.loads((ROOT / "BENCHMARK.json").read_text())
    entries = {m["name"]: m for m in b["per_layer"]}
    for name in list(STAGE_METRICS) + list(OTHER_METRICS):
        assert entries[name]["moves"] == "placements_per_s"
        assert entries[name]["workloads"] == ["borg10k-replay1"]
        assert (BENCH / "layer_metrics" / f"{name}.py").is_file()


def test_stage_metrics_on_a_hand_made_trace(monkeypatch, capsys):
    monkeypatch.setattr(profiling, "stage_tables", lambda: TABLE, raising=False)
    got = read_all(STAGE_METRICS)
    assert got == pytest.approx(STAGE_METRICS, rel=1e-9)
    # the stages and the unattributed time are all of the leaves' time
    staged = sum(v for k, v in got.items() if k.endswith("_ms_per_wave"))
    assert staged / (1 - got["chunk_unattributed_share"] / 100) == \
        pytest.approx(2 * 112e-3 / 4)
    # the per-plugin level goes to stderr, not to the ledger
    err = capsys.readouterr().err
    assert "stage ksim.filter_score/NodeResourcesFit 0.01500 ms/wave" in err


@pytest.mark.parametrize("tables", ["absent", "empty", "raises"])
def test_stage_metrics_read_nothing_without_a_table(monkeypatch, tables):
    """An older tree has no ``stage_tables``; one that registered nothing
    gives {}; neither takes the result line down."""
    def boom():
        raise RuntimeError("no executable text")

    if tables == "absent":
        monkeypatch.delattr(profiling, "stage_tables", raising=False)
    else:
        monkeypatch.setattr(profiling, "stage_tables",
                            dict if tables == "empty" else boom, raising=False)
    assert read_all(STAGE_METRICS) == dict.fromkeys(STAGE_METRICS)


def test_boundary_and_batch_metrics_on_a_hand_made_trace():
    assert read_all(OTHER_METRICS) == pytest.approx(OTHER_METRICS, rel=1e-9)


def test_boundary_and_batch_metrics_read_nothing_where_nothing_ran():
    """No release program, no host_mirror span, no chunk program."""
    ev = events()
    ev["devices"][0]["modules"] = []
    ev["host"] = [e for e in ev["host"] if e[0] != "host_mirror"]
    ctx = {"trace": trace_reduce.Reduced(ev), "shape": {"chunk_waves": 2}}
    names = list(OTHER_METRICS) + list(STAGE_METRICS)
    got = {m: run.load_part("layer_metrics", m).read(ctx) for m in names}
    assert got == dict.fromkeys(names)
