"""A Borg cell that is full, on the program's side (PR 41): the trace
ingest's ``bound_node`` column, the sampled resident set and arrival window
(``residentFill`` / ``tasksPerDay``), what ``validate`` says of them, and the
CLI's what-if with ``placements`` under a retry buffer. The device retry
path itself is held in tests/test_retry_device.py, the benchmark's cell in
benchmark/tests/test_backlog_cell.py."""

import json

import numpy as np
import pytest

from kubernetes_simulator_tpu.cli import validate_config
from kubernetes_simulator_tpu.models.encode import PAD
from kubernetes_simulator_tpu.models.state import bind, init_state
from kubernetes_simulator_tpu.sim import borg
from kubernetes_simulator_tpu.utils.config import SimConfig


def test_ingest_takes_a_resident_set_and_without_one_changes_nothing():
    spec = borg.BorgSpec(nodes=16, tasks=200, seed=3)
    cols = borg._sample_cols(spec)
    ec, ep, meta = borg.encoded_from_cols(spec, cols)
    assert (ep.bound_node == PAD).all() and meta["resident"] == 0
    np.testing.assert_array_equal(ep.arrival, cols["arrival"])
    bound = np.full(200, -1, np.int32)
    bound[:40] = np.arange(40) % 16
    ec2, ep2, meta2 = borg.encoded_from_cols(spec, {**cols, "bound_node": bound})
    np.testing.assert_array_equal(ep2.bound_node, bound)
    assert meta2["resident"] == 40
    # a resident is there at t = 0 and its duration counts from 0
    assert (ep2.arrival[:40] == 0).all()
    np.testing.assert_array_equal(ep2.arrival[40:], cols["arrival"][40:])
    np.testing.assert_array_equal(ep2.duration, cols["duration"])
    np.testing.assert_array_equal(ep2.requests, ep.requests)
    with pytest.raises(ValueError, match="names node 16 of 16"):
        borg.encoded_from_cols(spec, {**cols, "bound_node": bound + 1})


def test_the_resident_fold_is_the_loop_of_binds():
    """``init_state`` folds the pre-bound pods in one vectorized pass; the
    state is what binding them one by one gives."""
    spec = borg.BorgSpec(nodes=24, tasks=300, seed=5, resident_fill=0.6)
    ec, ep, meta = borg.make_borg_encoded(spec)
    assert meta["resident"] > 50
    fast = init_state(ec, ep)
    slow = init_state(ec, ep, apply_prebound=False)
    for p in np.nonzero(ep.bound_node >= 0)[0]:
        bind(ec, ep, slow, int(p), int(ep.bound_node[p]))
    for a, b in ((fast.used, slow.used), (fast.match_count, slow.match_count),
                 (fast.anti_active, slow.anti_active),
                 (fast.pref_wsum, slow.pref_wsum), (fast.bound, slow.bound)):
        np.testing.assert_array_equal(a, b)


def test_the_sampled_resident_set_fills_the_nodes_and_the_window_runs_at_the_days_rate():
    spec = borg.BorgSpec(nodes=40, tasks=2000, seed=1, tasks_per_day=4000,
                         resident_fill=0.9, resident_band=0.05)
    ec, ep, meta = borg.make_borg_encoded(spec)
    R = meta["resident"]
    res = ep.bound_node >= 0
    assert R == int(res.sum()) and res[:R].all() and ep.num_pods == R + 2000
    ci = ec.vocab._r["cpu"]
    use = np.bincount(ep.bound_node[res], ep.requests[res, ci], 40)
    share = use / ec.allocatable[:, ci]
    assert (share <= 0.95 + 1e-6).all() and (share > 0.78).all()
    assert 0.86 < use.sum() / ec.allocatable[:, ci].sum() < 0.92
    assert set(np.unique(ep.requests[res, ci])) <= {1.0, 2.0, 4.0, 8.0}
    mi = ec.vocab._r["memory"]
    assert (np.bincount(ep.bound_node[res], ep.requests[res, mi], 40)
            <= ec.allocatable[:, mi]).all()
    assert (ep.group_id[res] == PAD).all() and (ep.arrival[res] == 0).all()
    # 2,000 tasks of a 4,000-task day: half a day, not the day thinned
    assert ep.arrival[~res].max() == pytest.approx(43200, rel=0.1)
    day = borg.make_borg_encoded(borg.BorgSpec(nodes=40, tasks=2000, seed=1))[1]
    assert day.arrival.max() > 60000 and (day.bound_node == PAD).all()


def config_of(**borg_keys):
    return SimConfig.from_dict({
        "strategy": "jax", "chunkWaves": 16,
        "workload": {"borg": {"nodes": 64, "tasks": 4096, **borg_keys}},
        "whatIf": {"scenarios": 2, "completions": True, "retryBuffer": 64,
                   "placements": True},
    })


@pytest.mark.parametrize("keys, says", [
    ({"residentFill": 0.95, "tasksPerDay": 6400}, None),
    ({"residentFill": 1.2}, "residentFill"),
    ({"residentFill": 0.03, "residentBand": 0.05}, "residentFill"),
    ({"tasksPerDay": -1}, "tasksPerDay"),
    ({"residentFill": 0.5, "tracePath": "/nonexistent.csv"}, "brings its own"),
])
def test_validate_knows_the_resident_set_and_the_window(keys, says):
    errors = validate_config(config_of(**keys))
    if says is None:
        assert errors == []
    else:
        assert any(says in e for e in errors), errors


def test_validate_placements_need_a_what_if_batch():
    cfg = config_of()
    cfg.whatif.scenarios = 0
    assert any("whatIf.placements" in e for e in validate_config(cfg))


def test_the_example_spells_the_deployment():
    cfg = SimConfig.load("examples/config_borg_backlog.yaml")
    assert validate_config(cfg) == []
    assert (cfg.borg.nodes, cfg.borg.tasks) == (10000, 131072)
    assert cfg.borg.tasks_per_day == 1_000_000 and cfg.borg.resident_fill == 0.95
    assert cfg.whatif.retry_buffer == 4096 and cfg.whatif.placements
    assert cfg.whatif.scenarios == 128 and cfg.chunk_waves == 768
    bench = json.load(open("benchmark/configs/borg2019-10k-backlog.json"))
    assert bench["engine"]["retryBuffer"] == cfg.whatif.retry_buffer
    assert bench["workload"]["resident"]["fill"] == cfg.borg.resident_fill
    assert bench["workload"]["deployedTasksPerDay"] == cfg.borg.tasks_per_day


def test_cli_what_if_hands_back_the_queues_outcome(tmp_path, capsys):
    import yaml

    from kubernetes_simulator_tpu.cli import main

    path = tmp_path / "backlog.yaml"
    out = tmp_path / "rows.jsonl"
    path.write_text(yaml.safe_dump({
        "strategy": "jax", "waveWidth": 8, "chunkWaves": 16, "output": str(out),
        "workload": {"borg": {"nodes": 64, "tasks": 2048, "seed": 0,
                              "tasksPerDay": 6400, "residentFill": 0.95}},
        "whatIf": {"scenarios": 2, "seed": 0, "completions": True,
                   "retryBuffer": 64, "placements": True},
    }))
    assert main(["what-if", str(path)]) == 0
    rows = [json.loads(line) for line in out.read_text().splitlines()]
    scen = [r for r in rows if r["kind"] == "whatif-scenario"]
    assert len(scen) == 2
    for r in scen:
        assert r["placed"] + r["unschedulable"] == 2048
        assert r["retry_placed"] > 0
        assert {"queued_at_end", "retry_dropped"} <= set(r)
