"""Round 14: paged pod waves (the node-sharding half of the round was
removed in PR 29 — the file keeps its name).

The contract under test: ``paged`` is a pure memory/latency knob —
placements and result summaries are BIT-IDENTICAL paged on/off.

Also here: the paged-mode gang guard in pack_waves, the
KSIM_MAX_REPLICATED_BYTES refusal gate, the knob-combination validation
raises, and byte-parity for the round-14 DCN gather payload compression
(delta+zlib with raw-zlib overflow fallback).
"""

import numpy as np
import pytest

from kubernetes_simulator_tpu.framework.framework import FrameworkConfig
from kubernetes_simulator_tpu.models.encode import encode
from kubernetes_simulator_tpu.sim.jax_runtime import (
    JaxReplayEngine,
    replicated_resident_bytes,
)
from kubernetes_simulator_tpu.sim.synthetic import make_cluster, make_workload


def _case(n_nodes=24, n_pods=220, seed=7):
    """Full plugin surface: taints, affinity/anti-affinity, spread,
    tolerations, gangs, finite durations (completions on)."""
    cluster = make_cluster(n_nodes, seed=seed, taint_fraction=0.2)
    pods, _ = make_workload(
        n_pods, seed=seed, with_affinity=True, with_spread=True,
        with_tolerations=True, gang_fraction=0.1, gang_size=4,
        duration_mean=40.0,
    )
    return encode(cluster, pods)


def _stable_summary(res):
    """summary() minus the wall-clock-derived fields (the exact set the
    KSIM_DETERMINISTIC_JSONL scrub zeroes)."""
    row = dict(res.summary())
    for k in ("wall_clock_s", "placements_per_sec"):
        row.pop(k, None)
    return row


def test_paged_parity():
    """Paged pod waves change residency, not results: paged ≡ unpaged."""
    ec, ep = _case()
    # telemetry="off": phase timers are wall clocks.
    ref, res = (
        JaxReplayEngine(
            ec, ep, FrameworkConfig(), chunk_waves=4, paged=paged,
            telemetry="off",
        ).replay()
        for paged in (False, True)
    )
    np.testing.assert_array_equal(
        res.assignments, ref.assignments,
        err_msg="paged: assignments diverged",
    )
    assert _stable_summary(res) == _stable_summary(ref)


def test_pack_waves_rejects_page_smaller_than_gang():
    """Satellite bugfix: a page smaller than the largest gang would
    split the gang across page evictions — refuse up front, actionably."""
    from kubernetes_simulator_tpu.sim.waves import pack_waves

    _, ep = _case(n_pods=64)
    pods, _ = make_workload(
        64, seed=7, gang_fraction=0.5, gang_size=8,
    )
    _, ep = encode(make_cluster(8, seed=7), pods)
    with pytest.raises(ValueError, match="largest gang"):
        pack_waves(ep, 8, page_pods=4)
    # Page >= largest gang: packs fine.
    assert pack_waves(ep, 8, page_pods=8).idx.shape[1] == 8


def test_replicated_refusal_gate(monkeypatch):
    """KSIM_MAX_REPLICATED_BYTES refuses the replicated path past the
    budget (pointing at paged)."""
    ec, ep = _case(n_pods=64)
    assert replicated_resident_bytes(ec, ep) > 1000
    monkeypatch.setenv("KSIM_MAX_REPLICATED_BYTES", "1000")
    with pytest.raises(ValueError, match="KSIM_MAX_REPLICATED_BYTES"):
        JaxReplayEngine(ec, ep, FrameworkConfig())


def test_knob_combination_raises():
    ec, ep = _case(n_pods=64)
    with pytest.raises(ValueError, match="paged=True is not supported"):
        JaxReplayEngine(ec, ep, FrameworkConfig(), paged=True, retry_buffer=8)


# ── the removed node axis (PR 29) ────────────────────────────────────


@pytest.mark.parametrize(
    "doc, key",
    [
        ({"nodeShards": 2}, "nodeShards"),
        ({"overlap": {"twoPhaseExchange": True}}, "overlap.twoPhaseExchange"),
    ],
    ids=["nodeShards", "twoPhaseExchange"],
)
def test_removed_node_axis_keys_refused(doc, key, tmp_path, capsys):
    """A YAML file that still asks for the node axis is refused with the
    sentence that says it went and why, by ``from_dict`` and so by the
    ``validate`` subcommand."""
    import yaml

    from kubernetes_simulator_tpu.cli import main
    from kubernetes_simulator_tpu.utils.config import SimConfig

    doc = {"strategy": "jax", **doc}
    with pytest.raises(ValueError, match="node sharding was removed") as ei:
        SimConfig.from_dict(doc)
    assert str(ei.value).startswith(key + ":") and "36.7 MB" in str(ei.value)
    path = tmp_path / "old.yaml"
    path.write_text(yaml.safe_dump(doc))
    assert main(["validate", str(path)]) == 1
    assert "node sharding was removed" in capsys.readouterr().out


@pytest.mark.parametrize("shards", [0, 1])
def test_replicated_node_shards_accepted_and_ignored(shards):
    """``nodeShards: 0/1`` always meant the replicated path, so configs
    that carry it keep running; nothing of it reaches the config."""
    from kubernetes_simulator_tpu.cli import validate_config
    from kubernetes_simulator_tpu.utils.config import SimConfig

    cfg = SimConfig.from_dict({"strategy": "jax", "nodeShards": shards})
    assert not hasattr(cfg, "node_shards")
    assert validate_config(cfg) == []


@pytest.mark.parametrize("engine", ["replay", "whatif"])
def test_engines_take_no_node_shards(engine):
    from kubernetes_simulator_tpu.sim.whatif import (
        WhatIfEngine,
        uniform_scenarios,
    )

    ec, ep = _case(n_pods=64)
    with pytest.raises(TypeError, match="node_shards"):
        if engine == "replay":
            JaxReplayEngine(ec, ep, FrameworkConfig(), node_shards=2)
        else:
            WhatIfEngine(
                ec, ep, uniform_scenarios(ec, 2, seed=0), FrameworkConfig(),
                node_shards=2,
            )


# ── DCN gather payload compression (round-14 satellite) ──────────────


def _roundtrip(payload):
    from kubernetes_simulator_tpu.parallel.dcn import (
        _pack_leaf,
        _unpack_leaf,
        _walk_payload,
    )

    packed = _walk_payload(payload, _pack_leaf)
    return packed, _walk_payload(packed, _unpack_leaf)


def test_dcn_compression_byte_parity():
    from kubernetes_simulator_tpu.parallel.dcn import _PackedArray

    rng = np.random.default_rng(0)
    payload = {
        "assignments": rng.integers(-1, 500, size=(4, 4096), dtype=np.int32),
        "placed": rng.integers(0, 4096, size=(4,), dtype=np.int64),
        "util": rng.random((4,), dtype=np.float32),
        "nested": [np.arange(2048, dtype=np.int64), None],
        "tiny": np.arange(8, dtype=np.int32),  # below the size floor
    }
    packed, out = _roundtrip(payload)
    # The large int planes actually took the packed path...
    assert isinstance(packed["assignments"], _PackedArray)
    assert packed["assignments"].codec == "delta-zlib"
    # ...small/float leaves pass through untouched...
    assert packed["util"] is payload["util"]
    assert packed["tiny"] is payload["tiny"]
    # ...and the decode is byte-exact, dtype and shape included.
    for k in ("assignments", "placed", "util", "tiny"):
        assert out[k].dtype == payload[k].dtype
        np.testing.assert_array_equal(out[k], payload[k])
    np.testing.assert_array_equal(out["nested"][0], payload["nested"][0])
    assert out["nested"][1] is None


def test_dcn_compression_delta_overflow_fallback():
    """int64 values whose DELTAS fit int32 use the delta codec even when
    the values don't; deltas past int32 fall back to raw zlib — both
    byte-exact."""
    from kubernetes_simulator_tpu.parallel.dcn import _PackedArray

    # Monotone int64 whose VALUES overflow int32 but whose deltas (the
    # first delta is the first value — prepend 0) all fit -> delta-zlib.
    big_sorted = np.cumsum(np.full(4096, 1 << 20, dtype=np.int64))
    assert big_sorted.max() > np.iinfo(np.int32).max
    # Alternating extremes: deltas overflow int32 -> raw zlib fallback.
    extremes = np.empty(4096, dtype=np.int64)
    extremes[0::2], extremes[1::2] = np.iinfo(np.int64).min // 2, \
        np.iinfo(np.int64).max // 2
    packed, out = _roundtrip({"a": big_sorted, "b": extremes})
    assert isinstance(packed["a"], _PackedArray)
    assert packed["a"].codec == "delta-zlib"
    if isinstance(packed["b"], _PackedArray):  # incompressible may pass raw
        assert packed["b"].codec == "zlib"
    np.testing.assert_array_equal(out["a"], big_sorted)
    np.testing.assert_array_equal(out["b"], extremes)
