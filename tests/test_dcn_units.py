"""Single-process unit tests for the round-11 DCN plumbing: slicing
arithmetic, mesh localization, per-process output paths, DCN-aware
population fitting, the concurrent-safe compile cache, the
enable-before-initialize ordering contract, deterministic JSONL, and the
schema checker's round-11 fields — everything that doesn't need a real
2-process fleet (tests/test_dcn.py covers that)."""

import json
import os

import jax
import numpy as np
import pytest

from kubernetes_simulator_tpu.parallel import dcn
from kubernetes_simulator_tpu.parallel.mesh import (
    fit_population,
    make_mesh,
    spans_processes,
)

# -- slicing / mesh localization -------------------------------------------


def test_local_slice_contiguous_blocks(monkeypatch):
    monkeypatch.setattr(dcn, "process_info", lambda: (2, 0))
    assert dcn.local_slice(8) == slice(0, 4)
    monkeypatch.setattr(dcn, "process_info", lambda: (2, 1))
    assert dcn.local_slice(8) == slice(4, 8)
    monkeypatch.setattr(dcn, "process_info", lambda: (4, 2))
    assert dcn.local_slice(8) == slice(4, 6)


def test_local_slice_identity_single_process():
    assert dcn.local_slice(8) == slice(0, 8)


def test_spans_processes_and_localize_identity():
    """Single-process meshes never span; localize_mesh is the identity for
    them and for None (the production call sits unconditionally in
    WhatIfEngine.__init__, so the identity path IS the common path)."""
    mesh = make_mesh()
    assert not spans_processes(None)
    assert not spans_processes(mesh)
    assert dcn.localize_mesh(None) is None
    assert dcn.localize_mesh(mesh) is mesh


def test_output_path_for_process(monkeypatch):
    assert dcn.output_path_for_process(None) is None
    monkeypatch.setattr(dcn, "process_info", lambda: (2, 0))
    assert dcn.output_path_for_process("out.jsonl") == "out.jsonl"
    monkeypatch.setattr(dcn, "process_info", lambda: (2, 1))
    assert dcn.output_path_for_process("out.jsonl") == "out.jsonl.p1"


def test_gather_requires_initialized_coordinator():
    with pytest.raises(RuntimeError, match="not initialized"):
        dcn.gather("never", {"x": 1})


def test_maybe_init_noop_without_env(monkeypatch):
    for k in ("KSIM_DCN_COORD", "DCN_COORD", "KSIM_DCN_NPROC", "DCN_NPROC"):
        monkeypatch.delenv(k, raising=False)
    assert dcn.maybe_init_from_env() is False


def test_enable_cache_before_initialize_ordering(monkeypatch):
    """The regression pin for the round-11 ordering contract:
    ``maybe_init_from_env`` must configure the persistent compile cache
    BEFORE ``jax.distributed.initialize`` (a cache enabled after the
    backend exists misses the very compiles the DCN workers share)."""
    import kubernetes_simulator_tpu.parallel.mesh as mesh_mod
    import kubernetes_simulator_tpu.utils.compile_cache as cc

    calls = []
    monkeypatch.setattr(cc, "enable", lambda *a, **k: calls.append("cache"))
    monkeypatch.setattr(
        mesh_mod, "init_distributed",
        lambda **kw: calls.append(("init", kw["num_processes"],
                                   kw["process_id"])),
    )
    monkeypatch.setenv("KSIM_DCN_COORD", "127.0.0.1:1")
    monkeypatch.setenv("KSIM_DCN_NPROC", "2")
    monkeypatch.setenv("KSIM_DCN_PID", "1")
    assert dcn.maybe_init_from_env() is True
    assert calls == ["cache", ("init", 2, 1)]


# -- engine-level slicing (process count faked; construction only) ---------


def _tiny_batch(S):
    from kubernetes_simulator_tpu.models.encode import encode
    from kubernetes_simulator_tpu.sim.synthetic import (
        make_cluster,
        make_workload,
    )
    from kubernetes_simulator_tpu.sim.whatif import uniform_scenarios

    cluster = make_cluster(6, seed=3)
    pods, _ = make_workload(16, seed=3)
    ec, ep = encode(cluster, pods)
    return ec, ep, uniform_scenarios(ec, S, seed=3, p_capacity=0.5)


def test_engine_slices_scenarios_per_process(monkeypatch):
    """With a faked 2-process world the engine keeps only its contiguous
    half of the scenario axis (construction only — running would need the
    real coordinator)."""
    from kubernetes_simulator_tpu.framework.framework import FrameworkConfig
    from kubernetes_simulator_tpu.sim.whatif import WhatIfEngine

    ec, ep, scenarios = _tiny_batch(8)
    monkeypatch.setattr(jax, "process_count", lambda: 2)
    monkeypatch.setattr(jax, "process_index", lambda: 1)
    eng = WhatIfEngine(ec, ep, scenarios, FrameworkConfig(), chunk_waves=4)
    assert eng._dcn_sliced
    assert eng.S_global == 8 and eng.S == 4
    assert eng._proc_lo == 4


def test_engine_replicates_on_uneven_batch(monkeypatch):
    from kubernetes_simulator_tpu.framework.framework import FrameworkConfig
    from kubernetes_simulator_tpu.sim.whatif import WhatIfEngine

    ec, ep, scenarios = _tiny_batch(7)
    monkeypatch.setattr(jax, "process_count", lambda: 2)
    monkeypatch.setattr(jax, "process_index", lambda: 0)
    eng = WhatIfEngine(ec, ep, scenarios, FrameworkConfig(), chunk_waves=4)
    assert not eng._dcn_sliced
    assert eng.S == 7


def test_engine_rejects_set_label_under_dcn(monkeypatch):
    from kubernetes_simulator_tpu.framework.framework import FrameworkConfig
    from kubernetes_simulator_tpu.sim.whatif import (
        Perturbation,
        Scenario,
        WhatIfEngine,
    )

    ec, ep, _ = _tiny_batch(2)
    scenarios = [
        Scenario(),
        Scenario([Perturbation(
            "set_label", nodes=np.array([0]),
            key="topology.kubernetes.io/zone", value="zz",
        )]),
    ]
    monkeypatch.setattr(jax, "process_count", lambda: 2)
    with pytest.raises(ValueError, match="set_label"):
        WhatIfEngine(ec, ep, scenarios, FrameworkConfig(), chunk_waves=4)


def test_single_process_run_untouched_by_dcn_paths():
    """The common case: no DCN env, no slicing, no gather, result stamps
    process_count=1 — and the replication counter stays zero (the
    local-mesh chunk loop never round-trips full tensors)."""
    from kubernetes_simulator_tpu.framework.framework import FrameworkConfig
    from kubernetes_simulator_tpu.sim.whatif import WhatIfEngine

    ec, ep, scenarios = _tiny_batch(8)
    g0 = dcn.GATHER_COUNT
    eng = WhatIfEngine(
        ec, ep, scenarios, FrameworkConfig(), mesh=make_mesh(),
        chunk_waves=4,
    )
    res = eng.run()
    assert not eng._dcn_sliced
    assert eng._replicate_count == 0
    assert dcn.GATHER_COUNT == g0
    assert res.process_count == 1
    assert res.n_devices == 8


# -- fit_population: DCN factorizations ------------------------------------


def test_fit_population_single_process_mesh():
    mesh = make_mesh()  # 8 devices (conftest forces 8 virtual CPUs)
    assert fit_population(5, 3, mesh) == 8  # 8*3 first multiple of 8
    assert fit_population(5, 8, mesh) == 5  # already divides
    assert fit_population(1, 1, None) == 1


def test_fit_population_dcn_no_mesh(monkeypatch):
    monkeypatch.setattr(jax, "process_count", lambda: 2)
    # Mesh-less DCN sweep: the flat axis must still divide the process
    # count for the per-process slices to be even.
    assert fit_population(5, 3, None) == 6  # 6*3 even, 5*3 odd


def test_fit_population_dcn_local_mesh(monkeypatch):
    mesh = make_mesh()  # local 8 devices; x2 processes = 16 global
    monkeypatch.setattr(jax, "process_count", lambda: 2)
    assert fit_population(5, 3, mesh) == 16  # 16*3 = 48 divides 16
    assert fit_population(4, 4, mesh) == 4  # 16 divides 16 already


# -- compile cache: atomic writes + ordering -------------------------------


def test_atomic_put_writes_whole_entries(tmp_path):
    """The monkeypatched LRUCache.put goes through a per-process temp file
    + os.replace: the entry appears complete, no temp droppings remain,
    and a second put of the same key is a no-op (first writer wins)."""
    from jax._src import lru_cache as _lru

    from kubernetes_simulator_tpu.utils.compile_cache import (
        patch_atomic_writes,
    )

    patch_atomic_writes()
    cache = _lru.LRUCache(str(tmp_path), max_size=-1)
    cache.put("entry", b"x" * 1024)
    assert cache.get("entry") == b"x" * 1024
    files = sorted(p.name for p in tmp_path.iterdir())
    assert "entry-cache" in files
    assert not [f for f in files if ".tmp." in f], files
    cache.put("entry", b"y" * 1024)  # concurrent-sibling replay: kept
    assert cache.get("entry") == b"x" * 1024
    with pytest.raises(ValueError, match="empty"):
        cache.put("", b"z")


# -- deterministic JSONL ---------------------------------------------------


def test_deterministic_jsonl_zeroes_wall_clock(tmp_path, monkeypatch):
    """KSIM_DETERMINISTIC_JSONL=1 pins ts/wall_clock_s/placements_per_sec
    to 0.0 (fields stay present as numbers — schema v2 requires them), so
    DCN parity runs can compare JSONL bytes."""
    from kubernetes_simulator_tpu.utils.metrics import (
        JsonlWriter,
        deterministic_jsonl,
        whatif_rows,
    )

    monkeypatch.delenv("KSIM_DETERMINISTIC_JSONL", raising=False)
    assert not deterministic_jsonl()
    monkeypatch.setenv("KSIM_DETERMINISTIC_JSONL", "1")
    assert deterministic_jsonl()

    class _Res:
        placed = np.array([3, 4], np.int32)
        unschedulable = np.array([1, 0], np.int32)
        total_placed = 7
        wall_clock_s = 1.25
        placements_per_sec = 5.6
        completions_on = True
        engine = "v3"
        utilization_cpu = None

    path = tmp_path / "d.jsonl"
    with JsonlWriter(str(path), context={"seed": 0}) as out:
        for row in whatif_rows(_Res()):
            out.write(row)
    rows = [json.loads(l) for l in path.read_text().splitlines()]
    assert all(r["ts"] == 0.0 for r in rows)
    assert rows[0]["wall_clock_s"] == 0.0
    assert rows[0]["placements_per_sec"] == 0.0
    # identical rows ⇒ identical bytes, run to run
    with JsonlWriter(str(tmp_path / "e.jsonl"), context={"seed": 0}) as out:
        for row in whatif_rows(_Res()):
            out.write(row)
    assert (tmp_path / "e.jsonl").read_bytes() == path.read_bytes()


# -- schema checker: round-11 fields ---------------------------------------


def test_schema_accepts_dcn_fields():
    import sys

    sys.path.insert(
        0,
        os.path.abspath(
            os.path.join(os.path.dirname(__file__), "..", "scripts")
        ),
    )
    from check_metrics_schema import validate_row

    row = {
        "ts": 0.0, "schema": 2, "seed": 0, "engine": "v3",
        "config_hash": "h", "kind": "whatif-aggregate",
        "scenarios": 8, "total_placed": 100, "wall_clock_s": 0.0,
        "placements_per_sec": 0.0, "completions_on": True,
        "process_count": 2, "n_devices": 8,
        "mesh_shape": {"scenario": 8},
        "dcn_scaling": {"process_count": 2},
    }
    assert validate_row(row) == []
    assert validate_row({**row, "process_count": "2"})
    assert validate_row({**row, "dcn_scaling": 3})


# -- round-12 heartbeats / attributed gather timeout ------------------------


class _FakeKV:
    """In-memory stand-in for the jaxlib coordination-service KV client."""

    def __init__(self):
        self.store = {}

    def key_value_set(self, key, value, allow_overwrite=False):
        if not allow_overwrite and key in self.store:
            raise RuntimeError(f"key exists: {key}")
        self.store[key] = value

    def blocking_key_value_get(self, key, timeout_ms):
        import time

        if key in self.store:
            return self.store[key]
        time.sleep(timeout_ms / 1000.0)
        raise RuntimeError(f"Deadline Exceeded: {key}")

    def key_value_dir_get(self, prefix):
        return [
            (k, v) for k, v in sorted(self.store.items())
            if k.startswith(prefix)
        ]


def _fleet(monkeypatch, nproc=2, pid=1):
    kv = _FakeKV()
    monkeypatch.setattr(dcn, "process_info", lambda: (nproc, pid))
    monkeypatch.setattr(dcn, "_client", lambda: kv)
    # The degraded-fleet hard exit must never arm inside the TEST
    # process (it would override pytest's own exit status).
    monkeypatch.setattr(dcn, "_degraded_exit_armed", [True])
    monkeypatch.setattr(dcn, "DEGRADED", set())
    return kv


def test_heartbeat_noop_single_process(monkeypatch):
    kv = _FakeKV()
    monkeypatch.setattr(dcn, "_client", lambda: kv)
    assert dcn.heartbeat(3) is False
    assert kv.store == {}


def test_heartbeat_publishes_full_beacon(monkeypatch):
    kv = _fleet(monkeypatch, nproc=2, pid=1)
    ok = dcn.heartbeat(
        3, total=10, block=(4, 8), wall_s=1.5,
        phases={"dispatch": 0.25}, state="run",
    )
    assert ok is True
    beat = json.loads(kv.store[f"{dcn.HB_PREFIX}/1"])
    assert beat["pid"] == 1
    assert beat["chunk"] == 3
    assert beat["state"] == "run"
    assert beat["total_chunks"] == 10
    assert beat["block"] == [4, 8]
    assert beat["wall_s"] == 1.5
    assert beat["phases"] == {"dispatch": 0.25}
    assert isinstance(beat["t"], float)
    # live-buffer gauge (jax.live_arrays is available in-process)
    assert isinstance(beat["live_buffers"], int)


def test_heartbeat_overwrites_one_key(monkeypatch):
    kv = _fleet(monkeypatch, nproc=2, pid=0)
    assert dcn.heartbeat(0)
    assert dcn.heartbeat(5, state="gather")
    keys = [k for k in kv.store if k.startswith(dcn.HB_PREFIX)]
    assert keys == [f"{dcn.HB_PREFIX}/0"]
    beat = json.loads(kv.store[keys[0]])
    assert beat["chunk"] == 5 and beat["state"] == "gather"


def test_heartbeat_file_mirror(tmp_path, monkeypatch):
    _fleet(monkeypatch, nproc=2, pid=1)
    monkeypatch.setenv("KSIM_DCN_HB_DIR", str(tmp_path))
    assert dcn.heartbeat(2, total=4)
    beat = json.loads((tmp_path / "p1.json").read_text())
    assert beat["chunk"] == 2 and beat["total_chunks"] == 4
    assert not list(tmp_path.glob(".p*.tmp")), "tmp file left behind"


def test_maybe_heartbeat_cadence(monkeypatch):
    kv = _fleet(monkeypatch, nproc=2, pid=0)
    # every=4: the start-of-replay beacon (chunk_done=-1) always fires,
    # then chunks 3, 7, ... ((chunk_done+1) % every == 0).
    assert dcn.maybe_heartbeat(-1, every=4) is True
    assert dcn.maybe_heartbeat(0, every=4) is False
    assert dcn.maybe_heartbeat(2, every=4) is False
    assert dcn.maybe_heartbeat(3, every=4) is True
    assert dcn.maybe_heartbeat(7, every=4) is True
    # 0 disables entirely (and short-circuits before any KV traffic).
    kv.store.clear()
    assert dcn.maybe_heartbeat(-1, every=0) is False
    assert kv.store == {}


def test_heartbeat_every_env_default(monkeypatch):
    _fleet(monkeypatch, nproc=2, pid=0)
    assert dcn.heartbeat_every() == 1
    monkeypatch.setenv("KSIM_DCN_HEARTBEAT_EVERY", "0")
    assert dcn.heartbeat_every() == 0
    assert dcn.maybe_heartbeat(-1) is False


def test_read_heartbeats_parses_and_skips_junk(monkeypatch):
    kv = _fleet(monkeypatch, nproc=2, pid=0)
    kv.store[f"{dcn.HB_PREFIX}/0"] = json.dumps({"pid": 0, "chunk": 7})
    kv.store[f"{dcn.HB_PREFIX}/1"] = "not json"
    kv.store[f"{dcn.HB_PREFIX}/xx"] = json.dumps({})
    beats = dcn.read_heartbeats()
    assert set(beats) == {0}
    assert beats[0]["chunk"] == 7


def test_gather_timeout_stale_beacon_fails_fast(monkeypatch):
    """A sibling whose beacon went stale past KSIM_DCN_STALL_S is
    presumed dead: the gather wait aborts IMMEDIATELY with an attributed
    DcnGatherTimeout — long before the full KSIM_DCN_TIMEOUT_S."""
    import time

    kv = _fleet(monkeypatch, nproc=2, pid=0)
    monkeypatch.setenv("KSIM_DCN_TIMEOUT_S", "30")
    monkeypatch.setenv("KSIM_DCN_STALL_S", "0.05")
    monkeypatch.setenv("KSIM_DCN_POLL_S", "0.01")
    kv.store[f"{dcn.HB_PREFIX}/1"] = json.dumps(
        {"pid": 1, "chunk": 2, "total_chunks": 9, "state": "run",
         "t": time.time() - 10.0, "block": [4, 8]}
    )
    t0 = time.monotonic()
    with pytest.raises(dcn.DcnGatherTimeout) as ei:
        dcn._get_attributed(kv, "ksim/gather/1/x/1/n", 1, "x")
    assert time.monotonic() - t0 < 5.0, "did not fail fast"
    msg = str(ei.value)
    assert "process 1" in msg and "looks DEAD" in msg
    assert "last completed chunk 2/9" in msg
    assert "scenario block [4, 8)" in msg
    assert ei.value.missing == [1]
    assert 1 in ei.value.heartbeats


def test_gather_timeout_no_beacon_waits_full_deadline(monkeypatch):
    """No beacon is NO evidence of death (heartbeats may be disabled):
    the wait keeps round-11 semantics — full KSIM_DCN_TIMEOUT_S, then an
    attributed error naming the process that never published."""
    import time

    kv = _fleet(monkeypatch, nproc=2, pid=0)
    monkeypatch.setenv("KSIM_DCN_TIMEOUT_S", "0.2")
    monkeypatch.setenv("KSIM_DCN_POLL_S", "0.05")
    t0 = time.monotonic()
    with pytest.raises(dcn.DcnGatherTimeout) as ei:
        dcn._get_attributed(kv, "ksim/gather/1/x/1/n", 1, "x")
    assert time.monotonic() - t0 >= 0.15
    msg = str(ei.value)
    assert "timed out after KSIM_DCN_TIMEOUT_S=0.2s" in msg
    assert "no heartbeat ever received" in msg


def test_gather_wait_survives_fresh_beacon_then_delivers(monkeypatch):
    """A slow-but-alive sibling (fresh beacon) never trips the stall
    detector; the poll loop returns the value as soon as it lands."""
    import time

    kv = _fleet(monkeypatch, nproc=2, pid=0)
    monkeypatch.setenv("KSIM_DCN_TIMEOUT_S", "10")
    monkeypatch.setenv("KSIM_DCN_STALL_S", "60")
    monkeypatch.setenv("KSIM_DCN_POLL_S", "0.02")
    kv.store[f"{dcn.HB_PREFIX}/1"] = json.dumps(
        {"pid": 1, "chunk": 1, "t": time.time()}
    )
    calls = {"n": 0}
    real_get = kv.blocking_key_value_get

    def _late_get(key, timeout_ms):
        calls["n"] += 1
        if calls["n"] >= 3:
            kv.store.setdefault("k", "2")
        return real_get(key, timeout_ms)

    kv.blocking_key_value_get = _late_get
    assert dcn._get_attributed(kv, "k", 1, "x") == "2"
    assert calls["n"] >= 3


def test_jsonl_writer_stamps_process_under_dcn(tmp_path, monkeypatch):
    """Round 12: JSONL rows from a fleet carry process_id/process_count;
    single-process rows stay byte-unchanged (no stamp at all)."""
    from kubernetes_simulator_tpu.utils.metrics import JsonlWriter

    p1 = tmp_path / "single.jsonl"
    with JsonlWriter(str(p1)) as w:
        w.write({"kind": "x"})
    row = json.loads(p1.read_text())
    assert "process_id" not in row and "process_count" not in row

    monkeypatch.setattr(dcn, "process_info", lambda: (2, 1))
    p2 = tmp_path / "fleet.jsonl"
    with JsonlWriter(str(p2)) as w:
        w.write({"kind": "x"})
    row = json.loads(p2.read_text())
    assert row["process_id"] == 1 and row["process_count"] == 2


# -- round-15 recoverable work-queue ----------------------------------------


def test_recovery_knob_defaults(monkeypatch):
    for k in ("KSIM_DCN_RECOVER", "KSIM_DCN_CKPT_EVERY",
              "KSIM_DCN_MAX_CLAIMS", "KSIM_DCN_SPARES"):
        monkeypatch.delenv(k, raising=False)
    assert dcn.recover_enabled() is False
    assert dcn.ckpt_every() == 0
    assert dcn.max_claims() == 2
    assert dcn.spare_count() == 0
    monkeypatch.setenv("KSIM_DCN_RECOVER", "yes")
    monkeypatch.setenv("KSIM_DCN_CKPT_EVERY", "3")
    monkeypatch.setenv("KSIM_DCN_MAX_CLAIMS", "5")
    assert dcn.recover_enabled() is True
    assert dcn.ckpt_every() == 3
    assert dcn.max_claims() == 5
    monkeypatch.setenv("KSIM_DCN_CKPT_EVERY", "junk")
    monkeypatch.setenv("KSIM_DCN_MAX_CLAIMS", "0")
    assert dcn.ckpt_every() == 0
    assert dcn.max_claims() == 1  # floor: one claim generation always


def test_spares_shrink_worker_count_and_mirror_last_block(monkeypatch):
    monkeypatch.setattr(dcn, "process_info", lambda: (3, 2))
    monkeypatch.setenv("KSIM_DCN_SPARES", "1")
    assert dcn.worker_count() == 2
    assert dcn.is_spare() is True
    # The spare mirrors the LAST worker's block (shapes only — the
    # engine marks it _dcn_spare and never runs the chunks).
    assert dcn.local_slice(8) == slice(4, 8)
    monkeypatch.setattr(dcn, "process_info", lambda: (3, 1))
    assert dcn.is_spare() is False
    assert dcn.local_slice(8) == slice(4, 8)
    monkeypatch.setattr(dcn, "process_info", lambda: (3, 0))
    assert dcn.local_slice(8) == slice(0, 4)


def test_checkpoint_publish_load_roundtrip(monkeypatch):
    """publish_checkpoint → load_checkpoint round-trips the payload
    through the delta+zlib codec; the newest cursor wins; a torn blob
    (no ``/n`` manifest) is skipped; epochs are isolated."""
    kv = _fleet(monkeypatch, nproc=2, pid=1)
    pay0 = {"cursor": 1, "leaves": [np.arange(4096, dtype=np.int32)]}
    pay1 = {"cursor": 3, "leaves": [np.arange(4096, dtype=np.int32) * 2]}
    assert dcn.publish_checkpoint(1, pay0, (4, 8), epoch=7)
    assert dcn.publish_checkpoint(3, pay1, (4, 8), epoch=7)
    got = dcn.load_checkpoint(1, epoch=7)
    assert got is not None
    assert got["cursor"] == 3 and got["block"] == (4, 8)
    np.testing.assert_array_equal(
        got["payload"]["leaves"][0], pay1["leaves"][0]
    )
    assert got["payload"]["leaves"][0].dtype == np.int32
    # Torn blob: drop the manifest of the newest cursor — the reader
    # falls back to the older complete one.
    del kv.store[f"{dcn.CKPT_PREFIX}/7/1/4-8/3/n"]
    assert dcn.load_checkpoint(1, epoch=7)["cursor"] == 1
    # Epoch isolation: a previous replay's blobs are invisible.
    assert dcn.load_checkpoint(1, epoch=8) is None
    assert dcn.load_checkpoint(0, epoch=7) is None


def test_checkpoint_publish_noop_single_process(monkeypatch):
    kv = _FakeKV()
    monkeypatch.setattr(dcn, "_client", lambda: kv)
    assert dcn.publish_checkpoint(1, {"x": 1}, (0, 4)) is False
    assert kv.store == {}


def test_claim_cas_single_claimant_and_metadata_roundtrip(monkeypatch):
    """The write-once claim key admits exactly ONE claimant per
    generation; the loser reads the winner's metadata (claimant pid,
    block owner, generation) for attribution of a second failure."""
    kv = _fleet(monkeypatch, nproc=3, pid=0)
    assert dcn.try_claim(2, 0) is True
    # Same key from another pid: CAS loss.
    monkeypatch.setattr(dcn, "process_info", lambda: (3, 1))
    assert dcn.try_claim(2, 0) is False
    meta = dcn.read_claim(2, 0)
    assert meta["claimant"] == 0
    assert meta["for"] == 2
    assert meta["gen"] == 0
    assert isinstance(meta["t"], float)
    # Next generation is open, and namespaced separately.
    assert dcn.try_claim(2, 1) is True
    assert dcn.read_claim(2, 1)["claimant"] == 1
    assert dcn.read_claim(2, 2) is None


def test_recovery_heartbeat_names_claimed_block(monkeypatch):
    """Satellite: a recovering process beats under its OWN pid with the
    claimed block and the dead pid named, so a second failure during
    recovery is attributed to the claimant — round-tripped through
    read_heartbeats exactly as the stall detector reads it."""
    _fleet(monkeypatch, nproc=2, pid=0)
    assert dcn.heartbeat(
        -1, block=(4, 8), state="recover", extra={"recovering_for": 1}
    )
    beats = dcn.read_heartbeats()
    assert set(beats) == {0}
    beat = beats[0]
    assert beat["pid"] == 0  # the claimant's pid, never the dead one's
    assert beat["state"] == "recover"
    assert beat["recovering_for"] == 1
    assert beat["block"] == [4, 8]


def test_gather_wait_recovers_stale_sibling(monkeypatch, tmp_path):
    """With KSIM_DCN_RECOVER on and a recover callback, a stale sibling
    beacon triggers claim + re-execution + publication under the dead
    pid's keys instead of the attributed DcnGatherTimeout — and the
    claim/recovered events land in the KSIM_DCN_HB_DIR mirror."""
    import time

    kv = _fleet(monkeypatch, nproc=2, pid=0)
    monkeypatch.setenv("KSIM_DCN_TIMEOUT_S", "30")
    monkeypatch.setenv("KSIM_DCN_STALL_S", "0.05")
    monkeypatch.setenv("KSIM_DCN_POLL_S", "0.01")
    monkeypatch.setenv("KSIM_DCN_RECOVER", "1")
    monkeypatch.setenv("KSIM_DCN_HB_DIR", str(tmp_path))
    kv.store[f"{dcn.HB_PREFIX}/1"] = json.dumps(
        {"pid": 1, "chunk": 0, "state": "run", "t": time.time() - 10.0,
         "block": [4, 8]}
    )
    calls = []

    def _recover(p, gen):
        calls.append((p, gen))
        return {"placed": np.array([1, 2], np.int32)}

    got = dcn._get_attributed(
        kv, "ksim/gather/1/whatif/1/n", 1, "whatif", recover=_recover
    )
    assert calls == [(1, 0)]
    assert got == "1"  # the published manifest (one KV chunk)
    # Single-claimant key exists with our metadata.
    meta = dcn.read_claim(1, 0)
    assert meta["claimant"] == 0 and meta["for"] == 1
    # The dead pid's payload is decodable from its gather keys.
    part = dcn._decode_payload(
        [kv.store["ksim/gather/1/whatif/1/0"]]
    )
    np.testing.assert_array_equal(part["placed"], [1, 2])
    events = [
        json.loads(l)
        for l in (tmp_path / "events.jsonl").read_text().splitlines()
    ]
    assert [e["event"] for e in events] == ["claim", "recovered"]
    assert all(e["claimant"] == 0 and e["for"] == 1 for e in events)


def test_gather_wait_defers_to_live_claimant(monkeypatch):
    """A CAS loser never re-executes the block: with a LIVE claimant
    (fresh claim or fresh beacon) it keeps polling for the claimant's
    publication of the dead pid's keys."""
    import time

    kv = _fleet(monkeypatch, nproc=3, pid=0)
    monkeypatch.setenv("KSIM_DCN_TIMEOUT_S", "30")
    monkeypatch.setenv("KSIM_DCN_STALL_S", "0.05")
    monkeypatch.setenv("KSIM_DCN_POLL_S", "0.01")
    monkeypatch.setenv("KSIM_DCN_RECOVER", "1")
    kv.store[f"{dcn.HB_PREFIX}/1"] = json.dumps(
        {"pid": 1, "chunk": 0, "t": time.time() - 10.0}
    )
    # pid 2 already claimed gen 0 (fresh claim → benefit of the doubt
    # even before its first recovery beacon).
    kv.store[f"{dcn.CLAIM_PREFIX}/{dcn._seq}/whatif/1/0"] = json.dumps(
        {"claimant": 2, "for": 1, "gen": 0, "t": time.time()}
    )
    calls = {"n": 0}
    real_get = kv.blocking_key_value_get

    def _late_get(key, timeout_ms):
        calls["n"] += 1
        if calls["n"] >= 3:
            kv.store.setdefault("ksim/gather/1/whatif/1/n", "1")
        return real_get(key, timeout_ms)

    kv.blocking_key_value_get = _late_get

    def _never(p, gen):  # pragma: no cover - must not fire
        raise AssertionError("CAS loser re-executed the block")

    got = dcn._get_attributed(
        kv, "ksim/gather/1/whatif/1/n", 1, "whatif", recover=_never
    )
    assert got == "1"


def test_gather_wait_opens_next_generation_on_stale_claimant(monkeypatch):
    """Second failure during recovery: the gen-0 claimant's claim is old
    AND its beacon is stale → survivors open generation 1 and recover."""
    import time

    kv = _fleet(monkeypatch, nproc=3, pid=0)
    monkeypatch.setenv("KSIM_DCN_TIMEOUT_S", "30")
    monkeypatch.setenv("KSIM_DCN_STALL_S", "0.05")
    monkeypatch.setenv("KSIM_DCN_POLL_S", "0.01")
    monkeypatch.setenv("KSIM_DCN_RECOVER", "1")
    now = time.time()
    kv.store[f"{dcn.HB_PREFIX}/1"] = json.dumps(
        {"pid": 1, "chunk": 0, "t": now - 10.0}
    )
    kv.store[f"{dcn.CLAIM_PREFIX}/{dcn._seq}/whatif/1/0"] = json.dumps(
        {"claimant": 2, "for": 1, "gen": 0, "t": now - 10.0}
    )
    kv.store[f"{dcn.HB_PREFIX}/2"] = json.dumps(
        {"pid": 2, "chunk": -1, "state": "recover", "t": now - 10.0}
    )
    calls = []

    def _recover(p, gen):
        calls.append((p, gen))
        return {"placed": np.array([7], np.int32)}

    got = dcn._get_attributed(
        kv, "ksim/gather/1/whatif/1/n", 1, "whatif", recover=_recover
    )
    assert got == "1" and calls == [(1, 1)]
    assert dcn.read_claim(1, 1)["claimant"] == 0


def test_gather_wait_exhausted_claims_raise_attributed(monkeypatch):
    """All claim generations stale → the attributed DcnGatherTimeout of
    round 12 fires after all (recovery never hides a lost fleet)."""
    import time

    kv = _fleet(monkeypatch, nproc=3, pid=0)
    monkeypatch.setenv("KSIM_DCN_TIMEOUT_S", "30")
    monkeypatch.setenv("KSIM_DCN_STALL_S", "0.05")
    monkeypatch.setenv("KSIM_DCN_POLL_S", "0.01")
    monkeypatch.setenv("KSIM_DCN_RECOVER", "1")
    monkeypatch.setenv("KSIM_DCN_MAX_CLAIMS", "2")
    now = time.time()
    kv.store[f"{dcn.HB_PREFIX}/1"] = json.dumps(
        {"pid": 1, "chunk": 0, "t": now - 10.0}
    )
    kv.store[f"{dcn.HB_PREFIX}/2"] = json.dumps(
        {"pid": 2, "chunk": -1, "t": now - 10.0}
    )
    for gen in range(2):
        kv.store[f"{dcn.CLAIM_PREFIX}/{dcn._seq}/whatif/1/{gen}"] = (
            json.dumps({"claimant": 2, "for": 1, "gen": gen,
                        "t": now - 10.0})
        )
    with pytest.raises(dcn.DcnGatherTimeout, match="looks DEAD"):
        dcn._get_attributed(
            kv, "ksim/gather/1/whatif/1/n", 1, "whatif",
            recover=lambda p, gen: {},
        )


def test_gather_wait_stale_beacon_still_fails_without_recover_knob(
    monkeypatch,
):
    """Recovery requires BOTH the env knob and a callback: with a
    callback but KSIM_DCN_RECOVER unset, round-12 fail-fast holds."""
    import time

    kv = _fleet(monkeypatch, nproc=2, pid=0)
    monkeypatch.delenv("KSIM_DCN_RECOVER", raising=False)
    monkeypatch.setenv("KSIM_DCN_TIMEOUT_S", "30")
    monkeypatch.setenv("KSIM_DCN_STALL_S", "0.05")
    monkeypatch.setenv("KSIM_DCN_POLL_S", "0.01")
    kv.store[f"{dcn.HB_PREFIX}/1"] = json.dumps(
        {"pid": 1, "chunk": 2, "t": time.time() - 10.0}
    )
    with pytest.raises(dcn.DcnGatherTimeout, match="looks DEAD"):
        dcn._get_attributed(
            kv, "ksim/gather/1/whatif/1/n", 1, "whatif",
            recover=lambda p, gen: {},
        )


def test_snapshot_restore_carriers_roundtrip():
    """sim.jax_runtime snapshot/restore: positional leaf lists survive
    the host round-trip bit-exactly; shape/count mismatches refuse
    (callers then re-execute from chunk 0)."""
    from kubernetes_simulator_tpu.sim.jax_runtime import (
        restore_carriers,
        snapshot_carriers,
    )

    tree = {
        "states": (jax.numpy.arange(6).reshape(2, 3),
                   jax.numpy.ones((4,), jax.numpy.float32)),
        "retry": [jax.numpy.zeros((2, 2), jax.numpy.int32)],
    }
    leaves = snapshot_carriers(tree)
    assert all(isinstance(l, np.ndarray) for l in leaves)
    fresh = jax.tree_util.tree_map(lambda x: x * 0, tree)
    back = restore_carriers(fresh, leaves)
    np.testing.assert_array_equal(back["states"][0], tree["states"][0])
    np.testing.assert_array_equal(back["retry"][0], tree["retry"][0])
    with pytest.raises(ValueError, match="leaves"):
        restore_carriers(fresh, leaves[:-1])
    bad = list(leaves)
    bad[0] = np.zeros((9, 9))
    with pytest.raises(ValueError, match="shape"):
        restore_carriers(fresh, bad)


def test_schema_accepts_process_stamp():
    import sys

    sys.path.insert(
        0,
        os.path.abspath(
            os.path.join(os.path.dirname(__file__), "..", "scripts")
        ),
    )
    from check_metrics_schema import validate_row

    v2 = {
        "ts": 0.0, "schema": 2, "seed": 0, "engine": "v3",
        "config_hash": "h", "kind": "whatif-scenario",
        "scenario": 0, "placed": 3, "unschedulable": 0,
        "process_id": 1, "process_count": 2,
    }
    assert validate_row(v2) == []
    assert validate_row({**v2, "process_id": "1"})
    v3 = {
        "schema": 3, "run_type": "tune", "kind": "tune-round",
        "round": 0, "best_objective": 1.0, "round_best_objective": 1.0,
        "mean_objective": 1.0, "best_candidate": 0,
        "process_id": 0, "process_count": 2,
    }
    assert validate_row(v3) == []
    assert validate_row({**v3, "process_count": 2.5})
