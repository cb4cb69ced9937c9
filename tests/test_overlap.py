"""Round 19 — overlap plane parity: the two stall-hiding features
(threaded pager, background checkpoint publication) are pure LATENCY
knobs. Placements and deterministic JSONL are BIT-IDENTICAL with the
pager thread on vs off.

Also here: the round-19 pager resume-jump invalidation fix (a stale staged
page is discarded and counted, never silently under-reported as a plain
miss), the background publisher's single-flight/newest-wins/drain/error
unit semantics, and the ``overlap:`` config section's parsing and
validation refusals.
"""

import os

import numpy as np
import pytest

from kubernetes_simulator_tpu.framework.framework import FrameworkConfig
from kubernetes_simulator_tpu.models.encode import encode
from kubernetes_simulator_tpu.sim.jax_runtime import (
    JaxReplayEngine,
    _PodPager,
)
from kubernetes_simulator_tpu.sim.synthetic import make_cluster, make_workload

# The two env gates, both default-ON.
GATE_PAGER = "KSIM_PAGER_THREAD"
GATE_CKPT = "KSIM_DCN_CKPT_ASYNC"


def _case(n_nodes=24, n_pods=160, seed=11):
    cluster = make_cluster(n_nodes, seed=seed, taint_fraction=0.2)
    pods, _ = make_workload(
        n_pods, seed=seed, with_affinity=True, with_spread=True,
        with_tolerations=True, gang_fraction=0.1, gang_size=4,
        duration_mean=40.0,
    )
    return encode(cluster, pods)


@pytest.fixture(scope="module")
def case():
    return _case()


def _stable_summary(res):
    row = dict(res.summary())
    for k in ("wall_clock_s", "placements_per_sec"):
        row.pop(k, None)
    return row


def _deterministic_jsonl(res, path, monkeypatch):
    from kubernetes_simulator_tpu.utils.metrics import JsonlWriter, replay_row

    monkeypatch.setenv("KSIM_DETERMINISTIC_JSONL", "1")
    with JsonlWriter(str(path)) as w:
        w.write(replay_row("replay-jax", res))
    return path.read_bytes()


# ── threaded pager parity ────────────────────────────────────────────


@pytest.fixture(scope="module")
def pager_results(case):
    """{threaded: (engine, ReplayResult, flight_bytes)} for
    paged replays with the flight recorder on under the deterministic
    scrub — the stream itself must be byte-identical threaded on/off."""
    import os
    import tempfile

    ec, ep = case
    out = {}
    os.environ["KSIM_DETERMINISTIC_JSONL"] = "1"
    try:
        for threaded in (True, False):
            os.environ[GATE_PAGER] = "1" if threaded else "0"
            fl = os.path.join(
                tempfile.mkdtemp(prefix="ksim_ov_"), "fl.jsonl"
            )
            eng = JaxReplayEngine(
                ec, ep, FrameworkConfig(), chunk_waves=4,
                paged=True, telemetry="off", flight_recorder=fl,
            )
            res = eng.replay()
            with open(fl, "rb") as f:
                out[threaded] = (eng, res, f.read())
    finally:
        os.environ.pop(GATE_PAGER, None)
        os.environ.pop("KSIM_DETERMINISTIC_JSONL", None)
    return out


def test_threaded_pager_bit_parity(pager_results):
    _, ref, _ = pager_results[False]
    _, res, _ = pager_results[True]
    np.testing.assert_array_equal(
        res.assignments, ref.assignments,
        err_msg="pager_thread on vs off: assignments diverged",
    )
    assert _stable_summary(res) == _stable_summary(ref)


def test_threaded_pager_flight_stream_byte_identical(pager_results):
    """Under KSIM_DETERMINISTIC_JSONL the recorded stream is
    byte-identical threaded on/off: miss counts are structural,
    wait/wall fields are scrubbed, and the row schema never leaks which
    thread fetched the page."""
    assert pager_results[True][2] == pager_results[False][2], (
        "flight stream differs threaded on/off"
    )


def test_threaded_pager_jsonl_byte_identical(
    pager_results, tmp_path, monkeypatch
):
    blobs = {
        threaded: _deterministic_jsonl(
            res, tmp_path / f"p{threaded}.jsonl", monkeypatch
        )
        for threaded, (_, res, _) in pager_results.items()
    }
    assert len(set(blobs.values())) == 1


# ── pager resume-jump invalidation (round-19 fix) ────────────────────


@pytest.mark.parametrize("threaded", [False, True])
def test_pager_resume_jump_invalidation(threaded):
    """Crafted resume jump: a staged prefetch for chunk 1 followed by
    ``get(5)`` (what a checkpoint-resume jump does) must DISCARD the
    stale page — counted as an invalidation — and re-issue a
    synchronous fetch counted as a stall. Previously the stale hit was
    silently served a plain miss with no invalidation signal, so flight
    streams under-reported resume-jump misses. The deterministic
    counters (stalls, invalidations, prefetches, served pages) are
    identical threaded on or off."""
    fetched = []

    def fetch(ci):
        fetched.append(ci)
        return ("page", ci)

    pager = _PodPager(fetch, threaded=threaded)
    try:
        assert (pager.stalls, pager.invalidations, pager.depth) == (0, 0, 0)
        # Cold start: synchronous miss.
        assert pager.get(0) == ("page", 0)
        assert (pager.stalls, pager.invalidations) == (1, 0)
        # Healthy prefetch hit: no new stall.
        pager.prefetch(1)
        assert pager.get(1) == ("page", 1)
        assert (pager.stalls, pager.invalidations) == (1, 0)
        # Resume jump: staged 2, asked for 5.
        pager.prefetch(2)
        assert pager.get(5) == ("page", 5)
        assert pager.invalidations == 1, "stale staged page not counted"
        assert pager.stalls == 2, "re-issued fetch must count as a stall"
        assert pager.depth == 0
        # The pager must have actually fetched chunk 5 (not served 2).
        assert fetched[-1] == 5
        # And recovers to normal operation afterwards.
        pager.prefetch(6)
        assert pager.get(6) == ("page", 6)
        assert (pager.stalls, pager.invalidations, pager.prefetches) == (
            2, 1, 3,
        )
    finally:
        pager.close()


# ── background publisher unit semantics ──────────────────────────────


def test_publisher_single_flight_newest_wins(monkeypatch):
    """Submits while a publication is in flight coalesce to the newest
    snapshot; drain() blocks until the KV plane holds the last-submitted
    cursor."""
    import threading

    from kubernetes_simulator_tpu.parallel import dcn

    published = []
    gate = threading.Event()

    def fake_publish(cursor, payload, block, epoch=None):
        gate.wait(timeout=10.0)
        published.append((cursor, payload, block, epoch))
        return True

    monkeypatch.setattr(dcn, "publish_checkpoint", fake_publish)
    start = dcn.bg_publish_stats()
    pub = dcn._CheckpointPublisher()
    pub.submit(1, "p1", (0, 4), 0)
    # Worker is blocked on the gate holding job 1 (or job 1 is still
    # pending) — these three coalesce down to the newest.
    pub.submit(2, "p2", (0, 4), 0)
    pub.submit(3, "p3", (0, 4), 0)
    pub.submit(4, "p4", (0, 4), 0)
    gate.set()
    pub.drain()
    cursors = [p[0] for p in published]
    assert cursors[-1] == 4, cursors
    # Single-flight: at most 2 publications ran (the in-flight one plus
    # the coalesced survivor), never all 4.
    assert len(published) <= 2, cursors
    stats = dcn.bg_publish_stats()
    assert stats["submitted"] - start["submitted"] == 4
    assert stats["coalesced"] - start["coalesced"] >= 2
    assert stats["drains"] - start["drains"] == 1


def test_publisher_error_reraised_attributed(monkeypatch):
    """An unexpected worker error is stored and re-raised at the next
    loop touch, attributed to the failing cursor."""
    from kubernetes_simulator_tpu.parallel import dcn

    def boom(cursor, payload, block, epoch=None):
        raise OSError("kv wire melted")

    monkeypatch.setattr(dcn, "publish_checkpoint", boom)
    pub = dcn._CheckpointPublisher()
    pub.submit(7, "p", (0, 4), 0)
    with pytest.raises(RuntimeError, match="cursor 7") as ei:
        pub.drain()
    assert isinstance(ei.value.__cause__, OSError)
    # The error is consumed: the publisher is usable again.
    monkeypatch.setattr(
        dcn, "publish_checkpoint",
        lambda *a, **k: True,
    )
    pub.submit(8, "p", (0, 4), 0)
    pub.drain()


def test_publish_checkpoint_async_single_process_noop(monkeypatch):
    """Outside a DCN fleet the async entry point no-ops like every
    coordination call — nothing is queued, nothing is spawned."""
    from kubernetes_simulator_tpu.parallel import dcn

    start = dcn.bg_publish_stats()
    assert dcn.publish_checkpoint_async(3, "p", (0, 4)) is False
    assert dcn.bg_publish_stats()["submitted"] == start["submitted"]
    dcn.drain_publisher()  # must not hang or raise


def test_ckpt_async_gate_falls_back_sync(monkeypatch):
    """Gate off → the async entry point routes to the synchronous
    publisher (same return contract), never the thread."""
    from kubernetes_simulator_tpu.parallel import dcn

    calls = []
    monkeypatch.setenv(GATE_CKPT, "0")
    monkeypatch.setattr(
        dcn, "publish_checkpoint",
        lambda *a, **k: calls.append(a) or True,
    )
    monkeypatch.setattr(dcn, "process_info", lambda: (3, 1))
    start = dcn.bg_publish_stats()
    assert dcn.publish_checkpoint_async(5, "p", (0, 4), epoch=0) is True
    assert len(calls) == 1 and calls[0][0] == 5
    assert dcn.bg_publish_stats()["submitted"] == start["submitted"]


# ── overlap config section ───────────────────────────────────────────


def test_overlap_spec_parsing():
    from kubernetes_simulator_tpu.utils.config import SimConfig

    cfg = SimConfig.from_dict({
        "strategy": "jax",
        "overlap": {"pagerThread": True},
    })
    assert cfg.overlap.pager_thread is True
    assert cfg.overlap.background_publisher is None
    assert SimConfig.from_dict({}).overlap is None
    with pytest.raises(ValueError, match="overlap.pagerThread"):
        SimConfig.from_dict({"overlap": {"pagerThread": "yes"}})


def test_overlap_validation_refusals():
    """A gate explicitly enabled on a config lacking the machinery it
    overlaps is refused with an actionable message."""
    from kubernetes_simulator_tpu.cli import _overlap_errors
    from kubernetes_simulator_tpu.utils.config import SimConfig

    # pagerThread without pagedWaves.
    cfg = SimConfig.from_dict({
        "strategy": "jax", "overlap": {"pagerThread": True},
    })
    errs = _overlap_errors(cfg)
    assert any("pagedWaves" in e for e in errs), errs
    cfg = SimConfig.from_dict({
        "strategy": "jax", "pagedWaves": True,
        "overlap": {"pagerThread": True},
    })
    assert _overlap_errors(cfg) == []

    # backgroundPublisher without a checkpoint cadence.
    cfg = SimConfig.from_dict({
        "strategy": "jax", "overlap": {"backgroundPublisher": True},
    })
    errs = _overlap_errors(cfg)
    assert any("checkpoint" in e for e in errs), errs
    cfg = SimConfig.from_dict({
        "strategy": "jax",
        "dcn": {"recovery": {"enable": True, "checkpointEvery": 1}},
        "overlap": {"backgroundPublisher": True},
    })
    assert _overlap_errors(cfg) == []

    # Explicit opt-OUTs are always fine — they remove machinery, never
    # assume it.
    cfg = SimConfig.from_dict({
        "strategy": "jax",
        "overlap": {"pagerThread": False, "backgroundPublisher": False},
    })
    assert _overlap_errors(cfg) == []


def test_validate_accepts_example_config18():
    """The shipped round-19 example parses, carries both gates
    (backgroundPublisher deliberately false — it is the fleet-only
    leg), and passes full validation with zero errors."""
    import os

    from kubernetes_simulator_tpu.cli import validate_config
    from kubernetes_simulator_tpu.utils.config import SimConfig

    path = os.path.join(
        os.path.dirname(__file__), "..", "examples", "config18_overlap.yaml"
    )
    cfg = SimConfig.load(path)
    assert cfg.paged_waves
    assert cfg.overlap is not None
    assert cfg.overlap.pager_thread is True
    assert cfg.overlap.background_publisher is False
    assert cfg.flight_recorder is not None
    assert validate_config(cfg) == []


_EXAMPLES = os.path.join(os.path.dirname(__file__), "..", "examples")


@pytest.mark.parametrize(
    "name", sorted(f for f in os.listdir(_EXAMPLES) if f.endswith(".yaml"))
)
def test_every_example_validates(name, tmp_path, monkeypatch):
    """Every shipped example parses and passes full validation. The
    fleet examples (``dcn:`` recovery / workQueue / durable) are
    validated as ``scripts/dcn_launch.py`` would start them."""
    from kubernetes_simulator_tpu.cli import validate_config
    from kubernetes_simulator_tpu.utils.config import SimConfig

    cfg = SimConfig.load(os.path.join(_EXAMPLES, name))
    if cfg.dcn_recovery or cfg.dcn_workqueue or cfg.dcn_durable:
        monkeypatch.setenv("KSIM_DCN_NPROC", "2")
    if cfg.dcn_durable is not None and cfg.dcn_durable.dir:
        cfg.dcn_durable.dir = str(tmp_path / "journal")
    if cfg.flight_recorder is not None:
        cfg.flight_recorder.path = str(tmp_path / "flight.jsonl")
    assert validate_config(cfg) == []
