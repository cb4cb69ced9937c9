"""Native C++ layer: wave-packer parity with the Python reference packer,
and columnar trace CSV round-trip (SURVEY.md §2 trace driver; the native
runtime components the framework keeps outside Python)."""

import numpy as np
import pytest

from kubernetes_simulator_tpu import native
from kubernetes_simulator_tpu.models.encode import PAD, encode
from kubernetes_simulator_tpu.sim.borg import (
    BorgSpec,
    export_trace_csv,
    load_trace_csv,
    make_borg_encoded,
)
from kubernetes_simulator_tpu.sim.synthetic import make_cluster, make_workload
from kubernetes_simulator_tpu.sim.waves import WaveBatch, pack_waves


def _python_pack(ep, wave_width, order=None):
    """The original pure-Python packer (reference semantics)."""
    if order is None:
        unbound = np.nonzero(ep.bound_node == PAD)[0]
        order = unbound[np.argsort(ep.arrival[unbound], kind="stable")]
    members = {}
    for p in order:
        g = int(ep.group_id[p])
        if g != PAD:
            members.setdefault(g, []).append(int(p))
    waves, current, consumed = [], [], set()
    for p in order:
        p = int(p)
        if p in consumed:
            continue
        g = int(ep.group_id[p])
        batch = [p] if g == PAD else members[g]
        if len(current) + len(batch) > wave_width:
            waves.append(current)
            current = []
        current.extend(batch)
        consumed.update(batch)
    if current:
        waves.append(current)
    idx = np.full((max(len(waves), 1), wave_width), PAD, dtype=np.int32)
    for i, w in enumerate(waves):
        idx[i, : len(w)] = w
    return idx


@pytest.mark.skipif(not native.available(), reason="native lib unavailable")
class TestNativeWavepack:
    def test_parity_random_gangs(self):
        for seed in range(4):
            cluster = make_cluster(20, seed=seed)
            pods, _ = make_workload(
                500, seed=seed, gang_fraction=0.2, gang_size=5, with_affinity=True
            )
            _, ep = encode(cluster, pods)
            got = pack_waves(ep, 8)
            want = _python_pack(ep, 8)
            np.testing.assert_array_equal(got.idx, want)

    def test_parity_no_gangs_odd_width(self):
        cluster = make_cluster(10, seed=1)
        pods, _ = make_workload(97, seed=1, gang_fraction=0.0)
        _, ep = encode(cluster, pods)
        got = pack_waves(ep, 3)
        np.testing.assert_array_equal(got.idx, _python_pack(ep, 3))

    def test_empty(self):
        cluster = make_cluster(4, seed=0)
        pods, _ = make_workload(5, seed=0)
        _, ep = encode(cluster, pods)
        got = native.pack_waves_native(np.empty(0, np.int32), ep.group_id, 4)
        assert got.shape == (1, 4)
        assert (got == PAD).all()

    def test_oversized_gang_fills_consecutive_waves(self):
        """Two gangs of 6 at width 4: each starts on a wave's first slot and
        takes two waves, 4 + 2 (tests/test_wide_gangs.py has the rest)."""
        cluster = make_cluster(4, seed=0)
        pods, _ = make_workload(12, seed=0, gang_fraction=1.0, gang_size=6)
        _, ep = encode(cluster, pods)
        got = pack_waves(ep, 4).idx
        np.testing.assert_array_equal(
            got, [[0, 1, 2, 3], [4, 5, PAD, PAD], [6, 7, 8, 9], [10, 11, PAD, PAD]])
        with pytest.raises(ValueError, match="must fit in one page"):
            pack_waves(ep, 4, page_pods=4)


class TestTraceRoundtrip:
    def test_csv_roundtrip_matches_direct_build(self, tmp_path):
        spec = BorgSpec(nodes=50, tasks=2000, seed=3)
        ec0, ep0, meta0 = make_borg_encoded(spec)
        path = tmp_path / "trace.csv"
        export_trace_csv(spec, path)
        ec1, ep1, meta1 = load_trace_csv(path, spec)
        assert meta1["num_gangs"] == meta0["num_gangs"]
        np.testing.assert_allclose(ep1.requests, ep0.requests, rtol=1e-5)
        np.testing.assert_array_equal(ep1.priority, ep0.priority)
        np.testing.assert_array_equal(ep1.group_id, ep0.group_id)
        np.testing.assert_allclose(ep1.arrival, ep0.arrival, atol=5e-5)
        np.testing.assert_array_equal(ep1.tol_key, ep0.tol_key)
        np.testing.assert_array_equal(ep1.spread_g, ep0.spread_g)
        np.testing.assert_array_equal(ec1.allocatable, ec0.allocatable)

    def test_sparse_gang_ids_remapped(self, tmp_path):
        # External traces carry sparse collection ids; pg_min_member is
        # indexed by gang id, so ids must be remapped to contiguous.
        path = tmp_path / "sparse.csv"
        lines = ["arrival_s,cpu,mem_bytes,priority,group_id,app_id,tolerates,duration_s"]
        gids = [7, 7, -1, 1000003, 1000003, 1000003, -1, 7]
        for i, g in enumerate(gids):
            lines.append(f"{i}.0,1.0,1000.0,100,{g},0,0,60.0")
        path.write_text("\n".join(lines) + "\n")
        spec = BorgSpec(nodes=10, tasks=len(gids), seed=0)
        _, ep, meta = load_trace_csv(path, spec)
        assert meta["num_gangs"] == 2
        np.testing.assert_array_equal(ep.group_id, [0, 0, PAD, 1, 1, 1, PAD, 0])
        np.testing.assert_array_equal(ep.pg_min_member, [3, 3])

    def test_headerless_csv_python_fallback(self, tmp_path, monkeypatch):
        path = tmp_path / "nohdr.csv"
        path.write_text("0.5,1.0,1000.0,100,-1,0,0,60.0\n1.5,2.0,2000.0,0,-1,1,1,30.0\n")
        monkeypatch.setenv("KSIM_NO_NATIVE", "1")
        monkeypatch.setattr(native, "_LIB", None)
        monkeypatch.setattr(native, "_TRIED", False)
        spec = BorgSpec(nodes=5, tasks=2, seed=0)
        _, ep, _ = load_trace_csv(path, spec)
        assert ep.num_pods == 2
        np.testing.assert_allclose(ep.arrival, [0.5, 1.5])

    @pytest.mark.parametrize("use_native", [False, True])
    def test_borg_scale_collection_ids(self, tmp_path, monkeypatch, use_native):
        # Real Borg 2019 collection ids exceed 2^31; both readers must
        # carry them un-truncated into the contiguous remap.
        if use_native and not native.available():
            pytest.skip("native lib unavailable")
        if not use_native:
            monkeypatch.setenv("KSIM_NO_NATIVE", "1")
            monkeypatch.setattr(native, "_LIB", None)
            monkeypatch.setattr(native, "_TRIED", False)
        path = tmp_path / "big.csv"
        g1, g2 = 380618516317, 380618516317 + (1 << 32)  # would collide in int32
        lines = ["arrival_s,cpu,mem_bytes,priority,group_id,app_id,tolerates,duration_s"]
        for i, g in enumerate([g1, g1, g2, g2, -1]):
            lines.append(f"{i}.0,1.0,1000.0,100,{g},0,0,60.0")
        path.write_text("\n".join(lines) + "\n")
        spec = BorgSpec(nodes=10, tasks=5, seed=0)
        _, ep, meta = load_trace_csv(path, spec)
        assert meta["num_gangs"] == 2
        np.testing.assert_array_equal(ep.group_id, [0, 0, 1, 1, PAD])
        np.testing.assert_array_equal(ep.pg_min_member, [2, 2])

    def test_comment_then_header_python_fallback(self, tmp_path, monkeypatch):
        # A '#' comment before the header must not push the header row into
        # the data (the one-line sniff bug); same rule as the native reader.
        path = tmp_path / "ch.csv"
        path.write_text(
            "# generated\n"
            "arrival_s,cpu,mem_bytes,priority,group_id,app_id,tolerates,duration_s\n"
            " 0.5,1.0,1000.0,100,-1,0,0,60.0\n"
            "1.5,2.0,2000.0,0,-1,1,1,30.0\n"
        )
        monkeypatch.setenv("KSIM_NO_NATIVE", "1")
        monkeypatch.setattr(native, "_LIB", None)
        monkeypatch.setattr(native, "_TRIED", False)
        spec = BorgSpec(nodes=5, tasks=2, seed=0)
        _, ep, _ = load_trace_csv(path, spec)
        assert ep.num_pods == 2
        np.testing.assert_allclose(ep.arrival, [0.5, 1.5])
        assert np.isfinite(ep.arrival).all()

    @pytest.mark.skipif(not native.available(), reason="native lib unavailable")
    def test_native_reader_used(self, tmp_path):
        spec = BorgSpec(nodes=10, tasks=100, seed=0)
        path = tmp_path / "t.csv"
        cols = export_trace_csv(spec, path)
        got = native.read_trace_csv(path)
        assert got is not None
        np.testing.assert_allclose(got["arrival"], cols["arrival"], atol=5e-5)
        np.testing.assert_array_equal(got["group_id"], cols["group_id"])
