"""Borg-like trace generator, checkpoint/resume, config/CLI, metrics
(SURVEY.md §4.5, §5)."""

import json
import os

import numpy as np
import pytest

from kubernetes_simulator_tpu.framework.framework import FrameworkConfig
from kubernetes_simulator_tpu.models.encode import PAD, encode
from kubernetes_simulator_tpu.sim.borg import BorgSpec, make_borg_encoded, make_borg_trace
from kubernetes_simulator_tpu.sim.jax_runtime import JaxReplayEngine
from kubernetes_simulator_tpu.utils.config import SimConfig, build_case


class TestBorg:
    def test_encoded_fast_path_structure(self):
        spec = BorgSpec(nodes=100, tasks=5000, seed=1)
        ec, ep, meta = make_borg_encoded(spec)
        assert ep.num_pods == 5000
        assert ec.num_nodes == 100
        assert meta["num_gangs"] > 0
        # Gang members are contiguous (wave packing requirement).
        gid = ep.group_id
        for g in np.unique(gid[gid >= 0]):
            idxs = np.nonzero(gid == g)[0]
            assert (np.diff(idxs) == 1).all()
            assert ep.pg_min_member[g] == idxs.size
        # Priorities are tiered.
        assert set(np.unique(ep.priority)) <= {0, 100, 200, 360, 450}
        # Arrivals sorted.
        assert (np.diff(ep.arrival) >= 0).all()

    def test_encoded_trace_replays_on_jax(self):
        spec = BorgSpec(nodes=60, tasks=2000, seed=2, max_gang=6)
        ec, ep, meta = make_borg_encoded(spec)
        res = JaxReplayEngine(ec, ep, FrameworkConfig(), wave_width=8).replay()
        assert res.placed > 1500
        assert res.placed + res.unschedulable == 2000

    def test_object_model_variant_matches_shape(self):
        class S:
            nodes, tasks, seed, gang_fraction, max_gang = 30, 300, 3, 0.1, 4

        cluster, pods = make_borg_trace(S)
        assert len(pods) == 300
        gangs = {p.pod_group for p in pods if p.pod_group}
        assert gangs
        ec, ep = encode(cluster, pods)
        res = JaxReplayEngine(ec, ep, FrameworkConfig()).replay()
        assert res.placed > 200


class TestCheckpoint:
    def test_resume_identical(self, tmp_path):
        from kubernetes_simulator_tpu.sim.synthetic import config1

        cluster, pods, plugins = config1(num_nodes=20, num_pods=300)
        ec, ep = encode(cluster, pods)
        cfg = FrameworkConfig(plugins=plugins)
        full = JaxReplayEngine(ec, ep, cfg, chunk_waves=8).replay()

        ck = str(tmp_path / "ck.npz")
        eng = JaxReplayEngine(ec, ep, cfg, chunk_waves=8)
        eng.replay(checkpoint_path=ck, checkpoint_every=2)
        assert os.path.exists(ck)
        # Resume from the mid-run snapshot and finish.
        resumed = JaxReplayEngine(ec, ep, cfg, chunk_waves=8).replay(
            checkpoint_path=ck, resume=True
        )
        assert (resumed.assignments == full.assignments).all()
        assert resumed.placed == full.placed


class TestConfigCli:
    CFG = """
strategy: cpu
cluster:
  synthetic: {nodes: 20, seed: 0}
workload:
  synthetic: {pods: 50, seed: 0, affinity: true}
profile:
  plugins:
    - name: NodeResourcesFit
      args: {strategy: LeastAllocated}
    - name: TaintToleration
  weights: {NodeResourcesFit: 1, TaintToleration: 3}
whatIf:
  scenarios: 4
  seed: 1
"""

    def test_config_roundtrip(self, tmp_path):
        p = tmp_path / "cfg.yaml"
        p.write_text(self.CFG)
        cfg = SimConfig.load(str(p))
        assert cfg.strategy == "cpu"
        assert cfg.cluster.nodes == 20
        assert cfg.workload.pods == 50
        assert cfg.framework.plugins[0]["name"] == "NodeResourcesFit"
        assert cfg.whatif.scenarios == 4
        cluster, pods = build_case(cfg)
        assert len(cluster.nodes) == 20 and len(pods) == 50

    def test_cli_run_and_whatif(self, tmp_path, capsys):
        from kubernetes_simulator_tpu.cli import main

        out = tmp_path / "res.jsonl"
        p = tmp_path / "cfg.yaml"
        p.write_text(self.CFG + f"output: {out}\n")
        assert main(["run", str(p)]) == 0
        assert main(["run", str(p), "--strategy", "jax"]) == 0
        assert main(["what-if", str(p)]) == 0
        rows = [json.loads(l) for l in out.read_text().splitlines()]
        kinds = {r["kind"] for r in rows}
        assert "replay-cpu" in kinds and "replay-jax" in kinds
        assert "whatif-aggregate" in kinds and "whatif-scenario" in kinds
        agg = [r for r in rows if r["kind"] == "whatif-aggregate"][0]
        assert agg["total_placed"] > 0

    def test_profile_mode_collects_plugin_latency(self):
        from kubernetes_simulator_tpu.sim.runtime import CpuReplayEngine
        from kubernetes_simulator_tpu.sim.synthetic import make_cluster, make_workload

        cluster = make_cluster(10, seed=0)
        pods, _ = make_workload(30, seed=0, with_affinity=True)
        ec, ep = encode(cluster, pods)
        eng = CpuReplayEngine(ec, ep, FrameworkConfig(profile=True))
        eng.replay()
        assert any(k.startswith("Filter/") for k in eng.fw.plugin_time)
        assert any(k.startswith("Score/") for k in eng.fw.plugin_time)


class TestEncodedCli:
    def test_borg_config_uses_encoded_fast_path(self):
        # 250k tasks exceeds the object-model cap — the CLI must take the
        # template-expansion fast path (regression: config4_borg_1m.yaml
        # raised through build_case).
        from kubernetes_simulator_tpu.utils.config import SimConfig, build_encoded_case

        cfg = SimConfig.from_dict({
            "strategy": "jax",
            "workload": {"borg": {"nodes": 300, "tasks": 250_000, "seed": 1}},
        })
        ec, ep = build_encoded_case(cfg)
        assert ep.num_pods == 250_000 and ec.num_nodes == 300

    def test_borg_trace_path_config(self, tmp_path):
        from kubernetes_simulator_tpu.sim.borg import BorgSpec, export_trace_csv
        from kubernetes_simulator_tpu.utils.config import SimConfig, build_encoded_case

        path = tmp_path / "t.csv"
        export_trace_csv(BorgSpec(nodes=40, tasks=500, seed=2), path)
        cfg = SimConfig.from_dict({
            "workload": {"borg": {"nodes": 40, "tasks": 500, "seed": 2,
                                  "tracePath": str(path)}},
        })
        ec, ep = build_encoded_case(cfg)
        assert ep.num_pods == 500

    def test_cli_run_small_borg(self, tmp_path, capsys):
        import yaml

        from kubernetes_simulator_tpu.cli import main

        cfgp = tmp_path / "b.yaml"
        cfgp.write_text(yaml.safe_dump({
            "strategy": "jax",
            "workload": {"borg": {"nodes": 50, "tasks": 2000, "seed": 0}},
        }))
        assert main(["run", str(cfgp)]) == 0
        out = capsys.readouterr().out
        assert '"kind": "replay-jax"' in out


class TestValidate:
    def _write(self, tmp_path, doc):
        import yaml

        p = tmp_path / "cfg.yaml"
        p.write_text(yaml.safe_dump(doc))
        return str(p)

    def test_rejects_unknown_plugin_and_bad_gang(self, tmp_path, capsys):
        from kubernetes_simulator_tpu.cli import main

        cfg = self._write(
            tmp_path,
            {
                "strategy": "jax",
                "waveWidth": 4,
                "workload": {"borg": {"nodes": 10, "tasks": 100, "maxGang": 8}},
                "profile": {"plugins": [{"name": "NoSuchPlugin"}]},
            },
        )
        rc = main(["validate", cfg])
        out = capsys.readouterr().out
        assert rc == 1
        assert "unknown plugin 'NoSuchPlugin'" in out
        # a gang wider than the wave runs on the device engines' arrivals-only
        # paths; a Borg trace has durations, so this one is still refused
        assert "workload.borg.maxGang: a gang of" in out
        assert "exceeds the wave width (4)" in out
        assert "not supported with completions" in out

    @pytest.mark.parametrize("extra, refused", [
        ({}, None),
        ({"durationMean": 5.0}, "completions"),
        ({"spread": True}, "carried affinity / spread count planes"),
    ])
    def test_a_gang_wider_than_the_wave(self, tmp_path, capsys, extra, refused):
        """``gangSizes`` with jobs of 16 at waveWidth 8: accepted on the
        device path (the carried transaction), refused with what the open
        transaction cannot be combined with; the CPU engine takes any."""
        from kubernetes_simulator_tpu.cli import main

        wl = {"pods": 64, "gangSizes": {1: 0.5, 16: 0.5}, **extra}
        for strategy in ("jax", "cpu"):
            cfg = self._write(tmp_path, {
                "strategy": strategy, "waveWidth": 8, "workload": {"synthetic": wl}})
            rc = main(["validate", cfg])
            out = capsys.readouterr().out
            if refused and strategy == "jax":
                assert rc == 1 and "a gang of 16 exceeds the wave width (8)" in out
                assert refused in out
            else:
                assert rc == 0, out

    def test_rejects_a_job_size_mix_that_is_none(self, tmp_path, capsys):
        from kubernetes_simulator_tpu.cli import main

        cfg = self._write(tmp_path, {"workload": {"synthetic": {
            "gangSizes": {"many": 1.0},
            "jobExtendedResource": {"resource": "nvidia.com/gpu"}}}})
        assert main(["validate", cfg]) == 1
        out = capsys.readouterr().out
        assert "workload.gangSizes" in out
        assert "workload.jobExtendedResource: missing" in out

    def test_accepts_the_gpu_jobs_example(self, capsys):
        from kubernetes_simulator_tpu.cli import main

        assert main(["validate", "examples/config8_gpu_jobs_gangs.yaml"]) == 0
        assert '"errors": []' in capsys.readouterr().out

    def test_rejects_missing_trace_file(self, tmp_path, capsys):
        from kubernetes_simulator_tpu.cli import main

        cfg = self._write(
            tmp_path,
            {
                "workload": {
                    "borg": {
                        "nodes": 10,
                        "tasks": 10,
                        "instanceEvents": "/no/such/file.csv",
                    }
                }
            },
        )
        rc = main(["validate", cfg])
        assert rc == 1
        assert "file not found" in capsys.readouterr().out

    def test_accepts_valid_config(self, capsys):
        from kubernetes_simulator_tpu.cli import main

        rc = main(["validate", "examples/config3_whatif_256.yaml"])
        out = capsys.readouterr().out
        assert rc == 0
        assert '"errors": []' in out

    def test_rejects_retry_buffer_with_completions_off(self, tmp_path, capsys):
        """ADVICE r4: retryBuffer + completions:false must fail at
        validate with a message naming completions, not later at engine
        construction with a release-path message that never mentions it."""
        from kubernetes_simulator_tpu.cli import main

        cfg = self._write(
            tmp_path,
            {
                "strategy": "jax",
                "whatIf": {
                    "scenarios": 4,
                    "retryBuffer": 64,
                    "completions": False,
                },
            },
        )
        rc = main(["validate", cfg])
        out = capsys.readouterr().out
        assert rc == 1
        assert "retryBuffer" in out and "completions" in out

    def test_non_bool_completions_raises_at_parse(self):
        """ADVICE r4: a string whatIf.completions (e.g. 'yes') must raise
        in SimConfig.from_dict, not silently behave as default-on."""
        import pytest

        from kubernetes_simulator_tpu.utils.config import SimConfig

        with pytest.raises(ValueError, match="whatIf.completions"):
            SimConfig.from_dict({"whatIf": {"completions": "yes"}})
        # int 0/1 and real bools still coerce.
        assert SimConfig.from_dict(
            {"whatIf": {"completions": 1}}
        ).whatif.completions is True
        assert SimConfig.from_dict(
            {"whatIf": {"completions": False}}
        ).whatif.completions is False

    def test_recovery_requires_dcn_fleet_and_heartbeats(
        self, tmp_path, capsys, monkeypatch
    ):
        """Round 15: dcn.recovery.enable outside a DCN fleet (no
        KSIM_DCN_NPROC) or with heartbeats disabled must refuse with a
        message naming the fix; inside a fleet with beacons on, the same
        config validates clean."""
        from kubernetes_simulator_tpu.cli import main

        monkeypatch.delenv("KSIM_DCN_NPROC", raising=False)
        monkeypatch.delenv("KSIM_DCN_HEARTBEAT_EVERY", raising=False)
        cfg = self._write(
            tmp_path,
            {
                "strategy": "jax",
                "whatIf": {"scenarios": 4},
                "dcn": {"recovery": {"enable": True, "checkpointEvery": 2}},
            },
        )
        rc = main(["validate", cfg])
        out = capsys.readouterr().out
        assert rc == 1
        assert "dcn_launch" in out and "KSIM_DCN_NPROC" in out

        monkeypatch.setenv("KSIM_DCN_NPROC", "2")
        monkeypatch.setenv("KSIM_DCN_HEARTBEAT_EVERY", "0")
        rc = main(["validate", cfg])
        out = capsys.readouterr().out
        assert rc == 1
        assert "KSIM_DCN_HEARTBEAT_EVERY" in out and "heartbeat" in out

        monkeypatch.delenv("KSIM_DCN_HEARTBEAT_EVERY", raising=False)
        rc = main(["validate", cfg])
        out = capsys.readouterr().out
        assert rc == 0
        assert '"errors": []' in out

    def test_recovery_value_checks_apply_even_disabled(
        self, tmp_path, capsys
    ):
        """checkpointEvery/maxClaims sanity is structural — it must not
        hide behind enable: true (a disabled-but-broken section would
        explode the day someone flips the switch)."""
        from kubernetes_simulator_tpu.cli import main

        cfg = self._write(
            tmp_path,
            {
                "dcn": {
                    "recovery": {
                        "enable": False,
                        "checkpointEvery": -1,
                        "maxClaims": 0,
                    }
                },
            },
        )
        rc = main(["validate", cfg])
        out = capsys.readouterr().out
        assert rc == 1
        assert "dcn.recovery.checkpointEvery" in out
        assert "dcn.recovery.maxClaims" in out
