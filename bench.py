"""Benchmark entry point — prints ONE JSON line:
{"metric": ..., "value": N, "unit": ..., "vs_baseline": N}

Headline metric ([BASELINE]): pod-placements/sec. The reference publishes no
numbers (BASELINE.md), so ``vs_baseline`` is the speedup of the JAX what-if
path over this framework's own CPU default plugin path on the same
workload shape (per-placement rate ratio) — the honest available baseline.

Workload: batched what-if (config #3 shape) — S scenarios × full default
plugin set, measured on the real device. Since round 4 the headline
workload has finite pod durations (mean ``BENCH_DURATION_MEAN``), so the
number exercises the DEFAULT-ON chunk-granular completions machinery;
a durationless (arrivals-only) run ships in ``detail`` for cross-round
continuity with r01–r03. CPU rate is measured on a pod subsample of the
same workload (it is orders of magnitude slower).

Round 10: the headline is MESH-DEFAULT. When >1 accelerator is visible
the what-if engine runs shard_map over all of them and the scenario
count scales with the device count (BENCH_SCENARIOS per device — 128 ×
8 = 1024 on a v5e-8), with weak/strong-scaling reference runs in
``detail.scaling`` (see README § Performance for how to read them).
``n_devices`` / ``mesh_shape`` / ``scenarios`` are stamped at the TOP
level of the JSON line so BENCH_r0*.json rounds stay comparable across
configurations. On one device everything falls back to the r05
single-chip protocol unchanged. The durationless continuity run and the
tuner sweep intentionally STAY single-chip/per-device-shaped — they are
the cross-round continuity anchors.

Env knobs: BENCH_NODES, BENCH_PODS, BENCH_SCENARIOS (per device),
BENCH_CPU_PODS, BENCH_RUNS, BENCH_REF_RUNS (timed runs for the scaling
reference configurations), BENCH_DURATION_MEAN (seconds; 0 disables
durations), BENCH_TUNE_POP / BENCH_TUNE_SCEN (the ``tune_popsweep``
detail headline: candidate-policies/sec through the policy tuner's
batched sweep — the config2 search space, i.e. the full default plugin
set's 5 Score weights plus the NodeResourcesFit strategy selector; 0
population disables), BENCH_RECOVERY (0 skips the ``detail.dcn_recovery``
cost block), BENCH_RECOVERY_REPS, BENCH_CKPT_EVERY (cadence for the
fleet-only publication-overhead run), BENCH_DURABLE (0 skips the round-20
``detail.durable_ground`` durability-journal micro-bench: journal write
overhead vs the encode wall, cold-resume wall, adopted-block count),
BENCH_BORG / BENCH_BORG_NODES /
BENCH_BORG_PODS (borg_scale detail block), BENCH_HEADLINE /
BENCH_HEADLINE_NODES / BENCH_HEADLINE_PODS / BENCH_HEADLINE_FLIGHT
(round 16 ``borg_headline`` composed run — Borg-shaped trace through
nodeShards × pagedWaves with the flight recorder on).

Round 12: ``--profile`` (or ``KSIM_PROFILE_DIR=<dir>``) wraps the timed
headline runs in ``jax.profiler.trace`` with TraceAnnotation markers on
the PHASE_NAMES phases and chunk dispatch (utils.profiling) — load the
trace dir in TensorBoard/Perfetto; results are bit-identical with
profiling on or off. ``detail`` gains the engine-level wall-clock
``phases`` breakdown (from the fleet-merged telemetry, keys
``p<pid>/<phase>``) and a ``live_buffers`` watermark gauge
(``jax.live_arrays()`` count/bytes + backend peak bytes where reported).
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np


def _overlap_block(ph, flight_rows):
    """Round 19 overlap accounting for the headline run: split the pager
    fetch wall into what the chunk loop actually waited on
    (exposed_stall_s — THE number the threaded pager shrinks) and the
    wall the background worker absorbed (hidden_prefetch_s), and stamp
    which overlap features were live so bench_compare can refuse
    apples-to-oranges diffs."""
    from kubernetes_simulator_tpu.ops import tpu as _T
    from kubernetes_simulator_tpu.sim.jax_runtime import (
        _pager_thread_enabled,
    )

    def _cum(field, cast=float):
        return max(
            (
                cast(r.get(field, 0))
                for r in flight_rows
                if r.get("event") == "chunk"
            ),
            default=cast(0),
        )

    exposed = _cum("pager_stall_s")
    prefetch = _cum("pager_prefetch_s")
    return {
        "exposed_stall_s": round(exposed, 4),
        "prefetch_wall_s": round(prefetch, 4),
        "hidden_prefetch_s": round(max(prefetch - exposed, 0.0), 4),
        "pager_waits": _cum("pager_waits", int),
        "pager_invalidations": _cum("pager_invalidations", int),
        "pager_threaded": bool(_pager_thread_enabled()),
        "two_phase_exchange": bool(_T.two_phase_exchange()),
    }


def main():
    if "--profile" in sys.argv[1:]:
        os.environ.setdefault(
            "KSIM_PROFILE_DIR", os.path.join(os.getcwd(), "ksim_profile")
        )
    nodes = int(os.environ.get("BENCH_NODES", 2000))
    pods_n = int(os.environ.get("BENCH_PODS", 20_000))
    S = int(os.environ.get("BENCH_SCENARIOS", 128))
    cpu_pods = int(os.environ.get("BENCH_CPU_PODS", 2000))
    # Mean pod runtime: the 20k-pod workload spans ~200 s of arrivals at
    # the default rate, so 50 s means most pods complete mid-replay and
    # several chunk boundaries carry real release work.
    dur_mean = float(os.environ.get("BENCH_DURATION_MEAN", 50.0))

    # DCN headline mode (round 11): under scripts/dcn_launch.py this
    # joins the coordinator (enabling the compile cache FIRST, per the
    # documented ordering); otherwise it is a no-op and the single-host
    # protocol below is unchanged.
    from kubernetes_simulator_tpu.parallel import dcn

    dcn.maybe_init_from_env()

    from kubernetes_simulator_tpu.utils.compile_cache import enable as _cc

    _cc()

    import jax

    from kubernetes_simulator_tpu.framework.framework import FrameworkConfig
    from kubernetes_simulator_tpu.models.encode import encode
    from kubernetes_simulator_tpu.parallel.mesh import make_mesh
    from kubernetes_simulator_tpu.sim.greedy import greedy_replay
    from kubernetes_simulator_tpu.sim.synthetic import make_cluster, make_workload
    from kubernetes_simulator_tpu.sim.whatif import WhatIfEngine, uniform_scenarios
    from kubernetes_simulator_tpu.utils.metrics import round_fragmentation

    # Mesh-default headline (round 10): shard the scenario axis over every
    # visible device; scenario count scales with the device count so each
    # device keeps the r05 per-chip shape (weak-scaling protocol).
    ndev = len(jax.devices())  # GLOBAL under DCN (all processes' devices)
    nproc = jax.process_count()
    mesh = make_mesh() if ndev > 1 else None
    S_head = S * ndev if mesh is not None else S
    mesh_shape = (
        dict(zip(mesh.axis_names, (int(d) for d in mesh.devices.shape)))
        if mesh is not None
        else None
    )

    cluster = make_cluster(nodes, seed=0, taint_fraction=0.1)

    def _make_pods(duration_mean):
        pods, _ = make_workload(
            pods_n, seed=0, with_affinity=True, with_spread=True,
            with_tolerations=True, gang_fraction=0.02, gang_size=4,
            duration_mean=duration_mean or None,
        )
        return pods

    pods = _make_pods(dur_mean)
    ec, ep = encode(cluster, pods)
    cfg = FrameworkConfig()

    # CPU default-path baseline on a subsample (same workload incl.
    # durations — the greedy anchor mirrors the chunk-granular releases).
    pods_small = pods[:cpu_pods]
    ec_s, ep_s = encode(cluster, pods_small)
    cpu_res = greedy_replay(
        ec_s, ep_s, FrameworkConfig(),
        completions_chunk_waves=512 if dur_mean else None,
    )
    cpu_pps = cpu_res.placements_per_sec

    # JAX what-if batch: compile once (warmup run), then N timed runs.
    # The headline is the MEDIAN rate — a single run can stall, and a
    # single best-of-K number made cross-round comparisons
    # indistinguishable from noise (round-2 verdict); min/max/all walls
    # ship in detail for spread inspection.
    runs = max(1, int(os.environ.get("BENCH_RUNS", 5)))

    def _timed(eng, n):
        eng.run()  # warmup: compile + first execution
        rs = [eng.run() for _ in range(n)]
        ws = sorted(r.wall_clock_s for r in rs)
        return rs[0], float(np.median(ws)), ws

    # Device-profiler hooks (round 12): the per-process trace lands in
    # KSIM_PROFILE_DIR (siblings suffix .p<pid> like every other sink).
    from kubernetes_simulator_tpu.utils.profiling import (
        device_trace,
        live_buffer_stats,
        profile_dir,
    )

    prof_dir = profile_dir()
    eng_head = WhatIfEngine(
        ec, ep, uniform_scenarios(ec, S_head, seed=0), cfg,
        chunk_waves=512, mesh=mesh,
    )
    if prof_dir:
        # Compile outside the trace: a multi-second first dispatch fills
        # the profiler's event buffer and truncates the annotations the
        # trace exists for.
        eng_head.run()
    with device_trace(dcn.output_path_for_process(prof_dir)):
        res, med_wall, walls = _timed(eng_head, runs)
    value = res.total_placed / med_wall if med_wall > 0 else 0.0
    vs = value / cpu_pps if cpu_pps > 0 else 0.0

    # Weak/strong-scaling references (mesh only). Weak: the r05 per-chip
    # shape (S scenarios, one device) — efficiency is per-device headline
    # rate over that. Strong: the SAME total scenario count on one device
    # — speedup is the headline rate over that. References get fewer
    # timed runs (they exist for the ratio, not the headline).
    # DCN-scaling block (round 11): per-process and aggregate pps next to
    # the PR-6 weak/strong block. The weak/strong/continuity/tuner
    # anchors are SINGLE-PROCESS references — under DCN they would be
    # silently re-shaped by the scenario slicing, so they are skipped
    # here and stay comparable by running bench.py without the launcher.
    dcn_block = {}
    if nproc > 1:
        dcn_block = {
            "dcn_scaling": {
                "process_count": nproc,
                "local_devices": ndev // nproc,
                "aggregate_pps": round(value, 1),
                "per_process_pps": round(value / nproc, 1),
                "local_wall_median_s": round(med_wall, 3),
                "single_process_reference": (
                    "run bench.py without dcn_launch.py for the "
                    "weak/strong + continuity anchors"
                ),
            }
        }

    # Elastic-recovery costs (round 15) — informational detail only
    # (bench_compare.py never gates on it). The headline timed runs
    # above keep checkpoint publication OFF (KSIM_DCN_CKPT_EVERY
    # defaults to 0), so ``value`` and the dcn_scaling block are
    # byte-unchanged by this block existing; it prices what turning
    # recovery on would cost:
    #   * codec walls: pack→pickle→b64 round-trip of a carrier-shaped
    #     snapshot (states [S_head, pods] + outs) — the per-publication
    #     CPU cost, and the restore cost a claimant pays before
    #     re-entering the chunk loop (failure DETECTION adds
    #     KSIM_DCN_STALL_S on top — a knob, not a measurement).
    #   * publish_overhead_pct: one extra replay with publication
    #     forced on (BENCH_CKPT_EVERY, default 8) against the headline
    #     median. Fleet-only — publish_checkpoint no-ops single-process
    #     — so the key is null outside dcn_launch.py.
    rec_block = {}
    if int(os.environ.get("BENCH_RECOVERY", "1") or 0):
        from kubernetes_simulator_tpu.parallel.dcn import (
            _decode_payload,
            _encode_payload,
        )

        rng = np.random.default_rng(15)
        snap = {
            "cursor": 7,
            "leaves": {
                "states": rng.integers(
                    -1, nodes, size=(S_head, len(pods)), dtype=np.int32
                ),
            },
            "outs": rng.random((S_head, 8)).astype(np.float32),
        }
        raw_mib = (
            snap["leaves"]["states"].nbytes + snap["outs"].nbytes
        ) / 2**20
        reps = max(1, int(os.environ.get("BENCH_RECOVERY_REPS", 3)))
        t0 = time.perf_counter()
        for _ in range(reps):
            chunks = _encode_payload(snap)
        enc_s = (time.perf_counter() - t0) / reps
        t0 = time.perf_counter()
        for _ in range(reps):
            _decode_payload(chunks)
        dec_s = (time.perf_counter() - t0) / reps

        publish_overhead_pct = None
        if nproc > 1 and med_wall > 0:
            prev_ck = os.environ.get("KSIM_DCN_CKPT_EVERY")
            os.environ["KSIM_DCN_CKPT_EVERY"] = str(
                max(1, int(os.environ.get("BENCH_CKPT_EVERY", 8)))
            )
            try:
                wall_ck = eng_head.run().wall_clock_s
            finally:
                if prev_ck is None:
                    os.environ.pop("KSIM_DCN_CKPT_EVERY", None)
                else:
                    os.environ["KSIM_DCN_CKPT_EVERY"] = prev_ck
            publish_overhead_pct = round(
                100.0 * (wall_ck - med_wall) / med_wall, 1
            )
        rec_block = {
            "dcn_recovery": {
                "recover_enabled": dcn.recover_enabled(),
                "ckpt_every": dcn.ckpt_every(),
                "ckpt_raw_mib": round(raw_mib, 2),
                "ckpt_blob_mib": round(
                    sum(len(c) for c in chunks) / 2**20, 2
                ),
                "ckpt_encode_s": round(enc_s, 4),
                "ckpt_publish_overhead_pct": publish_overhead_pct,
                "recovery_restore_wall_s": round(dec_s, 4),
            }
        }

    # Faultline costs (round 17) — informational detail only
    # (bench_compare.py never gates on it). Prices the hardening layer
    # under a FIXED injected schedule, no fleet needed:
    #   * retry_*: kv_retry absorbing a seeded 30% transient-error storm
    #     (tiny real backoff so the wall is the helper's, not a sleep).
    #   * crc_frame_*: CRC32+length framing overhead over a carrier-
    #     shaped blob, as a % of the round-14 codec's encode wall.
    #   * torn detection + fallback_recovery_wall_s: every blob the
    #     injector tears must be rejected by the frame check, and the
    #     wall is the full fallback path — reject the corrupt newest
    #     cursor, unframe + decode the prior complete one.
    fault_block = {}
    if int(os.environ.get("BENCH_FAULTLINE", "1") or 0):
        from kubernetes_simulator_tpu.parallel import faultline
        from kubernetes_simulator_tpu.parallel.dcn import (
            DcnRetryError,
            _decode_payload,
            _encode_payload,
            _frame_chunk,
            _unframe_chunk,
            kv_retry,
        )

        inj = faultline.Injector(seed=17, pid=0, kv_error_rate=0.3)

        def _flaky_op():
            if inj.hit("kv_error"):
                raise faultline.FaultlineInjected("bench")

        rs0 = dcn.retry_stats()
        n_ops, gaveup = 64, 0
        t0 = time.perf_counter()
        for _ in range(n_ops):
            try:
                kv_retry(
                    _flaky_op, op="bench", attempts=4,
                    base_s=1e-4, cap_s=4e-4,
                )
            except DcnRetryError:
                gaveup += 1
        retry_wall = time.perf_counter() - t0
        rs1 = dcn.retry_stats()

        rng_f = np.random.default_rng(17)
        snap_f = {
            "cursor": 3,
            "leaves": {
                "states": rng_f.integers(
                    -1, nodes, size=(256, 512), dtype=np.int32
                )
            },
        }
        t0 = time.perf_counter()
        raw_f = _encode_payload(snap_f)
        enc_f = time.perf_counter() - t0
        t0 = time.perf_counter()
        framed = [_frame_chunk(c) for c in raw_f]
        frame_s = time.perf_counter() - t0
        tear_inj = faultline.Injector(seed=17, pid=0, torn_write_rate=1.0)
        torn = [tear_inj.tear(c) for c in framed]
        detected = 0
        t0 = time.perf_counter()
        for bad in torn:
            try:
                _unframe_chunk(bad)
            except ValueError:
                detected += 1
        _decode_payload(_unframe_chunk(c) for c in framed)
        fallback_wall = time.perf_counter() - t0
        fault_block = {
            "fault_injection": {
                "injected_kv_error_rate": 0.3,
                "retry_ops": n_ops,
                "retry_count": rs1["retries"] - rs0["retries"],
                "retry_giveups": gaveup,
                "retry_wall_s": round(retry_wall, 4),
                "crc_frame_wall_s": round(frame_s, 4),
                "crc_frame_overhead_pct": round(
                    100.0 * frame_s / enc_f if enc_f > 0 else 0.0, 1
                ),
                "torn_injected": len(torn),
                "torn_detected": detected,
                "fallback_count": len(torn),
                "fallback_recovery_wall_s": round(fallback_wall, 4),
            }
        }

    # Work-queue accounting (round 18) — informational detail only
    # (bench_compare.py never gates on it). Populated when the timed
    # runs above actually drained the work-stealing queue (bench under
    # dcn_launch.py with KSIM_DCN_WORKQUEUE=1): this process's lease/
    # steal/speculation counters, the lease-renewal overhead as a share
    # of the headline median wall, and the lower-bound straggler wall
    # saved by speculative wins.
    wq_block = {}
    if dcn.wq_enabled():
        ws = dcn.wq_stats()
        renew_pct = None
        if nproc > 1 and med_wall > 0:
            renew_pct = round(100.0 * ws["renew_wall_s"] / med_wall, 2)
        wq_block = {
            "work_queue": {
                "block_size": dcn.wq_block_size() or None,
                "speculate": dcn.speculate_enabled(),
                "leases": ws["leases"],
                "steals": ws["steals"],
                "blocks_executed": ws["blocks_executed"],
                "spec_attempts": ws["spec_attempts"],
                "spec_wins": ws["spec_wins"],
                "spec_losses": ws["spec_losses"],
                "spec_wasted_chunks": ws["spec_wasted_chunks"],
                "dup_discards": ws["dup_discards"],
                "lease_renewals": ws["renewals"],
                "lease_renew_overhead_pct": renew_pct,
                "straggler_wall_saved_s": round(
                    ws["straggler_wall_saved_s"], 3
                ),
            }
        }

    # Durable-ground accounting (round 20) — informational detail only
    # (bench_compare.py diffs it without gating). Fleet-free micro-bench
    # of the durability-journal layer (parallel.dcn, KSIM_DCN_DURABLE_DIR)
    # in a throwaway directory:
    #   * journal_write_overhead_pct: the mirror wall (framed chunks +
    #     manifest-last, temp-then-rename) as a share of the encode+frame
    #     wall the publication already pays — the budget the round-19
    #     publisher thread hides it behind;
    #   * cold_resume_wall_s: walk the journal exactly as a restarted
    #     fleet's load_checkpoint would (namespace scan + full kf1/crc
    #     validation of the newest cursor per block);
    #   * adopted_blocks: completed work-queue blocks a fresh fleet
    #     adopts from the journal without re-execution (_journal_wq_scan
    #     over mirrored result blobs + done records).
    durable_block = {}
    if int(os.environ.get("BENCH_DURABLE", "1") or 0):
        import shutil
        import tempfile
        import zlib

        from kubernetes_simulator_tpu.parallel.dcn import (
            _encode_payload,
            _frame_chunk,
            _journal_ckpt_entries,
            _journal_read_blob,
            _journal_wq_result,
            _journal_wq_scan,
            _journal_write_blob,
            _journal_write_json,
        )

        jdir = tempfile.mkdtemp(prefix="ksim_bench_journal_")
        prev_jdir = os.environ.get("KSIM_DCN_DURABLE_DIR")
        os.environ["KSIM_DCN_DURABLE_DIR"] = jdir
        try:
            rng_d = np.random.default_rng(20)
            n_epochs, n_blocks = 8, 4
            snaps, encode_wall, mirror_wall = [], 0.0, 0.0
            for epoch_i in range(n_epochs):
                snap = {
                    "cursor": epoch_i,
                    "leaves": {
                        "states": rng_d.integers(
                            -1, nodes, size=(256, 512), dtype=np.int32
                        )
                    },
                }
                t0 = time.perf_counter()
                raw = _encode_payload(snap)
                crc, blob_len = 0, 0
                for ch in raw:
                    crc = zlib.crc32(ch.encode("ascii"), crc)
                    blob_len += len(ch)
                chunks = [_frame_chunk(ch) for ch in raw]
                manifest = json.dumps(
                    {"n": len(chunks), "crc": f"{crc & 0xFFFFFFFF:08x}",
                     "len": blob_len},
                    sort_keys=True,
                )
                encode_wall += time.perf_counter() - t0
                t0 = time.perf_counter()
                ok = _journal_write_blob(
                    os.path.join("ckpt", "1", "0", "0-64", str(epoch_i)),
                    chunks, manifest,
                )
                mirror_wall += time.perf_counter() - t0
                assert ok, "journal mirror failed in a fresh tempdir"
                snaps.append(snap)
            for bid in range(n_blocks):
                _journal_wq_result(
                    os.path.join("wq", "1", "bench"), bid, snaps[bid]
                )
                _journal_write_json(
                    os.path.join("wq", "1", "bench", "done", str(bid)),
                    {"pid": 0, "gen": 0, "spec": 0},
                )
            t0 = time.perf_counter()
            entries = _journal_ckpt_entries(0, 1)
            newest = max(int(cur) for _, cur in entries)
            _journal_read_blob(
                os.path.join("ckpt", "1", "0", "0-64", str(newest))
            )
            cold_wall = time.perf_counter() - t0
            adopted, _hint = _journal_wq_scan(1, "bench", n_blocks)
            durable_block = {
                "durable_ground": {
                    "journal_epochs": n_epochs,
                    "journal_write_wall_s": round(mirror_wall, 4),
                    "journal_write_overhead_pct": round(
                        100.0 * mirror_wall / encode_wall
                        if encode_wall > 0 else 0.0,
                        1,
                    ),
                    "cold_resume_wall_s": round(cold_wall, 4),
                    "cold_resume_cursors_seen": len(entries),
                    "adopted_blocks": len(adopted),
                }
            }
        finally:
            if prev_jdir is None:
                os.environ.pop("KSIM_DCN_DURABLE_DIR", None)
            else:
                os.environ["KSIM_DCN_DURABLE_DIR"] = prev_jdir
            shutil.rmtree(jdir, ignore_errors=True)

    scaling = {}
    if mesh is not None and nproc == 1:
        runs_ref = max(1, int(os.environ.get("BENCH_REF_RUNS", 2)))
        res_w, med_w, _ = _timed(
            WhatIfEngine(
                ec, ep, uniform_scenarios(ec, S, seed=0), cfg,
                chunk_waves=512,
            ),
            runs_ref,
        )
        weak_pps = res_w.total_placed / med_w if med_w > 0 else 0.0
        res_st, med_st, _ = _timed(
            WhatIfEngine(
                ec, ep, uniform_scenarios(ec, S_head, seed=0), cfg,
                chunk_waves=512,
            ),
            runs_ref,
        )
        strong_pps = res_st.total_placed / med_st if med_st > 0 else 0.0
        scaling = {
            "scaling": {
                "per_device_pps": round(value / ndev, 1),
                "weak": {
                    "single_chip_scenarios": S,
                    "single_chip_pps": round(weak_pps, 1),
                    "efficiency": round(
                        (value / ndev) / weak_pps if weak_pps > 0 else 0.0, 3
                    ),
                },
                "strong": {
                    "single_chip_scenarios": S_head,
                    "single_chip_pps": round(strong_pps, 1),
                    "speedup": round(
                        value / strong_pps if strong_pps > 0 else 0.0, 2
                    ),
                    "efficiency": round(
                        value / strong_pps / ndev if strong_pps > 0 else 0.0,
                        3,
                    ),
                },
                "reference_timed_runs": runs_ref,
            }
        }

    # Arrivals-only continuity run (the r01–r03 protocol, same shape
    # minus durations) so rounds stay comparable across the change.
    # Deliberately single-chip at the per-device scenario count: this is
    # the cross-round anchor, so its configuration never moves.
    cont = {}
    if dur_mean and nproc == 1:
        ec_c, ep_c = encode(cluster, _make_pods(None))
        eng_c = WhatIfEngine(
            ec_c, ep_c, uniform_scenarios(ec_c, S, seed=0), cfg,
            chunk_waves=512,
        )
        eng_c.run()
        runs_c = [eng_c.run() for _ in range(runs)]
        walls_c = sorted(r.wall_clock_s for r in runs_c)
        med_c = float(np.median(walls_c))
        cont = {
            "durationless_pps": round(
                runs_c[0].total_placed / med_c if med_c > 0 else 0.0, 1
            ),
            "durationless_wall_median_s": round(med_c, 3),
            "durationless_walls_s": [round(w, 3) for w in walls_c],
        }

    # Policy-tuner population sweep (round 9): P candidate policy vectors
    # × S_t train scenarios flattened onto the scenario axis, values
    # swapped between runs via set_policies — one compile, so the rate is
    # pure sweep throughput, the quantity a search round pays per
    # candidate. Same search space as examples/config2_full_plugins_5k
    # (all 5 default Score weights + the fit-strategy selector).
    tune_sweep = {}
    P_t = int(os.environ.get("BENCH_TUNE_POP", 16))
    S_t = int(os.environ.get("BENCH_TUNE_SCEN", 4))
    if P_t > 0 and nproc == 1:
        from kubernetes_simulator_tpu.ops import tpu as T

        rng = np.random.default_rng(0)
        K = len(T.POLICY_COLS)

        def _cands():
            c = rng.uniform(0.0, 10.0, size=(P_t, K)).astype(np.float32)
            c[:, T.IDX_FIT_LEAST] = (rng.random(P_t) < 0.5).astype(np.float32)
            return np.repeat(c, S_t, axis=0)

        train = uniform_scenarios(ec, S_t, seed=0)
        eng_t = WhatIfEngine(
            ec, ep, train * P_t, cfg, chunk_waves=512, policies=_cands(),
        )
        eng_t.run()  # warmup: compile + first execution
        walls_t = []
        for _ in range(runs):
            eng_t.set_policies(_cands())
            walls_t.append(eng_t.run().wall_clock_s)
        med_t = float(np.median(sorted(walls_t)))
        tune_sweep = {
            "tune_popsweep": {
                "candidate_policies_per_sec": round(
                    P_t / med_t if med_t > 0 else 0.0, 2
                ),
                "population": P_t,
                "train_scenarios": S_t,
                "wall_median_s": round(med_t, 3),
            }
        }

    # Borg-scale single scenario (round 14): ONE scenario whose node and
    # pod axes dwarf the headline shape (default 10k nodes × 100k pods on
    # accelerators; CPU meshes downscale so CI stays in budget), run
    # node-sharded over every local device with paged pod waves — the
    # configuration the replicated path cannot hold at Borg scale at all.
    # BENCH_BORG=0 disables; BENCH_BORG_NODES / BENCH_BORG_PODS resize.
    borg_block = {}
    if int(os.environ.get("BENCH_BORG", 1)) and nproc == 1 and ndev > 1:
        from kubernetes_simulator_tpu.sim.jax_runtime import JaxReplayEngine

        on_cpu = jax.devices()[0].platform == "cpu"
        borg_nodes = int(
            os.environ.get("BENCH_BORG_NODES", 1000 if on_cpu else 10_000)
        )
        borg_pods = int(
            os.environ.get("BENCH_BORG_PODS", 20_000 if on_cpu else 100_000)
        )
        borg_cluster = make_cluster(borg_nodes, seed=0, taint_fraction=0.1)
        borg_pods_l, _ = make_workload(
            borg_pods, seed=0, with_affinity=True, with_spread=True,
            with_tolerations=True, gang_fraction=0.02, gang_size=4,
            duration_mean=dur_mean or None,
        )
        ec_b, ep_b = encode(borg_cluster, borg_pods_l)
        # Document the refusal the sharded mode exists to dodge: at the
        # flagship accelerator shape the REPLICATED planes bust a single
        # chip's HBM — probed via the residency estimate, not an OOM.
        from kubernetes_simulator_tpu.sim.jax_runtime import (
            replicated_resident_bytes,
        )
        replicated_bytes = replicated_resident_bytes(ec_b, ep_b)
        eng_b = JaxReplayEngine(
            ec_b, ep_b, cfg, chunk_waves=512, node_shards=ndev, paged=True,
        )
        eng_b.replay()  # warmup: compile + first execution
        runs_b = [
            eng_b.replay()
            for _ in range(max(1, int(os.environ.get("BENCH_REF_RUNS", 2))))
        ]
        walls_b = sorted(r.wall_clock_s for r in runs_b)
        med_b = float(np.median(walls_b))
        res_b = runs_b[0]
        borg_block = {
            "borg_scale": {
                "nodes": borg_nodes,
                "pods": borg_pods,
                "node_shards": ndev,
                "paged": True,
                "pps": round(
                    res_b.placed / med_b if med_b > 0 else 0.0, 1
                ),
                "wall_median_s": round(med_b, 3),
                "placed": int(res_b.placed),
                "replicated_resident_mib": round(
                    replicated_bytes / 2**20, 1
                ),
            }
        }

    # Borg-headline composed run (round 16): make_borg_encoded at the
    # BASELINE shape (BENCH_HEADLINE_NODES/PODS; CPU meshes downscale so
    # the CI gate stays in budget) through the FULL composed stack —
    # nodeShards over every local device × pagedWaves — with the flight
    # recorder ON. This is the 10k×1M run ROADMAP item 1 calls for,
    # instrumented: wall, pps, peak residency, per-phase shares and the
    # recorded stream's event count land in detail.borg_headline, and
    # the stream itself (path stamped) feeds scripts/bottleneck_report.py.
    # BENCH_HEADLINE=0 disables; BENCH_HEADLINE_FLIGHT overrides the sink.
    headline_block = {}
    if int(os.environ.get("BENCH_HEADLINE", 1)) and nproc == 1 and ndev > 1:
        import tempfile

        from kubernetes_simulator_tpu.sim.borg import (
            BorgSpec,
            make_borg_encoded,
        )
        from kubernetes_simulator_tpu.sim.flight import read_stream
        from kubernetes_simulator_tpu.sim.jax_runtime import (
            JaxReplayEngine,
            replicated_resident_bytes,
        )

        on_cpu = jax.devices()[0].platform == "cpu"
        h_nodes = int(
            os.environ.get("BENCH_HEADLINE_NODES", 1000 if on_cpu else 10_000)
        )
        h_pods = int(
            os.environ.get(
                "BENCH_HEADLINE_PODS", 20_000 if on_cpu else 1_000_000
            )
        )
        ec_h, ep_h, _ = make_borg_encoded(
            BorgSpec(nodes=h_nodes, tasks=h_pods, seed=0)
        )
        fl_path = os.environ.get("BENCH_HEADLINE_FLIGHT") or os.path.join(
            tempfile.mkdtemp(prefix="ksim_flight_"), "flight.jsonl"
        )
        eng_h = JaxReplayEngine(
            ec_h, ep_h, cfg, chunk_waves=512, node_shards=ndev, paged=True,
            telemetry="summary",
        )
        eng_h.replay()  # warmup: compile + first execution, recorder off
        eng_h.flight_recorder = fl_path  # record the timed run only
        t0_h = time.perf_counter()
        res_h = eng_h.replay()
        wall_h = time.perf_counter() - t0_h
        ph = dict(res_h.telemetry.phases) if res_h.telemetry else {}
        ph_total = sum(ph.values()) or 1.0
        flight_rows = read_stream(fl_path)
        headline_block = {
            "borg_headline": {
                "nodes": h_nodes,
                "pods": h_pods,
                "node_shards": ndev,
                "paged": True,
                "pps": round(
                    res_h.placed / wall_h if wall_h > 0 else 0.0, 1
                ),
                "wall_s": round(wall_h, 3),
                "placed": int(res_h.placed),
                "replicated_resident_mib": round(
                    replicated_resident_bytes(ec_h, ep_h) / 2**20, 1
                ),
                "phase_shares": {
                    k: round(v / ph_total, 3) for k, v in sorted(ph.items())
                },
                "flight_path": fl_path,
                "flight_events": len(flight_rows),
                "pager_stalls": max(
                    (
                        int(r.get("pager_stalls", 0))
                        for r in flight_rows
                        if r.get("event") == "chunk"
                    ),
                    default=0,
                ),
                # Overlap sub-block (round 19): how much of the three
                # former stalls is now hidden off the critical path.
                # exposed_stall_s is THE number the tentpole shrinks —
                # bench_compare flags its growth (pps stays the gate);
                # hidden_prefetch_s is pager fetch wall absorbed by the
                # background worker instead of the chunk loop.
                "overlap": _overlap_block(ph, flight_rows),
            }
        }

    # Resident query service (round 22): cold-start wall vs warm query
    # latency through the pooled-engine serving plane, plus coalesced
    # defrag throughput at full batch occupancy. The warm/cold ratio is
    # THE acceptance number — a warm query swaps scenario values against
    # the resident executable (zero recompilation, compile_counts pins
    # it), so it must come in >= 10x cheaper than the cold build.
    # BENCH_SERVICE=0 disables; BENCH_SERVICE_NODES/PODS resize.
    service_block = {}
    if int(os.environ.get("BENCH_SERVICE", 1)) and nproc == 1:
        from kubernetes_simulator_tpu.sim.service import QueryService

        s_nodes = int(os.environ.get("BENCH_SERVICE_NODES", 200))
        s_pods = int(os.environ.get("BENCH_SERVICE_PODS", 2000))
        s_rounds = int(os.environ.get("BENCH_SERVICE_ROUNDS", 4))
        cluster_s = make_cluster(s_nodes, seed=0)
        pods_s, _ = make_workload(
            s_pods, seed=0, duration_mean=dur_mean or None
        )
        ec_s, ep_s = encode(cluster_s, pods_s)
        svc = QueryService(ec_s, ep_s, cfg, max_batch=3, chunk_waves=512)
        rng_s = np.random.default_rng(0)
        qi = iter(range(10_000))

        def _defrag(i):
            picks = rng_s.choice(s_nodes, size=2, replace=False)
            return {"op": "defrag", "tenant": f"team-{i % 3}",
                    "id": f"q{i}", "nodes": [int(n) for n in picks],
                    "drainAt": 5.0, "recoverAt": 20.0}

        svc.submit(_defrag(next(qi)))
        svc.flush()
        cold_lat = float(svc.poll()[0]["latency_s"])
        warm_lats = []
        for _ in range(s_rounds):  # single-query flushes: pure latency
            svc.submit(_defrag(next(qi)))
            svc.flush()
            warm_lats.append(float(svc.poll()[0]["latency_s"]))
        warm_med = float(np.median(sorted(warm_lats)))
        t0_s = time.perf_counter()  # full-occupancy coalesced rounds
        n_coal = 0
        for _ in range(s_rounds):
            for _ in range(3):
                svc.submit(_defrag(next(qi)))  # 3rd submit auto-flushes
            n_coal += 3
        svc.poll()
        coal_wall = time.perf_counter() - t0_s
        st_s = svc.stats()
        svc.close()
        service_block = {
            "service": {
                "nodes": s_nodes,
                "pods": s_pods,
                "cold_latency_s": round(cold_lat, 3),
                "warm_latency_median_s": round(warm_med, 4),
                "warm_speedup": round(
                    cold_lat / warm_med if warm_med > 0 else 0.0, 1
                ),
                "warm_queries_per_sec": round(
                    n_coal / coal_wall if coal_wall > 0 else 0.0, 2
                ),
                "queries": st_s["queries"],
                "batches": st_s["batches"],
                "cold_builds": st_s["cold_builds"],
                "warm_hits": st_s["warm_hits"],
                "compile_counts": st_s["compile_counts"],
            }
        }

    # Memory watermarks (round 16): host RSS high-water + the PEAK
    # replicated-residency estimate across every workload this invocation
    # encoded — stamped at the TOP level of every bench JSON so the
    # BENCH_r* trajectory captures memory, not just pps.
    from kubernetes_simulator_tpu.sim.flight import rss_peak_mib
    from kubernetes_simulator_tpu.sim.jax_runtime import (
        replicated_resident_bytes as _rrb,
    )

    resident_peak_mib = _rrb(ec, ep) / 2**20
    for blk, key in (
        (borg_block.get("borg_scale"), "replicated_resident_mib"),
        (headline_block.get("borg_headline"), "replicated_resident_mib"),
    ):
        if blk:
            resident_peak_mib = max(resident_peak_mib, blk[key])

    line = json.dumps(
            {
                "metric": "pod-placements/sec (what-if %d scenarios x %d nodes x %d pods, full default plugin set, %s, %d device%s)"
                % (
                    S_head, nodes, pods_n,
                    "completions on"
                    if res.completions_on
                    else "arrivals-only",
                    ndev, "" if ndev == 1 else "s",
                ),
                "value": round(value, 1),
                "unit": "placements/sec",
                "vs_baseline": round(vs, 2),
                # Top-level provenance (round 10): rounds are only
                # comparable within a configuration — stamp it where the
                # round-over-round diff tooling looks first. Round 11
                # adds process_count (1 = the single-host protocol).
                "n_devices": ndev,
                "mesh_shape": mesh_shape,
                "scenarios": S_head,
                "process_count": nproc,
                # Round 16: memory watermarks on every bench line.
                "rss_peak_mib": rss_peak_mib(),
                "replicated_resident_peak_mib": round(resident_peak_mib, 1),
                "detail": {
                    "jax_wall_median_s": round(med_wall, 3),
                    "jax_wall_min_s": round(walls[0], 3),
                    "jax_wall_max_s": round(walls[-1], 3),
                    "jax_walls_s": [round(w, 3) for w in walls],
                    "timed_runs": runs,
                    "jax_total_placed": res.total_placed,
                    "completions_on": bool(res.completions_on),
                    "duration_mean_s": dur_mean,
                    "cpu_default_path_pps": round(cpu_pps, 1),
                    # Utilization economics (round 13): end-of-replay
                    # utilization + fragmentation gauges of the CPU
                    # baseline, and the what-if batch's mean scenario CPU
                    # utilization — bench_compare.py diffs these like the
                    # headline pps.
                    "utilization": {
                        "cpu_baseline_util_cpu": round(
                            cpu_res.utilization.get("cpu", 0.0), 6
                        ),
                        "cpu_baseline_fragmentation": round_fragmentation(
                            cpu_res.fragmentation
                        ),
                        "whatif_util_cpu_mean": round(
                            float(np.mean(res.utilization_cpu)), 6
                        ),
                    },
                    "scenario0_placed": int(res.placed[0]),
                    "device": _device_kind(),
                    # Round 12: engine wall-clock phase shares (fleet-
                    # merged, "p<pid>/<phase>" keys) + live-buffer/memory
                    # watermark after the timed runs.
                    "phases": (
                        dict(res.fleet_telemetry.phases)
                        if res.fleet_telemetry is not None
                        else {}
                    ),
                    "live_buffers": live_buffer_stats(),
                    **(
                        {"profile_dir": prof_dir} if prof_dir else {}
                    ),
                    **dcn_block,
                    **rec_block,
                    **fault_block,
                    **wq_block,
                    **durable_block,
                    **scaling,
                    **cont,
                    **tune_sweep,
                    **borg_block,
                    **headline_block,
                    **service_block,
                },
            }
        )
    # One JSON line per fleet: every process computes the identical
    # gathered result, only process 0 speaks.
    if jax.process_index() == 0:
        print(line)


def _device_kind() -> str:
    try:
        import jax

        return str(jax.devices()[0])
    except Exception as e:  # pragma: no cover
        return f"unavailable: {e}"


if __name__ == "__main__":
    main()
