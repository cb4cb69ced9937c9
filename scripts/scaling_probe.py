"""Perf probe: how does per-pod step cost scale with S (scenarios) and N
(nodes)? Finds whether the wave scan is latency- or compute-bound.

``--dcn`` (round 11) adds the process-count axis to the trajectory: the
probe re-runs ITSELF under scripts/dcn_launch.py for each process count,
so the scaling record holds device-count sweeps (the default sweep below)
and DCN process-count sweeps side by side. Inside a DCN fleet every
process prints its local wall; read process 0's line (the others carry a
[pN] prefix only on failure). The launcher is a CPU harness (its children
run with JAX_PLATFORMS=cpu — N processes cannot share a chip), so the
``--dcn`` axis is a CPU record whatever device the parent sweep ran on.

``--exchange [OUT_JSON]`` (round 19) pins the per-slot selection-exchange
payload bytes and replay wall at node_shards ∈ {1, 2, 4, 8} into a JSON
that scripts/bench_compare.py diffs — payload growth at any shard count
gates there.
"""

import os as _os
import sys as _sys

_sys.path.insert(0, _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))))

import argparse
import subprocess
import time

import numpy as np

from kubernetes_simulator_tpu.parallel import dcn as _dcn

_dcn.maybe_init_from_env()

from kubernetes_simulator_tpu.framework.framework import FrameworkConfig
from kubernetes_simulator_tpu.models.encode import encode
from kubernetes_simulator_tpu.sim.synthetic import make_cluster, make_workload
from kubernetes_simulator_tpu.sim.whatif import WhatIfEngine, uniform_scenarios


def probe(nodes, pods_n, S, chunk_waves=256, mesh=None):
    cluster = make_cluster(nodes, seed=0, taint_fraction=0.1)
    pods, _ = make_workload(
        pods_n, seed=0, with_affinity=True, with_spread=True, with_tolerations=True,
        gang_fraction=0.02, gang_size=4,
    )
    ec, ep = encode(cluster, pods)
    scenarios = uniform_scenarios(ec, S, seed=0)
    eng = WhatIfEngine(
        ec, ep, scenarios, FrameworkConfig(), chunk_waves=chunk_waves,
        mesh=mesh,
    )
    eng.run()  # warmup
    t0 = time.perf_counter()
    res = eng.run()
    wall = time.perf_counter() - t0
    per_pod_us = wall / pods_n * 1e6
    tag = f" nproc={res.process_count}" if res.process_count > 1 else ""
    print(
        f"S={S:4d} N={nodes:5d} P={pods_n:6d} G={ec.num_groups:3d} "
        f"wall={wall:6.2f}s agg={res.placements_per_sec/1e3:8.1f}k/s "
        f"us/pod-step={per_pod_us:7.1f}{tag}"
    , flush=True)


def default_sweep():
    for S in (8, 32, 128, 256):
        probe(2000, 10_000, S)
    probe(10_000, 10_000, 32)
    probe(10_000, 10_000, 128)


def node_probe(nodes, pods_n, node_shards, paged=False):
    """One single-scenario replay at N nodes — replicated planes when
    ``node_shards`` <= 1, node-sharded over that many devices otherwise
    (round 14 big-scenario mode)."""
    from kubernetes_simulator_tpu.sim.jax_runtime import JaxReplayEngine

    cluster = make_cluster(nodes, seed=0, taint_fraction=0.1)
    pods, _ = make_workload(
        pods_n, seed=0, with_affinity=True, with_spread=True,
        with_tolerations=True, gang_fraction=0.02, gang_size=4,
    )
    ec, ep = encode(cluster, pods)
    eng = JaxReplayEngine(
        ec, ep, FrameworkConfig(), node_shards=node_shards, paged=paged,
    )
    eng.replay()  # warmup (compile)
    t0 = time.perf_counter()
    res = eng.replay()
    wall = time.perf_counter() - t0
    mode = f"shards={node_shards}" if node_shards > 1 else "replicated"
    mode += "+paged" if paged else ""
    print(
        f"N={nodes:6d} P={pods_n:7d} {mode:>18s} wall={wall:6.2f}s "
        f"pps={res.placements_per_sec/1e3:8.1f}k/s",
        flush=True,
    )


def node_sweep(nodes_list, pods_n, paged=False):
    """Node-axis scaling at S=1 (round 14): each N runs replicated and
    node-sharded over all local devices, so the crossover where sharding
    starts paying (and the shapes the replicated path cannot hold at all)
    lands in the same scaling record as the S- and process-axis sweeps."""
    import jax

    ndev = len(jax.devices())
    for nodes in nodes_list:
        node_probe(nodes, pods_n, 1, paged=paged)
        if ndev > 1:
            node_probe(nodes, pods_n, ndev, paged=paged)


def exchange_sweep(out_path, nodes, pods_n):
    """Round 19: pin the per-slot selection-exchange payload at
    node_shards ∈ {1, 2, 4, 8}. Bytes are analytic
    (ops.tpu.exchange_payload_bytes — the implementation-neutral ring
    model, so the pin survives backend changes); walls are measured with
    a real node-sharded replay at every shard count the local device
    pool can host. The JSON lands under an ``exchange_sweep`` key that
    scripts/bench_compare.py diffs: payload growth at any shard count
    gates, wall moves are informational."""
    import json

    import jax

    from kubernetes_simulator_tpu.ops import tpu as T
    from kubernetes_simulator_tpu.sim.jax_runtime import JaxReplayEngine

    cluster = make_cluster(nodes, seed=0, taint_fraction=0.1)
    pods, _ = make_workload(
        pods_n, seed=0, with_affinity=True, with_spread=True,
        with_tolerations=True, gang_fraction=0.02, gang_size=4,
    )
    ec, ep = encode(cluster, pods)
    G = max(ec.num_groups, 1)
    two_phase = T.two_phase_exchange()
    ndev = len(jax.devices())
    points = []
    for n in (1, 2, 4, 8):
        pt = {
            "node_shards": n,
            "payload_bytes": T.exchange_payload_bytes(n, G, two_phase),
            "payload_bytes_legacy": T.exchange_payload_bytes(n, G, False),
            "wall_s": None,
        }
        if n <= max(ndev, 1):
            eng = JaxReplayEngine(
                ec, ep, FrameworkConfig(), node_shards=n,
            )
            eng.replay()  # warmup (compile)
            t0 = time.perf_counter()
            eng.replay()
            pt["wall_s"] = round(time.perf_counter() - t0, 3)
        points.append(pt)
        print(
            f"exchange @{n} shards: payload={pt['payload_bytes']}B/slot "
            f"(legacy {pt['payload_bytes_legacy']}B) wall={pt['wall_s']}",
            flush=True,
        )
    doc = {
        "exchange_sweep": {
            "nodes": nodes,
            "pods": pods_n,
            "groups": G,
            "two_phase": bool(two_phase),
            "points": points,
        }
    }
    with open(out_path, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
    print(f"exchange sweep -> {out_path}", flush=True)


def dcn_sweep(proc_counts, S, nodes, pods_n):
    """Re-launch this probe under scripts/dcn_launch.py once per process
    count — the DCN axis of the scaling trajectory (device-count sweeps
    stay in the default sweep)."""
    here = _os.path.abspath(__file__)
    launcher = _os.path.join(_os.path.dirname(here), "dcn_launch.py")
    for nproc in proc_counts:
        print(f"--- dcn axis: {nproc} process(es) ---", flush=True)
        cmd = [
            _sys.executable, launcher, "--nproc", str(nproc),
            "--devices-per-proc", "2", "--",
            _sys.executable, here, "--inner",
            "--scenarios", str(S), "--nodes", str(nodes),
            "--pods", str(pods_n),
        ]
        rc = subprocess.call(cmd)
        if rc != 0:
            print(f"dcn axis: nproc={nproc} FAILED rc={rc}", flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dcn", nargs="?", const="1,2", default=None,
                    help="comma list of process counts to sweep "
                         "(default '1,2')")
    ap.add_argument("--inner", action="store_true",
                    help="(internal) run one probe inside a DCN fleet")
    ap.add_argument("--scenarios", type=int, default=32)
    ap.add_argument("--nodes", type=str, default="2000",
                    help="node count (int) for --dcn/--inner, or a comma "
                         "list to run the round-14 node-axis sweep "
                         "(replicated vs node-sharded at S=1)")
    ap.add_argument("--pods", type=int, default=10_000)
    ap.add_argument("--paged", action="store_true",
                    help="stream pod pages in the node-axis sweep")
    ap.add_argument("--exchange", nargs="?", const="exchange_sweep.json",
                    default=None, metavar="OUT_JSON",
                    help="round-19 selection-exchange payload sweep at "
                         "node_shards 1/2/4/8 — writes a JSON "
                         "bench_compare.py can diff (payload growth "
                         "gates)")
    args = ap.parse_args()
    node_list = [int(x) for x in str(args.nodes).split(",") if x]
    if args.exchange:
        exchange_sweep(args.exchange, node_list[0], args.pods)
    elif args.inner:
        from kubernetes_simulator_tpu.parallel.mesh import make_mesh

        import jax

        mesh = make_mesh() if len(jax.devices()) > 1 else None
        probe(node_list[0], args.pods, args.scenarios, mesh=mesh)
    elif args.dcn is not None:
        dcn_sweep(
            [int(x) for x in args.dcn.split(",") if x],
            args.scenarios, node_list[0], args.pods,
        )
    elif len(node_list) > 1:
        node_sweep(node_list, args.pods, paged=args.paged)
    else:
        default_sweep()


if __name__ == "__main__":
    main()
