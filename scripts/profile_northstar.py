"""Profile the v3 what-if wave step on the north-star shape (VERDICT r2 #1:
"profile, THEN close the gap" — no more unprofiled kernel work).

Three measurements on one chip:
1. XLA cost analysis of the compiled chunk fn: total FLOPs + bytes accessed
   → achieved HBM bandwidth when divided by measured wall (v5e peak ≈ 819
   GB/s). If achieved ≈ peak, the step is traffic-bound and the bytes
   number IS the optimization target.
2. Measured wall per chunk (warm), → attempts/s and projected full-trace
   wall.
3. Optional ``jax.profiler`` trace (PROFILE_DIR=...): per-op self-time
   aggregated from the perfetto trace, grouped by fusion name — the
   op-level breakdown the round-2 verdict asked for.

Env knobs: NS_NODES, NS_TASKS, NS_S, NS_WAVE, NS_CHUNK, PROFILE_DIR,
PROFILE_CHUNKS (how many chunks to run under the trace).
"""

import os as _os
import sys as _sys

_sys.path.insert(0, _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))))

import gzip
import json
import os
import time
from collections import defaultdict

import jax
import numpy as np

from kubernetes_simulator_tpu.framework.framework import FrameworkConfig
from kubernetes_simulator_tpu.ops import tpu as T
from kubernetes_simulator_tpu.sim.borg import BorgSpec, make_borg_encoded
from kubernetes_simulator_tpu.sim.whatif import WhatIfEngine, uniform_scenarios

# Peak HBM bandwidth in GB/s by jax device_kind (Google Cloud documentation,
# "TPU v5e": 819 GB/s; JAX reports the v5e as "TPU v5 lite"). A device that
# is not listed gets no "% of peak" figure.
PEAK_HBM_GBS = {"TPU v5 lite": 819.0}


def main():
    nodes = int(os.environ.get("NS_NODES", 10_000))
    tasks = int(os.environ.get("NS_TASKS", 100_000))
    S = int(os.environ.get("NS_S", 128))
    wave = int(os.environ.get("NS_WAVE", 8))
    chunk = int(os.environ.get("NS_CHUNK", 2048))
    prof_dir = os.environ.get("PROFILE_DIR", "")
    prof_chunks = int(os.environ.get("PROFILE_CHUNKS", 2))

    t0 = time.perf_counter()
    ec, ep, _ = make_borg_encoded(BorgSpec(nodes=nodes, tasks=tasks, seed=0))
    print(f"trace gen: {time.perf_counter() - t0:.1f}s", flush=True)

    scenarios = uniform_scenarios(ec, S, seed=0)
    # completions=False: profile the arrivals chunk program (the shared
    # core; the completions-on path adds the bucketed release fns and the
    # vassign fold on top — phase-attribute those with blocking timers,
    # the pattern in the round-4 COVERAGE perf log).
    eng = WhatIfEngine(
        ec, ep, scenarios, FrameworkConfig(), wave_width=wave,
        chunk_waves=chunk, completions=False,
    )
    print(f"engine: {eng.engine}  W={wave} C={chunk} S={S} N={nodes}", flush=True)
    assert eng.engine == "v3", "profiler targets the v3 scan"

    # One chunk's inputs, exactly as run() feeds them (fused-gather form).
    import jax.numpy as jnp

    idx = eng.waves.idx
    C = min(chunk, max(idx.shape[0], 1))
    states = eng._init_states()
    dc = eng.sset.dc
    src, xsrc = eng._slot_srcs
    idx_d = jnp.asarray(idx[:C])

    # --- 1. AOT cost analysis -------------------------------------------
    lowered = eng._chunk_fn.lower(dc, states, src, xsrc, idx_d)
    compiled = lowered.compile()
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, list):
            ca = ca[0]
    except Exception as e:
        ca = {}
        print(f"cost_analysis unavailable: {e}", flush=True)
    flops = float(ca.get("flops", 0.0))
    bytes_acc = float(ca.get("bytes accessed", 0.0))
    print(
        f"cost analysis: flops={flops / 1e12:.3f} TF/chunk  "
        f"bytes={bytes_acc / 1e9:.3f} GB/chunk",
        flush=True,
    )

    # --- 2. Warm timing --------------------------------------------------
    # Run through the AOT-compiled executable — the jit dispatch cache is
    # separate from lower()/compile(), so calling eng._chunk_fn here would
    # compile the multi-minute chunk program a second time.
    def run_chunk(st):
        st, out = compiled(dc, st, src, xsrc, idx_d)
        return st, out

    states, out = run_chunk(states)  # warmup (already compiled; executes)
    jax.block_until_ready(out)
    walls = []
    for _ in range(3):
        t0 = time.perf_counter()
        states, out = run_chunk(states)
        jax.block_until_ready(out)
        walls.append(time.perf_counter() - t0)
    wall = float(np.median(walls))
    kind = jax.devices()[0].device_kind
    peak = PEAK_HBM_GBS.get(kind)
    of_peak = (
        f" ({100 * bytes_acc / wall / 1e9 / peak:.0f}% of {kind} peak)"
        if peak
        else f" (no peak on record for {kind!r})"
    )
    attempts = C * wave * S
    n_waves_total = eng.waves.idx.shape[0]
    print(
        f"chunk wall={wall:.3f}s (runs {['%.3f' % w for w in walls]})  "
        f"attempts/s={attempts / wall / 1e6:.2f}M  "
        f"achieved_bw={bytes_acc / wall / 1e9:.0f} GB/s{of_peak}  "
        f"flops_rate={flops / wall / 1e12:.2f} TF/s",
        flush=True,
    )
    per_wave_bytes = bytes_acc / C
    print(
        f"per-wave: {per_wave_bytes / 1e6:.1f} MB  "
        f"({per_wave_bytes / (S * nodes * 4) :.0f} [S,N]-f32-plane equivalents)",
        flush=True,
    )
    full_wall_proj = wall * (1_000_000 / (C * wave)) if tasks else 0.0
    print(
        f"projection to 1M tasks at this rate: {full_wall_proj:.0f}s per chip",
        flush=True,
    )

    # --- 3. Optional profiler trace -------------------------------------
    if prof_dir:
        with jax.profiler.trace(prof_dir):
            for _ in range(prof_chunks):
                states, out = run_chunk(states)
            jax.block_until_ready(out)
        print(f"profile written to {prof_dir}", flush=True)
        summarize_trace(prof_dir)


def summarize_trace(prof_dir: str, top: int = 40):
    """Aggregate device-lane op self-times from the newest perfetto trace
    under ``prof_dir`` (TensorBoard not needed)."""
    cands = []
    for root, _dirs, files in os.walk(prof_dir):
        for f in files:
            if f.endswith(".trace.json.gz") or f.endswith(".trace.json"):
                p = os.path.join(root, f)
                cands.append((os.path.getmtime(p), p))
    if not cands:
        print("no trace.json found under profile dir", flush=True)
        return
    path = max(cands)[1]
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as f:
        data = json.load(f)
    events = data.get("traceEvents", [])
    # Device lanes: pid/tid names containing "TPU"/"/device:" — fall back
    # to aggregating every complete event with a duration.
    pid_names = {}
    for e in events:
        if e.get("ph") == "M" and e.get("name") == "process_name":
            pid_names[e.get("pid")] = e.get("args", {}).get("name", "")
    device_pids = {
        p for p, n in pid_names.items()
        if any(k in n for k in ("TPU", "Device", "device", "/device:"))
    }
    tot = defaultdict(float)
    cnt = defaultdict(int)
    for e in events:
        if e.get("ph") != "X":
            continue
        if device_pids and e.get("pid") not in device_pids:
            continue
        name = e.get("name", "?")
        tot[name] += float(e.get("dur", 0.0))
        cnt[name] += 1
    total = sum(tot.values())
    print(f"device op time total: {total / 1e6:.3f}s across {len(tot)} op names")
    for name, us in sorted(tot.items(), key=lambda kv: -kv[1])[:top]:
        print(f"  {us / 1e6:9.4f}s  {cnt[name]:6d}x  {name[:110]}")


if __name__ == "__main__":
    main()
