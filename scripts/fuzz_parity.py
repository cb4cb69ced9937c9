"""Randomized parity stress: host greedy anchor vs the device engine
across the full feature-knob space — affinity,
spread, tolerations, gangs, extended resources, forced host planes,
tier preemption, odd wave widths, and (round 4) finite durations with
chunk-granular completions, preemption × completions, and the boundary
retry buffer (what-if device path vs the anchor). Not part of the CI
suite (slow); run ad hoc before releases:

    JAX_PLATFORMS=cpu python scripts/fuzz_parity.py [trials] [master_seed]

A reduced-width seeded slice runs in CI: tests/test_fuzz_parity.py
(pytest -m fuzz) calls run_fuzz() below.
"""
import os
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")
from kubernetes_simulator_tpu.framework.framework import FrameworkConfig
from kubernetes_simulator_tpu.models.encode import encode
from kubernetes_simulator_tpu.sim.greedy import greedy_replay
from kubernetes_simulator_tpu.sim.jax_runtime import JaxReplayEngine
from kubernetes_simulator_tpu.sim.synthetic import make_cluster, make_workload


def run_fuzz(trials: int, master: int, quick: bool = False):
  """(cases, fails) over ``trials`` randomized parity cases.

  ``quick=True`` (round 6, the default-gate ``fuzz_quick`` slice) keeps
  the knob distribution but caps trace shapes at 40 nodes / 200 pods and
  skips the what-if sub-trial (it compiles its own program per trial) so
  a handful of trials fit a <=30s budget with the compile cache off.
  The quick lists are prefixes of the full ones, so quick mode explores
  the small-shape corner of the same seeded space."""
  rng = np.random.default_rng(master)
  fails = 0
  cases = 0
  for trial in range(trials):
      seed = int(rng.integers(10_000))
      n_nodes = int(rng.choice([15, 40] if quick else [15, 40, 90, 160]))
      n_pods = int(rng.choice([80, 200] if quick else [80, 200, 400]))
      kw = dict(
          with_affinity=bool(rng.random() < 0.7),
          with_spread=bool(rng.random() < 0.7),
          with_tolerations=bool(rng.random() < 0.7),
          gang_fraction=float(rng.choice([0.0, 0.1, 0.25])),
          gang_size=int(rng.choice([2, 3, 5])),
      )
      ext = None
      if rng.random() < 0.3:
          ext = ("google.com/tpu", 8, 0.3)
      cluster = make_cluster(n_nodes, seed=seed, taint_fraction=float(rng.choice([0.0, 0.2, 0.5])),
                             num_zones=int(rng.choice([2, 4, 8])),
                             extended_resources={"google.com/tpu": (8, 0.25)} if ext else None)
      # Durations → chunk-granular completions (default ON in the device
      # engines; anchor mirrors with completions_chunk_waves).
      dm = float(rng.choice([0.0, 2.0, 8.0]))
      pods, _ = make_workload(
          n_pods, seed=seed, extended_resource=ext,
          arrival_rate=float(rng.choice([20.0, 60.0])),
          duration_mean=dm or None, **kw,
      )
      ec, ep = encode(cluster, pods)
      preempt = bool(rng.random() < 0.4)
      dmax = int(rng.choice([0, 4, 128])) if not preempt else 128
      cfg = FrameworkConfig()
      wave_width = int(rng.choice([5, 8, 13]))
      if kw["gang_fraction"] and kw["gang_size"] > wave_width:
          wave_width = 8
      C = int(rng.choice([4, 16]))
      try:
          a = greedy_replay(ec, ep, cfg, wave_width=wave_width, preemption=preempt,
                            completions_chunk_waves=C if dm else None)
          # granularity_guard=False throughout: the harness pins parity at
          # the EXPLICIT (C, RB) — the guard would rewrite them inside the
          # engines but not in the greedy anchor (its C/RB are arguments).
          d = JaxReplayEngine(ec, ep, cfg, wave_width=wave_width, chunk_waves=C,
                              dmax_coarse=dmax, preemption=preempt,
                              granularity_guard=False).replay()
          if preempt:
              # Round 10: the fused tier-preemption program vs the
              # retained pre-fusion program — sampled (each variant
              # compiles its own program) and BIT-exact when it runs.
              if rng.random() < (1.0 if quick else 0.4):
                  from kubernetes_simulator_tpu.ops import tpu3 as V3

                  old_f = V3.FUSED_PREEMPT
                  V3.FUSED_PREEMPT = not old_f
                  try:
                      d_alt = JaxReplayEngine(
                          ec, ep, cfg, wave_width=wave_width, chunk_waves=C,
                          dmax_coarse=dmax, preemption=True,
                          granularity_guard=False).replay()
                  finally:
                      V3.FUSED_PREEMPT = old_f
                  assert (d_alt.assignments == d.assignments).all(), (
                      f"fused/prefusion mismatch trial={trial} seed={seed}")
                  assert d_alt.placed == d.placed
                  assert d_alt.preemptions == d.preemptions

      except ValueError as e:
          if "host" in str(e):  # preemption+host-rows guard
              continue
          raise
      cases += 1
      mism = int((a.assignments != d.assignments).sum())
      ok = mism == 0 and a.placed == d.placed and a.preemptions == d.preemptions
      if not ok:
          fails += 1
          print(f"FAIL trial={trial} seed={seed} nodes={n_nodes} pods={n_pods} "
                f"kw={kw} preempt={preempt} dmax={dmax} W={wave_width} C={C} dm={dm} "
                f"mism={mism} placed {a.placed} vs {d.placed} "
                f"evict {a.preemptions} vs {d.preemptions}")
      # Round 5: single-replay boundary pass — retry_buffer on
      # JaxReplayEngine and kube-exact minimal-victims preemption
      # (sim.boundary), vs the greedy anchor. Sampled: each sub-trial
      # compiles nothing new (the boundary mode reuses the plain chunk
      # program), so this is cheap.
      if dm and rng.random() < 0.5:
          RB = int(rng.choice([16, 64]))
          kube = bool(rng.random() < 0.6)
          pk = "kube" if kube else False
          cases += 1
          ak = greedy_replay(ec, ep, cfg, wave_width=wave_width,
                             preemption=pk, completions_chunk_waves=C,
                             retry_buffer=RB)
          dk = JaxReplayEngine(ec, ep, cfg, wave_width=wave_width,
                               chunk_waves=C, preemption=pk,
                               retry_buffer=RB,
                               granularity_guard=False).replay()
          okk = (
              (ak.assignments == dk.assignments).all()
              and ak.placed == dk.placed
              and ak.preemptions == dk.preemptions
              and ak.retry_dropped == dk.retry_dropped
          )
          if not okk:
              fails += 1
              mismk = int((ak.assignments != dk.assignments).sum())
              print(f"KUBE-FAIL trial={trial} seed={seed} kube={kube} "
                    f"RB={RB} C={C} W={wave_width} mism={mismk} "
                    f"placed {ak.placed} vs {dk.placed} "
                    f"evict {ak.preemptions} vs {dk.preemptions}")
      # Boundary retry: the what-if device path vs the anchor (round-4
      # widened envelope — affinity/spread count planes included; only
      # preemption and DynTables stay out). Sampled at 40% — each retry
      # sub-trial compiles its own what-if program.
      if dm and not preempt and rng.random() < 0.4 and not quick:
          from kubernetes_simulator_tpu.sim.whatif import Scenario, WhatIfEngine

          RB = int(rng.choice([8, 32]))
          try:
              wi = WhatIfEngine(ec, ep, [Scenario()], cfg,
                                wave_width=wave_width, chunk_waves=C,
                                retry_buffer=RB, granularity_guard=False)
          except ValueError as e:
              # Only the retry-envelope rejection may be skipped; any
              # other construction error must fail the fuzz loudly.
              if "retry_buffer requires" not in str(e):
                  raise
              wi = None
          if wi is not None:
              cases += 1
              ar = greedy_replay(ec, ep, cfg, wave_width=wave_width,
                                 completions_chunk_waves=C, retry_buffer=RB)
              wres = wi.run()
              if int(wres.placed[0]) != ar.placed:
                  fails += 1
                  print(f"RETRY-FAIL trial={trial} seed={seed} RB={RB} C={C} "
                        f"W={wave_width} placed {int(wres.placed[0])} vs {ar.placed}")
  return cases, fails


if __name__ == "__main__":
  trials = int(sys.argv[1]) if len(sys.argv) > 1 else 48
  master = int(sys.argv[2]) if len(sys.argv) > 2 else 123
  cases, fails = run_fuzz(trials, master)
  print(f"{cases} cases, {fails} failures")
  sys.exit(1 if fails else 0)
