"""Test env: force JAX onto CPU with 8 virtual devices, so mesh/sharding
tests run without TPUs (SURVEY.md §4.4).

Both the environment and ``jax.config`` are set: the config update holds
even where jax was imported before this file (the backend is created
lazily at first use, after conftest import), and XLA_FLAGS is read at
backend-creation time, so setting it here works.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax

jax.config.update("jax_platforms", "cpu")

# Persistent XLA compilation cache for the suite — a no-op on the CPU
# backend since round 6: warm-cache chunk executables deserialized
# nondeterministically wrong (see utils/compile_cache.py docstring), and
# every test here runs on CPU. enable() stays so a TPU-backed run of the
# suite still gets the warm start; KSIM_COMPILE_CACHE=1 forces it on CPU.
from kubernetes_simulator_tpu.utils.compile_cache import enable as _cc

if _cc() is not None:
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.3)


import pytest


@pytest.fixture
def fork_at_start(tmp_path):
    """``make(ec, ep) -> path``: a fork checkpoint taken before the first
    chunk. Nothing is skipped, and a fork is a structural cause that keeps
    a what-if batch on the host pending-fold path (asking for placements
    no longer does)."""
    import numpy as np

    from kubernetes_simulator_tpu.models.state import init_state
    from kubernetes_simulator_tpu.sim.checkpoint import ReplayCheckpoint

    def make(ec, ep) -> str:
        h = init_state(ec, ep)
        path = str(tmp_path / "fork_at_start.npz")
        ReplayCheckpoint(
            chunk_cursor=0, used=h.used, match_count=h.match_count,
            anti_active=h.anti_active, pref_wsum=h.pref_wsum, outs=[],
            released=np.zeros(ep.num_pods, bool),
        ).save(path)
        return path

    return make
