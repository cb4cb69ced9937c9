"""Persistent XLA compilation cache for every entry point.

The chunk programs cost minutes of XLA compile per shape; a process
restart with the SAME shapes should pay seconds. ``enable()`` turns on
JAX's persistent compilation cache so compiled executables survive across
processes — every config change still compiles once, but only once per
cache directory. ``cli.main``, ``chip_smoke.py`` and ``benchmark/run.py``
call the one ``enable()``.

Where the cache lives is decided from outside: when
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this module
sets NO directory in code (it only lowers the persistence thresholds).
When it is not set, the directory is ``<checkout>/.jax_cache`` — a fixed,
git-ignored path, never derived from a temp name, pid or time (a
directory that moves never hits). Opt out with ``KSIM_COMPILE_CACHE=0``.
Entries below 1 s of compile time are not persisted (the cache is for
the chunk programs, not every tiny jit).

CPU backend (round 6): the cache is OFF by default. On jax 0.4.x the
thunk-runtime CPU executables did not survive the persistent-cache
round-trip — warm-cache replays of the chunk programs returned
nondeterministic placements (the preemption program most visibly),
out-of-bounds node ids and occasional segfaults, while every cold
compile of the same program was correct. Not re-verified on jax 0.9.0;
until it is, correctness wins over warm-start time on CPU.
``KSIM_COMPILE_CACHE=1`` forces it on for local experiments.

Concurrent DCN workers (round 11): N processes on one machine share the
cache directory, and ``LRUCache.put`` (still so in jax 0.9.0) writes
entries with a bare ``write_bytes`` — no lock when eviction is off (the
default) — so a reader can observe a half-written executable.
``enable()`` therefore patches the put path to write a per-process temp
file and ``os.replace`` it into place (atomic on POSIX): concurrent
writers of the same content-addressed key each land a complete file,
last rename wins with identical bytes. Ordering stays as documented:
``enable()`` must run BEFORE ``jax.distributed.initialize``
(parallel.dcn.maybe_init_from_env does this by construction; pinned by
tests/test_dcn_units.py).

Nothing here is swallowed: a cache that was asked for and cannot be set
up raises, so a run on the chip never silently pays a cold compile.
"""

from __future__ import annotations

import importlib.util
import os
import time
from pathlib import Path

#: Fixed in-checkout cache directory, used when JAX_COMPILATION_CACHE_DIR
#: is not set. Listed in .gitignore.
IN_CHECKOUT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"
_atomic_patched = False


def patch_atomic_writes() -> None:
    """Replace ``jax._src.lru_cache.LRUCache.put``'s unlocked
    ``write_bytes`` with temp-then-``os.replace`` so concurrent DCN
    workers sharing one cache directory never expose partial entries.
    Idempotent."""
    global _atomic_patched
    if _atomic_patched:
        return
    from jax._src import lru_cache as _lru

    suffix_c = _lru._CACHE_SUFFIX
    suffix_a = _lru._ATIME_SUFFIX
    orig_put = _lru.LRUCache.put

    def _atomic_put(self, key, val):
        if self.eviction_enabled:
            # The eviction path serializes through a file lock
            # upstream — keep it.
            return orig_put(self, key, val)
        if not key:
            raise ValueError("key cannot be empty")
        cache_path = self.path / f"{key}{suffix_c}"
        if cache_path.exists():
            return
        tmp = self.path / f"{key}.tmp.{os.getpid()}"
        tmp.write_bytes(val)
        os.replace(str(tmp), str(cache_path))
        (self.path / f"{key}{suffix_a}").write_bytes(
            time.time_ns().to_bytes(8, "little")
        )

    _lru.LRUCache.put = _atomic_put
    _atomic_patched = True


def _cpu_backend_expected() -> bool:
    """True when this process will run on the CPU backend. Must NOT
    initialize the backend (enable() runs before
    jax.distributed.initialize in the DCN workers), so it reads
    config/env and probes for the TPU plugin instead of asking the
    runtime."""
    import jax

    plats = os.environ.get("JAX_PLATFORMS") or jax.config.jax_platforms or ""
    first = plats.split(",")[0].strip().lower()
    if first == "cpu":
        return True
    return first == "" and importlib.util.find_spec("libtpu") is None


def enable() -> str | None:
    """Idempotently enable the persistent compilation cache. Returns the
    directory JAX is configured with, or None when the cache is off
    (``KSIM_COMPILE_CACHE=0``, or the CPU backend without
    ``KSIM_COMPILE_CACHE=1`` — see module docstring)."""
    raw = os.environ.get("KSIM_COMPILE_CACHE")
    if raw in ("", "0"):
        return None
    if raw != "1" and _cpu_backend_expected():
        return None
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        IN_CHECKOUT_DIR.mkdir(exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", str(IN_CHECKOUT_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    # Persist regardless of entry size (the default gates on bytes).
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    patch_atomic_writes()
    return jax.config.jax_compilation_cache_dir
