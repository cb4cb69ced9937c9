"""chip_smoke.py refuses to run off the chip, and the compile cache is
placed from outside (JAX_COMPILATION_CACHE_DIR) or at the fixed
in-checkout path — the two contracts a CPU run can pin. What the smoke
checks ON the chip is not testable here by design."""

import json
import os
import subprocess
import sys
import time
from pathlib import Path

import jax

from kubernetes_simulator_tpu.utils import compile_cache as cc

REPO = Path(__file__).resolve().parent.parent


def _run(args, **env):
    return subprocess.run(
        [sys.executable, *args], cwd=REPO, capture_output=True, text=True,
        timeout=120, env={**os.environ, **env},
    )


def test_smoke_refuses_without_a_tpu():
    t0 = time.monotonic()
    out = _run(["chip_smoke.py"], JAX_PLATFORMS="cpu")
    assert out.returncode != 0
    assert time.monotonic() - t0 < 60  # before any heavy work
    assert "no TPU" in out.stderr and "JAX_PLATFORMS='cpu'" in out.stderr
    assert out.stdout == ""  # no result line


def test_result_line_has_exactly_the_contract_keys():
    """The driver refuses a last line with any key beyond ok and
    device{platform, kind, count}; the rich report goes on its own line."""
    sys.path.insert(0, str(REPO))
    try:
        import chip_smoke
    finally:
        sys.path.remove(str(REPO))
    report = {
        "ok": True,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
        "versions": {}, "phases": {}, "claim": None,
    }
    line = chip_smoke.result_line(report)
    assert "\n" not in line
    assert json.loads(line) == {
        "ok": True,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
    }


def test_cache_dir_from_environment_is_left_to_jax(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, enable() sets no directory in
    code: JAX's own reading of the variable stands, and neither the
    in-checkout cache nor the old ~/.cache/ksim_tpu_xla appears."""
    home = tmp_path / "home"
    home.mkdir()
    code = (
        "import jax\n"
        "from kubernetes_simulator_tpu.utils import compile_cache as cc\n"
        "cc.IN_CHECKOUT_DIR = cc.IN_CHECKOUT_DIR.with_name('.jax_cache_must_not_exist')\n"
        "before = jax.config.jax_compilation_cache_dir\n"
        "got = cc.enable()\n"
        "assert got == before == jax.config.jax_compilation_cache_dir, (got, before)\n"
        "assert not cc.IN_CHECKOUT_DIR.exists()\n"
        "assert jax.config.jax_persistent_cache_min_compile_time_secs == 1.0\n"
        "print(got)\n"
    )
    out = _run(
        ["-c", code], JAX_COMPILATION_CACHE_DIR=str(tmp_path / "outside"),
        KSIM_COMPILE_CACHE="1", HOME=str(home),
    )
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == str(tmp_path / "outside")
    assert not (home / ".cache" / "ksim_tpu_xla").exists()


def test_cache_dir_defaults_to_fixed_in_checkout_path(tmp_path, monkeypatch):
    assert cc.IN_CHECKOUT_DIR == REPO / ".jax_cache"
    assert ".jax_cache/" in (REPO / ".gitignore").read_text().split()
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.delenv("KSIM_COMPILE_CACHE", raising=False)
    assert cc.enable() is None  # CPU backend: default-off stays
    monkeypatch.setenv("KSIM_COMPILE_CACHE", "1")
    monkeypatch.setattr(cc, "IN_CHECKOUT_DIR", tmp_path / ".jax_cache")
    names = (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes",
    )
    saved = {n: getattr(jax.config, n) for n in names}
    try:
        assert cc.enable() == str(tmp_path / ".jax_cache")
        assert (tmp_path / ".jax_cache").is_dir()
        assert cc.enable() == str(tmp_path / ".jax_cache")  # idempotent
    finally:
        # The CPU cache is unsound for this suite (compile_cache docstring).
        for n, v in saved.items():
            jax.config.update(n, v)
