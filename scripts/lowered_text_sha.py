#!/usr/bin/env python3
"""sha256 of ``Lowered.as_text()`` of every chunk, pass, release and eviction
program a cell's rehearsal dispatches, one JSON line a cell: run from the
root of two checkouts, the lines have to agree where a change claims to leave
a configuration's programs as they were.

    JAX_PLATFORMS=cpu python3 scripts/lowered_text_sha.py <cell> [<cell> ...]

The rehearsal runs armed (``KSIM_PROFILE_DIR``), so the engine registers its
programs with ``utils.profiling``; nothing is timed."""

import hashlib
import json
import os
import sys
import tempfile
from pathlib import Path

ROOT = Path.cwd()
sys.path[:0] = [str(ROOT), str(ROOT / "benchmark")]
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import run  # noqa: E402

from kubernetes_simulator_tpu.utils import profiling  # noqa: E402


def main() -> int:
    for cell in sys.argv[1:]:
        _, _, config, traffic = run.load_cell(cell)
        profiling._PROGRAMS.clear()
        with tempfile.TemporaryDirectory() as d:
            os.environ["KSIM_PROFILE_DIR"] = d
            try:
                _, _, engine = run.prepare(config, traffic, 7, True, {})
                engine.batch()
            finally:
                os.environ.pop("KSIM_PROFILE_DIR", None)
        print(json.dumps({"cell": cell, "programs": {
            name: hashlib.sha256(lower().as_text().encode()).hexdigest()[:16]
            for name, lower in sorted(profiling._PROGRAMS.items())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
