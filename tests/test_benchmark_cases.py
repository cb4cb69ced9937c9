"""The benchmark's own cases (``benchmark/tests/test_*.py``) in the tier-1
run: a change to the program that breaks the benchmark's rehearsal path, its
controls or its trace reduction fails here and not first on the chip.

Nothing is copied: each module is imported from where it lies and its
``test_*`` functions are collected under ``<module>__<name>``, so that
two modules may use one name.
"""

import importlib
import sys
from pathlib import Path

import pytest

_DIR = Path(__file__).resolve().parents[1] / "benchmark" / "tests"
_MODULES = sorted(p.stem for p in _DIR.glob("test_*.py"))

pytest.register_assert_rewrite(*_MODULES)
sys.path.insert(0, str(_DIR))
for _mod in _MODULES:
    for _name, _fn in vars(importlib.import_module(_mod)).items():
        if _name.startswith("test_") and callable(_fn):
            globals()[f"{_mod}__{_name}"] = _fn
