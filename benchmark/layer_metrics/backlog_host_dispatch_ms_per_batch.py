"""backlog_host_dispatch_ms_per_batch: what ``host_dispatch_ms_per_batch`` reads from the
program's host spans, in the full Borg cell, under a name of its own because
the accepted metric lists its cells and cannot be edited (_program_spans.py)."""

from layer_metrics.host_dispatch_ms_per_batch import read  # noqa: F401
