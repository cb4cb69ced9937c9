"""backlog_idle_unattributed_share: what ``idle_unattributed_share`` reads from the
program's host spans, in the full Borg cell, under a name of its own because
the accepted metric lists its cells and cannot be edited (_program_spans.py)."""

from layer_metrics.idle_unattributed_share import read  # noqa: F401
