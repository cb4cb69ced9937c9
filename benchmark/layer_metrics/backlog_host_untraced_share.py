"""backlog_host_untraced_share: what ``host_untraced_share`` reads from the
program's host spans, in the full Borg cell, under a name of its own because
the accepted metric lists its cells and cannot be edited (_program_spans.py)."""

from layer_metrics.host_untraced_share import read  # noqa: F401
