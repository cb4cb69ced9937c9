"""drain_release_ms_per_boundary: what ``backlog_release_ms_per_boundary`` reads, in the drained Borg cell, under a name of its own because
the accepted metric lists its cells and cannot be edited."""

from layer_metrics.backlog_release_ms_per_boundary import read  # noqa: F401
