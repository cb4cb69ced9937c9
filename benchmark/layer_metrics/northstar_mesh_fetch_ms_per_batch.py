"""northstar_mesh_fetch_ms_per_batch: what ``mesh_fetch_ms_per_batch`` reads, in the four-chip north-star cell, under a name of its own because
the accepted metric lists its cells and cannot be edited."""

from layer_metrics.mesh_fetch_ms_per_batch import read  # noqa: F401
