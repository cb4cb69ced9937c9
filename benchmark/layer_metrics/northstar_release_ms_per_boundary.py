"""northstar_release_ms_per_boundary: what ``whatif_release_ms_per_boundary`` reads, in the four-chip north-star cell (device 0's release program under shard_map), under a name of its own because
the accepted metric lists its cells and cannot be edited."""

from layer_metrics.whatif_release_ms_per_boundary import read  # noqa: F401
