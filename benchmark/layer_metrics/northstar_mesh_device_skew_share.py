"""northstar_mesh_device_skew_share: what ``mesh_device_skew_share`` reads, in the four-chip north-star cell, under a name of its own because
the accepted metric lists its cells and cannot be edited."""

from layer_metrics.mesh_device_skew_share import read  # noqa: F401
