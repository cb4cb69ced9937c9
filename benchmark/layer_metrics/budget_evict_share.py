"""budget_evict_share: the eviction program's device time as a share of all
the device was busy in the traced window, in %, in the budgeted drain."""

from layer_metrics.drain_evict_share import read  # noqa: F401
