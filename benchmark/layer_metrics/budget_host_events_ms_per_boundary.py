"""budget_host_events_ms_per_boundary: the program's ``host_events`` span, ms
a span, over the traced batch of the budgeted drain: a boundary picks its
three staged arrays (the node list, each entry's kind, the ``node_up`` s) and
builds the eviction program's arguments."""

from layer_metrics.drain_host_events_ms_per_boundary import read  # noqa: F401
