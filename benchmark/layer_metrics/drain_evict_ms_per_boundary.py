"""drain_evict_ms_per_boundary: device ms of the eviction program
(``jit_whatif_evict``) over its executions, one a chunk boundary at which
some plan moves a node: finding every bind on a leaving node in the
placement buffer and the record, rewinding its usage and counts, clearing
it, and joining the evicted to the queue and the log."""

from layer_metrics import _drain


def read(ctx):
    return _drain.ms_per_boundary(ctx)
