"""budget_evict_ms_per_boundary: device ms of the eviction program
(``jit_whatif_evict``) over its executions in the budgeted drain, one a chunk
boundary at which some plan may have a node failing, cordoned, draining or
due back: finding every live bind on a failing or cordoned node, the
admission against the budgets, the rewind of what leaves, the queue, the log
and the nodes' planes."""

from layer_metrics import _drain


def read(ctx):
    return _drain.ms_per_boundary(ctx)
