"""backlog_retry_ms_per_boundary: device ms a chunk boundary under the
``ksim.retry`` scope of the chunk program: the retry pass's scan over the
queue (its wave steps, whatever their own stage) and the queue's upkeep
(the sort by priority, the record of the pass's binds)."""

from layer_metrics import _backlog


def read(ctx):
    return _backlog.retry_ms_per_boundary(ctx)
