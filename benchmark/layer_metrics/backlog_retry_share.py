"""backlog_retry_share: the ``ksim.retry`` scope's device time as a share of
the chunk program's, in %: how much of a chunk call the standing queue
costs beside the arrival waves."""

from layer_metrics import _backlog
from layer_metrics.chunk_ms_per_wave import CHUNK_PROGRAM


def read(ctx):
    got = _backlog.seconds(ctx, _backlog.RETRY)
    total = ctx["trace"].program_seconds(CHUNK_PROGRAM)
    if not got or not got[0] or not total:
        return None
    return 100.0 * got[0] / total
