"""retry_record_ms_per_boundary: device ms a run of the retry pass program
under ``ksim.retry/Record``: everything after the pass's loop: the release
boundary of each bind, the pass's row put into the record (task, node,
boundary, the rows a release rewinds, read by task id), the eviction
counters, a re-bind's allowance given back."""

from layer_metrics import _program_stages
from layer_metrics.retry_gather_ms_per_boundary import MODULE


def read(ctx):
    return _program_stages.ms_per_run(ctx, MODULE, "ksim.retry/Record")
