"""evict_write_ms_per_boundary: device ms a run of the eviction program under
``ksim.evict/Write``: the binds cleared where they stand in the placement
buffer and in the record (under budgets the scatter of the admitted), ``owed``,
``used`` zeroed on a node that left and ``down`` (the ``[N, L]`` membership
compares), the log behind its cursor, the counters."""

from layer_metrics import _program_stages
from layer_metrics.evict_search_ms_per_boundary import MODULE


def read(ctx):
    return _program_stages.ms_per_run(ctx, MODULE, "ksim.evict/Write")
