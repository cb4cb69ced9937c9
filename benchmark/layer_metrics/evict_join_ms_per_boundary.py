"""evict_join_ms_per_boundary: device ms a run of the eviction program under
``ksim.evict/Join``: the leaving non-gang tasks joined to the retry queue as
far as there is room, the one stable sort by priority over ``RB + E`` rows,
the cut to the buffer."""

from layer_metrics import _program_stages
from layer_metrics.evict_search_ms_per_boundary import MODULE


def read(ctx):
    return _program_stages.ms_per_run(ctx, MODULE, "ksim.evict/Join")
