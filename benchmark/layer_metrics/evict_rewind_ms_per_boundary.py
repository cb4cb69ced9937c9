"""evict_rewind_ms_per_boundary: device ms a run of the eviction program under
``ksim.evict/Rewind``: the leaving tasks' rows read by task at ``E`` (``Ea``
under budgets), dealt over the blocks of the list, and the release core that
takes their usage and counts back."""

from layer_metrics import _program_stages
from layer_metrics.evict_search_ms_per_boundary import MODULE


def read(ctx):
    return _program_stages.ms_per_run(ctx, MODULE, "ksim.evict/Rewind")
