"""evict_sort_ms_per_boundary: device ms a run of the eviction program under
``ksim.evict/Sort``: the ``E`` candidates sorted into
``BoundaryOps.evict_node``'s order (under budgets the forced before the
asking, whether an entry is forced a dense select over ``[E, L]``), each
one's node and the boundary it was bound at."""

from layer_metrics import _program_stages
from layer_metrics.evict_search_ms_per_boundary import MODULE


def read(ctx):
    return _program_stages.ms_per_run(ctx, MODULE, "ksim.evict/Sort")
