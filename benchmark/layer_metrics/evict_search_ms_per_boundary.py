"""evict_search_ms_per_boundary: device ms a run of the eviction program
(``jit_whatif_evict``, one a boundary it runs at) under ``ksim.evict/Search``:
the one candidate search of both eviction programs (``sim.whatif.evict_search``:
the compare pass over the places, the blocks' counts and offsets, a slot's two
gathers, its list entry)."""

from layer_metrics import _program_stages

MODULE = "jit_whatif_evict"


def read(ctx):
    return _program_stages.ms_per_run(ctx, MODULE, "ksim.evict/Search")
