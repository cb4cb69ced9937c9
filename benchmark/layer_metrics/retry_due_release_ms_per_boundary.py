"""retry_due_release_ms_per_boundary: device ms a run of the retry pass
program under ``ksim.release``: the releases of re-tried binds that are due,
a loop over the record's earlier rows, each through the release core. The
static lists' programs (``jit_whatif_release_k<K>``), which the accepted
``*_release_ms_per_boundary`` add to it, are NOT in it."""

from layer_metrics import _program_stages
from layer_metrics.retry_gather_ms_per_boundary import MODULE


def read(ctx):
    return _program_stages.ms_per_run(ctx, MODULE, "ksim.release")
