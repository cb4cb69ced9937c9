"""evict_unscoped_share: of the eviction program's op time under any ``ksim.``
scope, the share under bare ``ksim.evict``, in %: what no sub-scope
(``utils.profiling.SUB_STAGES``) names. A guard on the sub-scopes' coverage:
it moves no end-to-end metric itself, and it rises when work is added to the
program outside them. Op time under NO scope (copies the compiler adds carry
no ``op_name``) is printed beside it on stderr and is in neither term."""

from layer_metrics import _program_stages
from layer_metrics.evict_search_ms_per_boundary import MODULE

STAGE = "ksim.evict"


def read(ctx):
    got = _program_stages.read(ctx, MODULE)
    if not got:
        return None
    seconds = got["seconds"]
    scoped = sum(v for p, v in seconds.items() if p)
    if not scoped:
        return None
    _program_stages.say(
        f"{MODULE}: {1e3 * seconds.get('', 0.0) / got['runs']:.3f} ms a run "
        f"under no scope, {1e3 * scoped / got['runs']:.3f} under a scope")
    return 100 * seconds.get(STAGE, 0.0) / scoped
