"""budget_admit_ms_per_boundary: device ms under the admission's scope
(``ksim.evict/Budget``) over the eviction program's executions in the window:
the candidates' applications, the running count against ``maxUnavailable -
down``, the cut to the admitted, what each node held and lost, ``unavail``."""

from layer_metrics import _budget


def read(ctx):
    got = _budget.admit_seconds(ctx)
    return 1e3 * got[0] / got[1] if got and got[1] else None
