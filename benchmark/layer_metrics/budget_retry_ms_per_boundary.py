"""budget_retry_ms_per_boundary: device ms of the retry pass program
(``jit_per_scenario_retry``) over its executions in the window, one a
boundary, in the budgeted drain: the pass re-binds what the budgets let go,
in smaller and later portions than the drain cell's, and gives each re-bind's
allowance back."""

from layer_metrics import _budget


def read(ctx):
    return _budget.retry_ms_per_boundary(ctx)
