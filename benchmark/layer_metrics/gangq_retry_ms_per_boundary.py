"""gangq_retry_ms_per_boundary: device ms a run of the pass program
(``jit_per_scenario_retry``, one a boundary) under ``ksim.retry`` in a batch
under ``retry_groups``: the one gather by task id, the queue's job layout,
every executed pass wave (its tile, its step, its job's verdict) and the
record."""

from layer_metrics import _gangq


def read(ctx):
    return _gangq.pass_ms(ctx, "ksim.retry")
