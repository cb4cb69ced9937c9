"""gangq_join_ms_per_boundary: device ms a run of the arrival program
(``jit_per_scenario_arrivals``, one a boundary) under ``ksim.retry/Join``: the
chunk's rolled-back JOBS joining the queue whole (the verdicts read, applied
to the placement buffer before any release reads it, and the loop that drops
the first job the buffer has no room for)."""

from layer_metrics import _gangq, _program_stages


def read(ctx):
    return _program_stages.ms_per_run(ctx, _gangq.ARRIVALS, "ksim.retry/Join")
