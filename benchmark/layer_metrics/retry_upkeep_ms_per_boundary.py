"""retry_upkeep_ms_per_boundary: device ms a run of the arrival program
(``jit_per_scenario_arrivals``, one a boundary) under ``ksim.retry``: the
queue's upkeep alone (the chunk's failures joined behind what the pass left,
the ONE stable sort by priority over ``RB + C * W`` rows, the cut); the
arrival waves are under no pass. Told from the pass's ``ksim.retry`` by the
MODULE's name."""

from layer_metrics import _program_stages

MODULE = "jit_per_scenario_arrivals"


def read(ctx):
    return _program_stages.ms_per_run(ctx, MODULE, "ksim.retry")
