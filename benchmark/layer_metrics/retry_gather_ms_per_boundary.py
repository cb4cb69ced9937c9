"""retry_gather_ms_per_boundary: device ms a run of the retry pass program
(``jit_per_scenario_retry``, one a boundary) under ``ksim.retry/Gather``: the
queue's slots and their extra rows gathered by task id over ``rq.ids``
(``gather_slots_device`` / ``gather_extra_device`` at ``[S, RB]``), before the
pass's loop."""

from layer_metrics import _program_stages

MODULE = "jit_per_scenario_retry"


def read(ctx):
    return _program_stages.ms_per_run(ctx, MODULE, "ksim.retry/Gather")
