"""gangq_due_release_ms_per_boundary: device ms a run of the pass program
(``jit_per_scenario_retry``, one a boundary) under ``ksim.release``, in a
batch under ``retry_groups``: the due releases of re-tried JOBS alone, a loop
over the blocks of the record's log that hold a bind (as many as the fullest
scenario's passes have filled), each through the release core. The static
lists' programs, which ``gangq_release_ms_per_boundary`` adds, are NOT in it."""

from layer_metrics import _gangq


def read(ctx):
    return _gangq.pass_ms(ctx, "ksim.release")
