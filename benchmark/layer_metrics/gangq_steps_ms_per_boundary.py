"""gangq_steps_ms_per_boundary: device ms a run of the pass program under
``ksim.retry/ksim.<stage>``, in a batch under ``retry_groups``: the pass's
own wave steps (the NORMAL wave step over the queue's jobs, each from a fresh
wave, as many waves as the fullest scenario has queued), whatever their
stage, the step's ``ksim.gang_txn`` / ``ksim.gang_rollback`` among them."""

from layer_metrics import _gangq, _program_stages


def read(ctx):
    if not _gangq.under_groups(ctx):
        return None
    return _program_stages.ms_per_run(
        ctx, _gangq.PASS, "ksim.retry/ksim.", under=str.startswith)
