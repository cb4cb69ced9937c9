"""gangq_txn_ms_per_pass_wave: device ms an EXECUTED pass wave in what the
job transaction adds to a wave step of the pass: ``ksim.retry/Close`` (the
wave's tile read at the loop's cursor, the lanes past the job's end blanked,
the picks written, a rolled-back wide job's members cleared) and the step's
own ``ksim.gang_txn`` / ``ksim.gang_rollback`` inside the pass. Over the
passes' executed waves a run (the ``retry_pass_waves`` mark), not the
buffer's."""

from layer_metrics import _gangq, _program_stages

TXN = ("ksim.retry/Close", "ksim.retry/ksim.gang_txn",
       "ksim.retry/ksim.gang_rollback")


def read(ctx):
    if not _gangq.under_groups(ctx):
        return None
    waves = _gangq.pass_waves(ctx)
    ms = [_program_stages.ms_per_run(ctx, _gangq.PASS, s) for s in TXN]
    if not waves or not waves[0] or ms[0] is None:
        return None
    return sum(m or 0.0 for m in ms) * waves[1] / waves[0]
