"""gangq_arrival_txn_ms_per_wave: device ms an ARRIVAL wave in the carried
transaction of the jobs wider than the wave, in a batch under
``retry_groups``: the arrival program's (``jit_per_scenario_arrivals``, one a
boundary) ``ksim.gang_txn`` and ``ksim.gang_rollback`` outside the upkeep,
over the chunk's waves: ``gang_txn_ms_per_wave``'s quantity, by a run of ONE
named module (that reader divides by the runs of every chunk program)."""

from layer_metrics import _gangq, _program_stages

TXN = ("ksim.gang_txn", "ksim.gang_rollback")


def read(ctx):
    if not _gangq.under_groups(ctx):
        return None
    ms = [_program_stages.ms_per_run(ctx, _gangq.ARRIVALS, s) for s in TXN]
    waves = ctx["shape"].get("chunk_waves")
    if ms[0] is None or not waves:
        return None
    return sum(m or 0.0 for m in ms) / waves
