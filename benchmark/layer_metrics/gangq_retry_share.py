"""gangq_retry_share: the pass program's ``ksim.retry`` device time as a share
of the device time of a boundary's two loop programs (the pass and the
arrival scan with the upkeep), in %: how much of a boundary the standing job
queue costs beside the arrival waves."""

from layer_metrics import _gangq, _program_stages


def read(ctx):
    took = _gangq.pass_ms(ctx, "ksim.retry")
    both = [_program_stages.read(ctx, m) for m in (_gangq.PASS, _gangq.ARRIVALS)]
    if took is None or not all(both):
        return None
    total = sum(1e3 * g["op_seconds"] / g["runs"] for g in both)
    return 100.0 * took / total if total else None
