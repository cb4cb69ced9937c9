"""gangq_retry_roofline: the least time a boundary's pass needs at the chip's
HBM peak (``roofline_gang_backlog.retry_min_ms``: the bytes of the wave steps
the passes EXECUTED, a pass's mean over the traced batch from the program's
``retry_pass_waves`` mark, with the transaction's plane beside ``used``, and
the queue's upkeep) over ``gangq_retry_ms_per_boundary``, in %."""

import roofline_gang_backlog
from layer_metrics import _drain, _gangq


def read(ctx):
    took = _gangq.pass_ms(ctx, "ksim.retry")
    waves = _gangq.pass_waves(ctx) if took else None
    config = _drain.config_of(ctx, "gangq_retry_roofline") if waves else None
    if not config:
        return None
    sh = ctx["shape"]
    least = roofline_gang_backlog.retry_min_ms(
        ctx["device_kind"], waves_per_pass=waves[0] / waves[1],
        scenarios=sh["scenarios_per_chip"], nodes=sh["nodes"],
        resources=sh["resources"], wave_width=sh["wave_width"],
        planes=sh["planes"], buffer=int(config["engine"]["retryBuffer"]),
        chunk_slots=sh["chunk_waves"] * sh["wave_width"])
    return 100.0 * least / took
