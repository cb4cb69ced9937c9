"""drain_evict_roofline: the least time a boundary's eviction needs at the
chip's HBM peak (``roofline_drain.evict_min_ms``: the placement buffer, the
record's node rows, the node mask, the planes the rewind touches, the
victims' rows, the queue and the log, once each) over
``drain_evict_ms_per_boundary``, in %. The victims a boundary are the
configuration's own record of its median plan (``measured`` in its file):
evictions a batch over the boundaries that evict."""

import roofline_drain
from layer_metrics import _drain


def read(ctx):
    took = _drain.ms_per_boundary(ctx)
    config = _drain.config_of(ctx, "drain_evict_roofline") if took else None
    if not config:
        return None
    sh, eng = ctx["shape"], config["engine"]
    measured = config["scenarios"]["measured"]
    victims = measured["evictionsMean"] / max(measured["boundariesThatEvict"], 1)
    least = roofline_drain.evict_min_ms(
        ctx["device_kind"], scenarios=sh["scenarios_per_chip"],
        nodes=sh["nodes"], resources=sh["resources"],
        tasks=int(measured["tasks"]), buffer=int(eng["retryBuffer"]),
        boundaries=int(measured["boundaries"]), victims=victims,
        planes=sh["planes"])
    return 100.0 * least / took
