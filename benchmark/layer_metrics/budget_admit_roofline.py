"""budget_admit_roofline: the least time a boundary's admission needs at the
chip's HBM peak (``roofline_budget_drain.admit_min_ms``: the candidate list
and the per-application counters read and written once) over
``budget_admit_ms_per_boundary``, in %. The candidates a boundary are the
configuration's own record (``scenarios.measured`` in its file: candidate
turns a batch over the boundaries that evict); nothing where the record is
not filled."""

import roofline_budget_drain
from layer_metrics import _budget, _drain


def read(ctx):
    got = _budget.admit_seconds(ctx)
    config = _drain.config_of(ctx, "budget_admit_roofline") if got else None
    if not config or not got[0]:
        return None
    measured = config["scenarios"]["measured"]
    if "candidateTurnsMean" not in measured:
        return None
    turns = measured["candidateTurnsMean"] / max(measured["boundariesThatEvict"], 1)
    least = roofline_budget_drain.admit_min_ms(
        ctx["device_kind"], scenarios=ctx["shape"]["scenarios_per_chip"],
        candidates=turns, apps=config["workload"]["numApps"])
    return 100.0 * least / (1e3 * got[0] / got[1])
