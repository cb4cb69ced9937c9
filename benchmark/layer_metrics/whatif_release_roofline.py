"""whatif_release_roofline: the least time the release programs' bytes need
at the chip's HBM peak (roofline_whatif.release_min_ms, each execution at
its own width K) over the time they took, in %."""

import roofline_whatif
from layer_metrics import _whatif_release


def read(ctx):
    got = _whatif_release.runs(ctx)
    if not got:
        return None
    sh = ctx["shape"]
    least = sum(roofline_whatif.release_min_ms(
        ctx["device_kind"], scenarios=sh["scenarios_per_chip"],
        nodes=sh["nodes"], resources=sh["resources"], rows=k)
        for k, _ in got)
    return 100.0 * least / (sum(d for _, d in got) / 1e6)
