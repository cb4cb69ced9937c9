"""mesh_device_skew_share: the latest less the earliest end, over the chips
of the mesh, of a traced batch's last chunk-program execution, over the
batch's duration, in %, median over batches: what the slowest chip costs.
The scenarios are dealt to the chips in blocks and every chip runs the same
program over the same waves, so what differs is when each was started and
what its own scenarios make the program do."""

import statistics

from layer_metrics import _mesh


def read(ctx):
    got = _mesh.batches(ctx)
    if not got:
        return None
    return statistics.median(
        100.0 * (max(b["last"]) - min(b["last"])) / (b["end"] - b["start"])
        for b in got)
