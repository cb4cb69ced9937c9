"""mesh_stage_ms_per_batch: from the start of a traced batch to the first
chunk-program execution on the LAST chip of the mesh to start one, median
over batches: what a meshed what-if ``run()`` does before every chip works
(the program's ``stage`` phase: the state stack built sharded over the chips,
the scenario-shared tables and chunk indices replicated, its ``mesh_put``
span, plus the first dispatch). ``stage_ms_per_batch``'s stretch, read on
the chip that waits longest."""

import statistics

from layer_metrics import _mesh


def read(ctx):
    got = _mesh.batches(ctx)
    if not got:
        return None
    return statistics.median((max(b["first"]) - b["start"]) / 1e6 for b in got)
