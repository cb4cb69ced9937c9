"""mesh_handback_ms_per_batch: from the end of the last chunk-program
execution on the last chip of the mesh to finish, to the traced batch's end,
median over batches: what a meshed what-if ``run()`` does once every chip's
chunk loop is done (the tail of ``device_wait``, utilization to the host,
``jit_whatif_handback`` on every chip, then the fetch of the four shards and
their stringing together on the host: the program's ``gather`` and
``handback`` phases, the ``mesh_fetch`` span inside the latter)."""

import statistics

from layer_metrics import _mesh


def read(ctx):
    got = _mesh.batches(ctx)
    if not got:
        return None
    return statistics.median((b["end"] - max(b["last"])) / 1e6 for b in got)
