"""host_handback_ms_per_batch: the program's ``handback`` span of a traced
what-if batch, in ms, median over batches: the placements un-permuted on
the device and copied to the host (under a mesh: gathered, then fetched
from one chip). The device-side twins (``whatif_handback_ms_per_batch``,
``whatif_arrivals_handback_ms_per_batch``, ``mesh_handback_ms_per_batch``)
also hold the tail of ``device_wait`` and ``gather``. None where the tree
writes no root span or the batch hands nothing back."""

from layer_metrics import _program_spans


def read(ctx):
    return _program_spans.ms_per_batch(ctx, "handback")
