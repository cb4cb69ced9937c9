"""gang_handback_ms_per_batch: the program's ``handback`` span of a traced
batch of the gang-scheduled training cell, in ms, median over batches: the
placements un-permuted on the device, every member of a rolled-back wide pod
group handed back unplaced through the group's verdict there, and the copy
to the host. (``host_handback_ms_per_batch`` reads the same span in the cells
it lists.) None where the tree writes no root span."""

from layer_metrics import _program_spans


def read(ctx):
    return _program_spans.ms_per_batch(ctx, "handback")
