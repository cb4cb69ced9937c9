"""host_dispatch_ms_per_batch: the sum of a traced batch's ``dispatch``
spans, in ms, median over batches: the host time the chunk loop spends
handing chunk programs to the device (each span holds that chunk's
``chunk:<i>`` marker). None where the tree writes no root span."""

from layer_metrics import _program_spans


def read(ctx):
    return _program_spans.ms_per_batch(ctx, "dispatch")
