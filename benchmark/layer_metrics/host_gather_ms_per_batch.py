"""host_gather_ms_per_batch: the program's ``gather`` span of a traced
batch, in ms, median over batches: from the end of ``device_wait`` to the
result (a replay: choices and state to the host, gauges; a what-if: counts
and utilization, up to the placements' hand-back). ``gather_ms_per_batch``
times the device's side of it, which also holds the tail of
``device_wait``. None where the tree writes no root span."""

from layer_metrics import _program_spans


def read(ctx):
    return _program_spans.ms_per_batch(ctx, "gather")
