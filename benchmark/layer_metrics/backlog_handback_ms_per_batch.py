"""backlog_handback_ms_per_batch: the program's ``handback`` span of a traced
batch on the device retry path, in ms, median over batches: two arrays, every
task's node and the boundary that bound it, put into task order on the
device, copied, and the re-tried binds written in from the queue's record on
the host. What ``host_handback_ms_per_batch`` reads in its cells, under a
name of this cell's own because the accepted metric lists its cells and
cannot be edited (_program_spans.py)."""

from layer_metrics.host_handback_ms_per_batch import read  # noqa: F401
