"""stage_ms_per_batch: from the start of a traced batch to the device's
first chunk-program execution in it, median over batches: what the
program's ``stage`` span covers (index uploads, state build, pager set-up)
plus the first dispatch."""

import statistics

from layer_metrics import _batches


def read(ctx):
    got = _batches.heads_and_tails(ctx)
    return statistics.median(h for h, _ in got) if got else None
