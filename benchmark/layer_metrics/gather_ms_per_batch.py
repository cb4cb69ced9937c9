"""gather_ms_per_batch: from the end of the device's last chunk-program
execution in a traced batch to the batch's end, median over batches: what
the program's ``gather`` span covers (choices to the host, state to the
host, gauges) plus the tail of ``device_wait``."""

import statistics

from layer_metrics import _batches


def read(ctx):
    got = _batches.heads_and_tails(ctx)
    return statistics.median(t for _, t in got) if got else None
