"""whatif_handback_ms_per_batch: from the end of the device's last
chunk-program execution in a traced batch to the batch's end, median over
batches: what the what-if ``run()`` does once the chunk loop is done (the
tail of ``device_wait``, counts and utilization to the host, then every
scenario's placements: the program's ``gather`` and ``handback`` phases),
as ``gather_ms_per_batch`` reads the replay's tail. Measured from the last
chunk program and not from the last program of any name, so that it holds
the whole hand-back whether its un-permute runs on the device or on the
host."""

import statistics

from layer_metrics import _batches


def read(ctx):
    got = _batches.heads_and_tails(ctx)
    return statistics.median(t for _, t in got) if got else None
