"""whatif_arrivals_handback_ms_per_batch: from the end of the device's last
chunk-program execution in a traced batch to the batch's end, median over
batches: what an arrivals-only what-if ``run()`` does once the chunk loop is
done (the tail of ``device_wait``, utilization to the host, then every
scenario's placements: the program's ``gather`` and ``handback`` phases). The
same stretch ``whatif_handback_ms_per_batch`` reads in the device-release
cell, under a name of its own because what fills it is another path: the
chunks' choices put into task order, on the device or on the host."""

from layer_metrics.whatif_handback_ms_per_batch import read  # noqa: F401
