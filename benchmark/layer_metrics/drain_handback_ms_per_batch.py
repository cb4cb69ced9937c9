"""drain_handback_ms_per_batch: the program's ``handback`` span of the traced
batch in the drained Borg cell, in ms: three answers (every task's node, the
boundary of its last bind, the eviction log) put into order on the device and
copied. What ``host_handback_ms_per_batch`` reads, over the whole traced batch
(``_drain.whole``: the device's trace buffer ends the window inside it)."""

from layer_metrics import _drain, host_handback_ms_per_batch


def read(ctx):
    return host_handback_ms_per_batch.read(_drain.whole(ctx))
