"""gangq_handback_ms_per_batch: the program's ``handback`` span of the traced
batch of the job-queue cell, in ms: two answers (every pod's node, the
boundary that bound its job) put into task order on the device with the
queue's record merged in, and copied. What ``host_handback_ms_per_batch``
reads, over the whole traced batch (``_drain.whole``)."""

from layer_metrics import _drain, _gangq, host_handback_ms_per_batch


def read(ctx):
    if _gangq.pass_waves(ctx) is None:  # no batch under retry_groups
        return None
    return host_handback_ms_per_batch.read(_drain.whole(ctx))
