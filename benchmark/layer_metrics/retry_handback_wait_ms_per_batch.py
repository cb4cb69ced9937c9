"""retry_handback_wait_ms_per_batch: the program's ``handback_wait`` span
inside ``handback`` of a traced batch with a ``retry_buffer``, in ms: from the
dispatch of the hand-back program (``jit_whatif_handback_retry``) to its
outputs being ready: what is still queued on the device when the host gets
there, then the program. Over the WHOLE traced batch (``_drain.whole``: the
device's trace buffer ends both eviction cells' windows before it)."""

from layer_metrics import _drain, _program_spans


def read(ctx):
    return _program_spans.ms_per_batch(
        _drain.whole(ctx), "handback_wait", inside="handback")
