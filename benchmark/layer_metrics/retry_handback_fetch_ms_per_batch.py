"""retry_handback_fetch_ms_per_batch: the program's ``handback_fetch`` spans
inside ``handback`` of a traced batch with a ``retry_buffer``, summed, in ms:
one span an answer brought to the host (``assignments``, ``bind_boundary``,
and where timelines evict ``eviction_log``, whose span holds the program that
turns the log too, and under budgets ``node_out_at``). Each span carries
``answer`` and ``bytes`` as its stats; per answer the bytes, ms and GB/s are
printed on stderr, no ledger metric. Over the WHOLE traced batch
(``_drain.whole``)."""

from layer_metrics import _drain, _program_spans

SPAN = "handback_fetch"


def read(ctx):
    whole = _drain.whole(ctx)
    ms = _program_spans.ms_per_batch(whole, SPAN, inside="handback")
    if ms is None:
        return None
    for b in _program_spans.read(whole)["batches"]:
        for e in b["children"]:
            size = e[4].get("bytes")
            if e[0] == SPAN and isinstance(size, int) and e[2]:
                _program_spans.say(
                    f"{SPAN} {e[4].get('answer')} {size} bytes in "
                    f"{e[2] / 1e6:.3f} ms: {size / e[2]:.3f} GB/s")
    return ms
