"""gangq_layout_ms_per_boundary: device ms a run of the pass program under
``ksim.retry/Layout``: the queue's jobs laid out from fresh waves (which
entries head a job, the pass's trip count, the table the loop reads its
tiles from)."""

from layer_metrics import _gangq


def read(ctx):
    return _gangq.pass_ms(ctx, "ksim.retry/Layout")
