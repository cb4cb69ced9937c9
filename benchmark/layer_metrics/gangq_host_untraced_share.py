"""gangq_host_untraced_share: what ``host_untraced_share`` reads from the
program's host spans, in the job-queue cell, over the whole traced batch
(``_drain.whole``), under a name of its own because the accepted metric lists
its cells and cannot be edited (_program_spans.py)."""

from layer_metrics import _drain, _gangq, host_untraced_share


def read(ctx):
    if _gangq.pass_waves(ctx) is None:  # no batch under retry_groups
        return None
    return host_untraced_share.read(_drain.whole(ctx))
