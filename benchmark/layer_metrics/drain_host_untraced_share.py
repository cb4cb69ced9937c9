"""drain_host_untraced_share: what ``host_untraced_share`` reads from the
program's host spans, in the drained Borg cell, over the whole traced batch
(``_drain.whole``: the device's trace buffer ends the window inside it)."""

from layer_metrics import _drain, host_untraced_share


def read(ctx):
    return host_untraced_share.read(_drain.whole(ctx))
