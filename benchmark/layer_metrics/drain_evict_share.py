"""drain_evict_share: the eviction program's device time as a share of all
the device was busy in the traced window, in %: what moving nodes costs a
batch beside its chunk scans, retry passes, releases and hand-back."""

from layer_metrics import _drain


def read(ctx):
    got = _drain.runs(ctx)
    busy = ctx["trace"].busy_s
    if not got or not busy:
        return None
    return 100.0 * sum(got) / 1e9 / busy
