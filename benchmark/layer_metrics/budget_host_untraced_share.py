"""budget_host_untraced_share: what ``host_untraced_share`` reads from the
program's host spans, in the budgeted drain, over the whole traced batch
(``_drain.whole``)."""

from layer_metrics.drain_host_untraced_share import read  # noqa: F401
