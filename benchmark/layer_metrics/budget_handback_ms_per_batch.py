"""budget_handback_ms_per_batch: the program's ``handback`` span of the traced
batch in the budgeted drain, in ms: four answers (every task's node, the
boundary of its last bind, the eviction log with each row's kind, the boundary
each node went out) put into order on the device and copied; over the whole
traced batch (``_drain.whole``)."""

from layer_metrics.drain_handback_ms_per_batch import read  # noqa: F401
