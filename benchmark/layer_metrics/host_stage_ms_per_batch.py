"""host_stage_ms_per_batch: the program's ``stage`` span of a traced batch,
in ms, median over batches: a ``replay()`` / ``run()`` call from its start to
its first chunk's boundary work, on the host's side and the trace's clock
(``stage_ms_per_batch`` and ``mesh_stage_ms_per_batch`` time the same
stretch from the device's side, up to the first chunk program). None where
the tree writes no root span."""

from layer_metrics import _program_spans


def read(ctx):
    return _program_spans.ms_per_batch(ctx, "stage")
