"""chunk_reads_ms_per_wave: device ms a wave in the wave-start reads of the
wave step (``ksim.reads``), the slot gathers with the scan's own per-wave
slicing of them (``ksim.gather``) and the per-chunk derived tables
(``ksim.derive``): op events inside the chunk program's executions, joined
to the program's stage tables (_stages.py)."""

from layer_metrics import _stages


def read(ctx):
    return _stages.ms_per_wave(ctx, "ksim.reads", "ksim.gather", "ksim.derive")
