"""chunk_corrections_ms_per_wave: device ms a wave in the exact in-wave
corrections from the wave's earlier pods (``ksim.corrections``): op events
inside the chunk program's executions, joined to the program's stage
tables (_stages.py)."""

from layer_metrics import _stages


def read(ctx):
    return _stages.ms_per_wave(ctx, "ksim.corrections")
