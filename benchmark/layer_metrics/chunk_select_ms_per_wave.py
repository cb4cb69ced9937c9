"""chunk_select_ms_per_wave: device ms a wave in the pick (``ksim.select``:
extrema, tie-break to the lowest index, pack-select): op events inside the
chunk program's executions, joined to the program's stage tables
(_stages.py)."""

from layer_metrics import _stages


def read(ctx):
    return _stages.ms_per_wave(ctx, "ksim.select")
