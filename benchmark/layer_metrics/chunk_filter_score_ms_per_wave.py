"""chunk_filter_score_ms_per_wave: device ms a wave in the fused Filter +
Score section (``ksim.filter_score``) and every plugin's scope beneath it:
op events inside the chunk program's executions, joined to the program's
stage tables (_stages.py, which prints the per-plugin split on stderr)."""

from layer_metrics import _stages


def read(ctx):
    return _stages.ms_per_wave(ctx, "ksim.filter_score")
