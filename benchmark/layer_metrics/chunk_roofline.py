"""chunk_roofline: the least time one wave's bytes need at the chip's HBM
peak (roofline.wave_min_ms; memory-bound) over chunk_ms_per_wave, in %."""

import roofline
from layer_metrics import chunk_ms_per_wave


def read(ctx):
    per_wave = chunk_ms_per_wave.read(ctx)
    if not per_wave:
        return None
    sh = ctx["shape"]
    least = roofline.wave_min_ms(
        ctx["device_kind"], scenarios=sh["scenarios_per_chip"],
        nodes=sh["nodes"], resources=sh["resources"],
        wave_width=sh["wave_width"], planes=sh["planes"])
    return 100.0 * least / per_wave
