"""chunk_count_planes_ms_per_wave: device ms a wave in the upkeep of the
count planes: ``ksim.corrections`` (what slots j < k of the wave add to the
counts slot k reads) and ``ksim.commit`` (the wave-end update of ``used`` and
of the domain and host count planes): op events inside the chunk program's
executions, joined to the program's stage tables (_stages.py)."""

from layer_metrics import _stages


def read(ctx):
    return _stages.ms_per_wave(ctx, "ksim.corrections", "ksim.commit")
