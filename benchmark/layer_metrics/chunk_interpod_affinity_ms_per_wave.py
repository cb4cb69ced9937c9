"""chunk_interpod_affinity_ms_per_wave: device ms a wave under
``ksim.filter_score/InterPodAffinity`` (the required affinity and
anti-affinity filters over the domain and host count rows, the symmetric
check, the score row): op events inside the chunk program's executions,
joined to the program's stage tables (_stages.py)."""

from layer_metrics import _stages


def read(ctx):
    return _stages.ms_per_wave(ctx, "ksim.filter_score/InterPodAffinity")
