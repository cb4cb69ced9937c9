"""whatif_chunk_filter_score_rest_ms_per_wave: device ms a wave of the
vmapped chunk program under ``ksim.filter_score`` but outside the
InterPodAffinity and PodTopologySpread scopes, which have metrics of their
own (``chunk_interpod_affinity_ms_per_wave``,
``chunk_topology_spread_ms_per_wave``): NodeResourcesFit, TaintToleration,
NodeAffinity and the fused weighted sum. With those two, the reads, the
select, the count planes and the unattributed share it tiles the op time of
a wave (_stages.py)."""

from layer_metrics import _stages

APART = ("ksim.filter_score/InterPodAffinity",
         "ksim.filter_score/PodTopologySpread")


def read(ctx):
    whole = _stages.ms_per_wave(ctx, "ksim.filter_score")
    return None if whole is None else whole - _stages.ms_per_wave(ctx, *APART)
