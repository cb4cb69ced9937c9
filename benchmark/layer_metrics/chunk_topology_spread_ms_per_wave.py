"""chunk_topology_spread_ms_per_wave: device ms a wave under
``ksim.filter_score/PodTopologySpread`` (the ``DoNotSchedule`` filter on
``maxSkew``, the ``ScheduleAnyway`` score and its normalize; under
``select_form`` ``two_pass`` the zone feasibility too): op events inside the
chunk program's executions, joined to the program's stage tables
(_stages.py)."""

from layer_metrics import _stages


def read(ctx):
    return _stages.ms_per_wave(ctx, "ksim.filter_score/PodTopologySpread")
