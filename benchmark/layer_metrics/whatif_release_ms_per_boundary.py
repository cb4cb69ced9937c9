"""whatif_release_ms_per_boundary: device time of the what-if release
program over its executions (one a chunk boundary that has releases)."""

from layer_metrics import _whatif_release


def read(ctx):
    got = _whatif_release.runs(ctx)
    return sum(d for _, d in got) / 1e6 / len(got) if got else None
