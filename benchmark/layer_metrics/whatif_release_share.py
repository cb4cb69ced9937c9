"""whatif_release_share: device seconds in the what-if release program
over the traced window, in %."""

from layer_metrics import _whatif_release


def read(ctx):
    got = _whatif_release.runs(ctx)
    if not got:
        return None
    return 100.0 * sum(d for _, d in got) / 1e9 / ctx["trace"].window_s
