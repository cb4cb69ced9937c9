"""backlog_release_ms_per_boundary: device ms a chunk boundary in everything
that releases: the static lists' release programs
(``jit_whatif_release_k<K>``: arrival binds and, from the tail of the
placement buffer, the resident set) and, inside the chunk program, the
``ksim.release`` scope (the re-tried binds' releases, every earlier pass's
row held against the boundary; the fold of the chunk's choices)."""

from layer_metrics import _backlog, _whatif_release


def read(ctx):
    inside = _backlog.seconds(ctx, "ksim.release")
    if not inside:
        return None
    static = sum(d for _, d in _whatif_release.runs(ctx)) / 1e9
    return 1e3 * (inside[0] + static) / inside[1]
