"""gangq_release_ms_per_boundary: device ms a boundary in everything that
releases, in a batch under ``retry_groups``: the static lists' release
programs (``jit_whatif_release_k<K>``: jobs bound at their arrival, released
whole) over the pass program's runs, and the pass program's ``ksim.release``
(the due releases of re-tried jobs, every earlier pass's row held against the
boundary)."""

from layer_metrics import _gangq, _program_stages, _whatif_release


def read(ctx):
    inside = _gangq.pass_ms(ctx, "ksim.release")
    if inside is None:
        return None
    runs = _program_stages.read(ctx, _gangq.PASS)["runs"]
    static = sum(d for _, d in _whatif_release.runs(ctx)) / 1e6
    return inside + static / runs
