"""chunk_unattributed_share: of the op time inside the chunk program's
executions, the % whose instruction is in no stage table or under no
``ksim.`` scope: the check on the join of trace and HLO (_stages.py)."""

from layer_metrics import _stages


def read(ctx):
    got = _stages.read(ctx)
    total = sum(got["seconds"].values()) if got else 0.0
    return 100.0 * got["seconds"].get("", 0.0) / total if total else None
