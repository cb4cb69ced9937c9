"""chunk_commit_ms_per_wave: device ms a wave in the commit
(``ksim.commit``: the bound node's domain, gang rollback mask, ``used``
update, domain and host planes): op events inside the chunk program's
executions, joined to the program's stage tables (_stages.py)."""

from layer_metrics import _stages


def read(ctx):
    return _stages.ms_per_wave(ctx, "ksim.commit")
