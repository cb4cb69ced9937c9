"""gang_txn_ms_per_wave: device ms a wave in the carried transaction of the
pod groups wider than the wave: ``ksim.gang_txn`` (what it adds to every wave:
what the wave's members took added to the group's carried plane, the verdict
so far, the log) and ``ksim.gang_rollback`` (a failed group's plane taken out
of ``used`` again where it closes): op events inside the chunk program's
executions, joined to the program's stage tables (_stages.py), over ALL the
waves of those executions. None where the tree has no such scope."""

from layer_metrics import _stages


def read(ctx):
    got = _stages.ms_per_wave(ctx, "ksim.gang_txn", "ksim.gang_rollback")
    return got or None  # a tree without the scopes sums nothing: left out
