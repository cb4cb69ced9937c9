"""host_untraced_share: 1 - the union of the program's spans inside a call's
root span (``replay:<n>`` / ``whatif_run:<n>``) over the root's own time, in
%, median over the traced batches: the share of a ``replay()`` / ``run()``
call that is host work no span names. None where the tree writes no root
span."""

import statistics

import trace_reduce
from layer_metrics import _program_spans


def read(ctx):
    got = _program_spans.read(ctx)
    if not got:
        return None
    shares = []
    for b in got["batches"]:
        covered = trace_reduce.merge(
            [(e[1], e[1] + e[2]) for e in b["children"]])
        shares.append(100.0 * (1.0 - sum(e - s for s, e in covered)
                               / max(b["root"][2], 1)))
    return statistics.median(shares)
