"""gangq_record_ms_per_boundary: device ms a boundary in the record of what a
pass bound, in a batch under ``retry_groups``: the pass program's
``ksim.retry/Record`` (after the pass's loop: the release boundary of each
bind, the queue's counters) and the whole of the program between the two loop
programs, ``jit_whatif_record`` (what the pass bound brought to the front in
queue order, the rows a release rewinds read by task id, all of it APPENDED to
the record's log: one window a scenario), a run of each a boundary."""

from layer_metrics import _gangq, _program_stages

RECORD = "jit_whatif_record"


def read(ctx):
    inside = _gangq.pass_ms(ctx, "ksim.retry/Record")
    got = _program_stages.read(ctx, RECORD)
    if inside is None or not got:
        return None
    return inside + 1e3 * got["op_seconds"] / got["runs"]
