"""idle_unattributed_share: of the idle nanoseconds of the traced window
over EVERY chip of the cell (each chip's own gaps between its op events),
the share whose gap lies inside no program execution on that chip and whose
middle lies under no span of the program but a call's root
(``replay:<n>`` / ``whatif_run:<n>``) or under none at all, in %: the idle
time the record cannot put down to anything the host was doing. Read from
the program's own host spans (``_program_spans.idle``, which prints the
whole table by span on stderr); None where the tree writes no root span."""

from layer_metrics import _program_spans


def read(ctx):
    table = _program_spans.idle(ctx)
    if not table or not sum(table.values()):
        return None
    return (100.0 * (table.get("root", 0) + table.get("none", 0))
            / sum(table.values()))
