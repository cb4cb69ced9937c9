"""drain_host_events_ms_per_boundary: the program's ``host_events`` span, ms a
span, over the traced batches: what the host does for a boundary's events on
the device path (the plans' leaving and returning nodes are staged on the
device once an engine; a boundary picks its two arrays and builds the
eviction program's arguments)."""

from layer_metrics import _drain, _program_spans


def read(ctx):
    got = _program_spans.read(_drain.whole(ctx))
    if not got:
        return None
    spans = [e[2] for b in got["batches"] for e in b["children"]
             if e[0] == "host_events"]
    return sum(spans) / 1e6 / len(spans) if spans else None
