"""Not a metric: each traced batch as every chip of the mesh saw it. Per
``bench:batch:<i>`` span that lies whole inside the window and in which
every chip ran a chunk program: the span's start and end, and per chip the
start of its first chunk-program execution in the span and the end of its
last, in ns on the trace's one clock. Nothing where a chip ran none (a tree
that does not shard the batch over the cell's chips)."""

from layer_metrics.chunk_ms_per_wave import CHUNK_PROGRAM
from trace_reduce import WINDOW_SPAN


def batches(ctx):
    trace = ctx["trace"]
    w0, w1 = trace.window
    per_chip = trace.program_runs(CHUNK_PROGRAM)
    out = []
    for n, s, d in trace.host:
        if not WINDOW_SPAN.match(n) or s < w0 or s + d > w1:
            continue
        inside = [[(a, a + b) for a, b in runs if a >= s and a + b <= s + d]
                  for runs in per_chip]
        if inside and all(inside):
            out.append({"start": s, "end": s + d,
                        "first": [runs[0][0] for runs in inside],
                        "last": [runs[-1][1] for runs in inside]})
    return out
