"""Not a metric: head and tail of each traced batch, seen from the device.
Per ``bench:batch:<i>`` span that lies whole inside the window, the time
from its start to the first chunk-program execution in it and from the
end of the last one to its end, in ms."""

from layer_metrics.chunk_ms_per_wave import CHUNK_PROGRAM
from trace_reduce import WINDOW_SPAN


def heads_and_tails(ctx):
    trace = ctx["trace"]
    w0, w1 = trace.window
    runs = trace.program_runs(CHUNK_PROGRAM)[0]
    out = []
    for n, s, d in trace.host:
        if not WINDOW_SPAN.match(n) or s < w0 or s + d > w1:
            continue
        inside = [(a, a + b) for a, b in runs if a >= s and a + b <= s + d]
        if inside:
            out.append(((inside[0][0] - s) / 1e6,
                        (s + d - inside[-1][1]) / 1e6))
    return out
