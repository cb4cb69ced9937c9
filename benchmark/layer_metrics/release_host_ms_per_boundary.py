"""release_host_ms_per_boundary: median duration of the program's
``host_mirror`` spans in the window: what a chunk boundary's completions
cost the host (the scan for due releases, the delta build, the release
program's dispatch)."""

import statistics


def read(ctx):
    trace = ctx["trace"]
    w0, w1 = trace.window
    spans = [d / 1e6 for n, s, d in trace.host
             if n == "host_mirror" and w0 <= s < w1]
    return statistics.median(spans) if spans else None
