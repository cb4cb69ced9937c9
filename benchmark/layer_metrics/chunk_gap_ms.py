"""chunk_gap_ms: median idle time on device 0 between consecutive
executions of the chunk program — what the host chunk loop leaves the
device waiting for. Time in which another program runs between two chunks
(the release program) is not idle and is not counted."""

import statistics

from layer_metrics.chunk_ms_per_wave import CHUNK_PROGRAM


def read(ctx):
    trace = ctx["trace"]
    runs = trace.program_runs(CHUNK_PROGRAM)[0]
    gaps = [trace.idle_ns(a[0] + a[1], b[0]) / 1e6
            for a, b in zip(runs, runs[1:])]
    return statistics.median(gaps) if gaps else None
