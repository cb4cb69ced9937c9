"""release_device_ms_per_boundary: device time of the replay's release
program over its executions, found by XLA module name."""

RELEASE_PROGRAM = r"^jit_release_subtract\("


def read(ctx):
    trace = ctx["trace"]
    runs = trace.program_runs(RELEASE_PROGRAM)
    n = sum(len(r) for r in runs) / len(runs)
    if not n:
        return None
    return trace.program_seconds(RELEASE_PROGRAM) * 1e3 / n
