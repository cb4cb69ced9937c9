"""chunk_ms_per_wave: device time of the chunk program's executions over
the waves they covered (executions x the engine's chunk_waves). The program
is found by XLA module name: the program has no named_scope yet."""

CHUNK_PROGRAM = r"^jit_(per_scenario\w*|chunk_fn\w*)\("


def read(ctx):
    trace = ctx["trace"]
    runs = trace.program_runs(CHUNK_PROGRAM)
    n = sum(len(r) for r in runs) / len(runs)
    if not n:
        return None
    waves = n * ctx["shape"]["chunk_waves"]
    return trace.program_seconds(CHUNK_PROGRAM) * 1e3 / waves
