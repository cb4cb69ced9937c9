"""Not a metric: what the ``backlog_*`` metrics share. The retry pass runs
inside the chunk program (``jit_per_scenario_retry``) under the stage scope
``ksim.retry``: its scan over the queue, whose wave-step instructions the
program's stage tables file under ``ksim.retry/<the step's own stage>``, and
the queue's upkeep (the sort, the record). The releases of re-tried binds
run in the same program under ``ksim.release``; the static lists' in the
release programs ``jit_whatif_release_k<K>``.

Returns None, and never raises, where the program has no such scope (an
older tree) or no chunk program ran in the window."""

from layer_metrics import _stages

RETRY = "ksim.retry"


def boundaries(ctx, got):
    """Chunk calls in the window: one a boundary."""
    return got["waves"] / ctx["shape"]["chunk_waves"]


def seconds(ctx, stage):
    """(device seconds under ``stage`` in the window's chunk calls, those
    calls) or None."""
    got = _stages.read(ctx)
    if not got:
        return None
    s = sum(v for path, v in got["seconds"].items()
            if path == stage or path.startswith(stage + "/"))
    return s, boundaries(ctx, got)


def retry_ms_per_boundary(ctx):
    got = seconds(ctx, RETRY)
    if not got or not got[0]:
        return None
    return 1e3 * got[0] / got[1]
