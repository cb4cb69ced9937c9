"""Not a metric: what the ``gangq_*`` metrics share. A boundary of a batch
under ``retry_groups`` is the release programs (``jit_whatif_release_k<K>``),
the pass program ``jit_per_scenario_retry`` (its due releases under
``ksim.release``; under ``ksim.retry`` the one gather, ``ksim.retry/Layout``
the queue's jobs laid out from fresh waves, ``ksim.retry/Close`` a pass wave's
tile and its job's verdict, the wave steps, ``ksim.retry/Record``) and the
arrival program ``jit_per_scenario_arrivals`` (``ksim.retry/Join``: the
chunk's rolled-back jobs joining whole, inside the upkeep's ``ksim.retry``).
**A boundary is a run of ONE named module** (``_program_stages``): nothing is
divided by runs of any chunk program.

The device's trace buffer may end the window inside the one traced batch (about
28,000 wave steps fit, PERF.md §7): the device-side metrics read the window
that is there, per run; the host-side ones the whole batch (``_drain.whole``).

``pass_waves(ctx)``: the wave steps the traced batches' passes EXECUTED and
how many passes, from the program's ``retry_pass_waves`` mark (a counter in
the trace's host plane). Returns None, and never raises, where the tree has no
such scope, mark or program (an older tree) or none ran in the window."""

from layer_metrics import _drain, _program_spans, _program_stages

PASS = "jit_per_scenario_retry"
ARRIVALS = "jit_per_scenario_arrivals"
SCOPES = ("ksim.retry/Layout", "ksim.retry/Close", "ksim.retry/Join")
MARK = "retry_pass_waves"


def under_groups(ctx):
    """The pass program of this run carries the job layout's scope."""
    got = _program_stages.read(ctx, PASS)
    return bool(got) and SCOPES[0] in got["paths"]


def pass_ms(ctx, stage):
    return (_program_stages.ms_per_run(ctx, PASS, stage)
            if under_groups(ctx) else None)


def pass_waves(ctx):
    """(executed wave steps, passes) summed over the traced batches, or None."""
    got = _program_spans.read(_drain.whole(ctx))
    marks = [e[4] for b in (got or {}).get("batches", ())
             for e in b["children"] if e[0] == MARK]
    try:
        waves = sum(int(m["waves"]) for m in marks)
        passes = sum(int(m["passes"]) for m in marks)
    except (KeyError, TypeError, ValueError):
        return None
    return (waves, passes) if passes else None
