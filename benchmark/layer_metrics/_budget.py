"""Not a metric: what the ``budget_*`` metrics share. The cell
``borg10k-budget128`` runs ``borg10k-drain128``'s programs with the eviction
program grown (``jit_whatif_evict``, scope ``ksim.evict``; its admission under
``ksim.evict/Budget``), so the readers are ``_drain``'s with two differences:
they count BOUNDARIES by the runs of the eviction program or of the first of
a boundary's two chunk programs (``jit_per_scenario_retry``), never by the
runs of every chunk program (a boundary has had two since PR 47), and the
admission's time is read from the op events inside the eviction program's
runs, joined to the program's stage table by instruction name.

The device's trace buffer overflows inside one batch of this cell as it does
in the drain cell: the device-side readers read the window that is there, the
host-side ones the whole traced batch (``_drain.whole``).

Returns None or nothing, and never raises, where the program has no such
program, scope or span (an older tree) or none ran in the window."""

import bisect
import re

from layer_metrics import _drain, _stages

ADMIT = "ksim.evict/Budget"
MODULE = "jit_whatif_evict"
RETRY_PROGRAM = re.compile(r"^jit_per_scenario_retry\(")
KEY = "budget_admit"


def admit_seconds(ctx):
    """(device seconds under the admission's scope inside the eviction
    program's runs in the window, those runs) or None."""
    if KEY not in ctx:
        ctx[KEY] = _admit_seconds(ctx)
    return ctx[KEY]


def _admit_seconds(ctx):
    trace = ctx["trace"]
    w0, w1 = trace.window
    runs = sorted((s, s + d) for n, s, d in trace.devices[0]["modules"]
                  if _drain.EVICT_PROGRAM.match(n) and s >= w0 and s + d <= w1)
    tables = _stages.stage_tables() if runs else None
    table = (tables or {}).get(MODULE)
    if not table or ADMIT not in set(table.values()):
        return None
    starts = [r[0] for r in runs]
    ns = 0
    for name, s, d in trace.devices[0]["ops"]:
        i = bisect.bisect_right(starts, s) - 1
        if i < 0 or s >= runs[i][1] or _stages.WHILE.match(name):
            continue
        if table.get(_stages.INSTRUCTION.match(name).group(1)) == ADMIT:
            ns += d
    return ns / 1e9, len(runs)


def retry_ms_per_boundary(ctx):
    """Device ms of the retry pass program (``jit_per_scenario_retry``: the
    releases of re-tried binds that are due, the pass over the queue, its
    record) over its runs in the window, one a boundary."""
    trace = ctx["trace"]
    w0, w1 = trace.window
    got = [d for n, s, d in trace.devices[0]["modules"]
           if RETRY_PROGRAM.match(n) and s >= w0 and s + d <= w1]
    return sum(got) / 1e6 / len(got) if got else None
